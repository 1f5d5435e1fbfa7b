"""Live tokens per step against the flat buffer: sum of fed tokens (seen by
``backend.on_step``) / (steps x max_batch_tokens).  The rest of the buffer
is padding, which the program still computes."""
LAYER = "serving engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    steps = run["steps"]
    if not steps:
        return None
    live = sum(n for _, rows, _ in steps for n, _, _, _ in rows)
    return 100.0 * live / (len(steps) * run["max_batch_tokens"])
