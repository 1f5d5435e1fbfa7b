"""Of the prompt tokens admitted in the window, the share whose prefill was
skipped because a finished turn's pages were found in the prefix cache:
``prefix_hit_tokens / (prefix_hit_tokens + prefill_tokens)`` of ``ServingStats``
over the window.  Near the later turns' share of all prompt tokens where
sessions run to their end (63 % in ``docsessions-open``); sessions cut by the
close lower it.  The harness's ``stats_delta`` leaves ``prefix_hit_tokens``
out, so the family's tap notes the engine's running counters a step
(``families/axk1.py``); None on a run of a family without the tap."""
from benchmarks.families import axk1

LAYER = "serving engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    steps = [s for s in axk1.steps_in(run) if "prefix_hit_tokens" in s]
    if len(steps) < 2:
        return None
    hit = steps[-1]["prefix_hit_tokens"] - steps[0]["prefix_hit_tokens"]
    fed = steps[-1]["prefill_tokens"] - steps[0]["prefill_tokens"]
    return 100.0 * hit / (hit + fed) if hit + fed else None
