"""Peak device memory after the window: ``peak_bytes_in_use`` of ``memory_stats()``
/ 1e9 (weights, both arenas, the step's temporaries, any undonated arena
copy)."""
LAYER = "device"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    peak = (run.get("memory") or {}).get("peak_bytes_in_use")
    return peak / 1e9 if peak else None
