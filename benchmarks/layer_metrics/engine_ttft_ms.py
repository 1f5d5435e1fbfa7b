"""Median of ``ServingStats.ttft_seconds`` over the window: engine submit to
first sampled token (the wait for a slot plus prefill) on the host clock
inside the engine; not the client's TTFT."""
from benchmarks.harness.stats import median

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"


def read(run):
    xs = run["ttft_seconds"]
    return 1e3 * median(xs) if xs else None
