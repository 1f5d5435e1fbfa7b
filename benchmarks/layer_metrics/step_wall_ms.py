"""Median of ``ServingStats.step_seconds`` over the window: host wall round the
whole ``backend.step`` call, which ends in the copy of the result to the
host, so a true step wall (not device time)."""
from benchmarks.harness.stats import median

LAYER = "serving backend"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(run):
    xs = run["step_seconds"]
    return 1e3 * median(xs) if xs else None
