"""Median, per sampled cycle, of ``step.pack`` + ``step.dispatch``: the host
arrays built, the five transfers and the call of the jitted program until it
returns (asynchronously; a wait for the device lock would show here)."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.step_cycle_ms import cycles

LAYER = "serving backend"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    xs = [c["step.pack"] + c["step.dispatch"] for c in cycles(run)]
    return median(xs) if xs else None
