"""Median of the sampled ``step.wait`` spans: ``np.asarray`` of the result, that
is the program running on the device and the copy of its result to the host."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.step_cycle_ms import durations_ms

LAYER = "serving backend"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    xs = durations_ms(run, "step.wait")
    return median(xs) if xs else None
