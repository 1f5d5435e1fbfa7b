"""Median of the sampled ``step.assemble`` spans: ``_admit``, cancellations,
draft planning, copy-on-write, ``_assemble`` and the hand-over to the executor
thread, up to the backend's entry stamp."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.step_cycle_ms import durations_ms

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    xs = durations_ms(run, "step.assemble")
    return median(xs) if xs else None
