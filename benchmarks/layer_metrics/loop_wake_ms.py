"""Median of the sampled cycles' ``emit.wake``: from ``backend.step``'s return
stamp on the executor thread (the end of ``step.unpack``) to the serving loop
running again on the event loop (its own ``returned`` stamp): the hand-back
between the two threads, the first part of ``step.emit``.  None on a program
without the span."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.engine_parked_share import inside_cycles

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    xs = [c["emit.wake"] for c in inside_cycles(run) if "emit.wake" in c]
    return median(xs) if xs else None
