"""Median, per sampled cycle, of ``step.unpack`` + ``step.emit``: the result
list and ``on_step``, the wake-up of the loop, the engine's result loop, the
token publishes (on the loopback bus the gateway's relay to its taps is
delivered inside them), retires and the yield to the event loop."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.step_cycle_ms import cycles

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    xs = [c["step.unpack"] + c["step.emit"] for c in cycles(run)]
    return median(xs) if xs else None
