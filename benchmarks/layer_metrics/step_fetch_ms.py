"""Median of the sampled cycles' ``wait.fetch``: from the step's result being
ready on the device (the stamp behind ``block_until_ready``) to its tokens as
a numpy array on the host (``np.asarray``: the copy started at dispatch), the
end of ``step.wait``: the result's way back.  None on a program without the
span."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.engine_parked_share import inside_cycles

LAYER = "serving backend"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    xs = [c["wait.fetch"] for c in inside_cycles(run) if "wait.fetch" in c]
    return median(xs) if xs else None
