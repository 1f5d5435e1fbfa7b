"""Median over the sampled cycles of ``step.wait`` less ``wait.fetch``: from
the hand-over of the step's program (the end of ``step.dispatch``) to its
result being ready ON THE DEVICE (``block_until_ready`` returned on the
executor thread): the launch and the program.  Held against
``ragged_step_device_ms`` (the program alone, on the device's clock) it says
what stands inside ``backend.step`` beside the program, on ONE sample of
cycles.  None on a program without ``wait.fetch``."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.engine_parked_share import inside_cycles

LAYER = "serving backend"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    xs = [c["step.wait"] - c["wait.fetch"] for c in inside_cycles(run) if "wait.fetch" in c]
    return median(xs) if xs else None
