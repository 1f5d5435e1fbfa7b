"""Seconds Python spent tracing the backend's jitted programs and JAX lowering
them to MLIR, summed over the programs of the start-up record
(``startup.program.trace`` + ``startup.program.lower``, from JAX's own events;
nested traces count once): the part of set-up that grows with an unrolled
layer loop and a larger walk body, cache hit or not.  None without the record
(``setup_serving_s.py``)."""
from benchmarks.layer_metrics import setup_serving_s

LAYER = "model programs"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return setup_serving_s.seconds(run, "startup.program.trace", "startup.program.lower")
