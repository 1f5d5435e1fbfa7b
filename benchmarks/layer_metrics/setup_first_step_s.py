"""Seconds of ``startup.first_step``: from the end of the ragged program's load
to its first result on the host (step 0's ``dispatch`` + ``wait`` less the
program events inside them): the first execution, the arenas' donation, the
first transfer.  None without the start-up record (``setup_serving_s.py``)."""
from benchmarks.layer_metrics import setup_serving_s

LAYER = "serving backend"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return setup_serving_s.seconds(run, "startup.first_step")
