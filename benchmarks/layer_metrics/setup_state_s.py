"""Seconds of ``startup.state``: ``ServingBackend._ensure``, the weights placed
(``startup.weights``, to ``block_until_ready``) and the zeroed page arenas
(``startup.arenas``).  From the start-up record (``setup_serving_s.py``); None
without it."""
from benchmarks.layer_metrics import setup_serving_s

LAYER = "serving backend"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return setup_serving_s.seconds(run, "startup.state")
