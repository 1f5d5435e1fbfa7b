"""Seconds of ``startup.compute``: ``TPUCompute.__init__`` (``worker/handlers.py``),
the mesh and the embedder, which is built whether a request uses it or not.
From the start-up record (``setup_serving_s.py``); None without it."""
from benchmarks.layer_metrics import setup_serving_s

LAYER = "worker runtime"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return setup_serving_s.seconds(run, "startup.compute")
