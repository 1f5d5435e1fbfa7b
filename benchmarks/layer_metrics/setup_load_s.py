"""Seconds of JAX's backend-compile events over the programs of the start-up
record (``startup.program.load``): on a hit of the persistent cache the key's
hash, the executable's read, its deserialisation and its load, which grow with
the executable's bytes; on a miss the compile (``setup_cache_hit_share`` tells
which).  None without the record (``setup_serving_s.py``)."""
from benchmarks.layer_metrics import setup_serving_s

LAYER = "model programs"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return setup_serving_s.seconds(run, "startup.program.load")
