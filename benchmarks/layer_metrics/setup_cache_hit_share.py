"""Of the compile requests JAX made inside the backend's own calls until the
first sampled token (the start-up record's root: ``programs``), the share the
persistent compilation cache served (``cache_hits``): 100 on a warm checkout,
near 0 on its first run, which is what tells a cold ``setup_s`` from a warm
one.  None without the record (``setup_serving_s.py``) or with no request."""
from benchmarks.layer_metrics import setup_serving_s

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    rows = setup_serving_s.record(run)
    if rows is None or not rows[-1]["attrs"].get("programs"):
        return None
    return 100.0 * rows[-1]["attrs"]["cache_hits"] / rows[-1]["attrs"]["programs"]
