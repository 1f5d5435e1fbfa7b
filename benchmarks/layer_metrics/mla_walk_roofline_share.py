"""The attention walks' share of their own roofline: least time for the
slice's walks (harness/roofline_mla.py: 139 kFLOP a query slot, visible key
and layer in the absorbed form; each row's latent read once a row and layer)
over the walks' device seconds (``mla_walk_busy_share.walk_seconds``: half the
``while s32[]`` line, so this share reads HIGH by what the outer loops add).
None when the walks are not among the ten heaviest operations."""
from benchmarks.families import axk1
from benchmarks.harness import roofline_mla
from benchmarks.layer_metrics.mla_walk_busy_share import walk_seconds
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(run):
    ds = runs_of(run)
    steps = axk1.steps_in(run, "slice")
    spent = walk_seconds(run)
    if not ds or not steps or not spent or run.get("peaks") is None:
        return None
    least = [roofline_mla.walk_least_seconds(run["config"], s["rows"], run["peaks"])[0]
             for s in steps]
    return 100.0 * (sum(least) / len(least)) / (spent / len(ds))
