"""The expert products' share of their own roofline: least time for the
slice's grouped products (touched experts' weights once, the assignments'
rows; harness/roofline_afmoe.py) over the device time of the operations
``jax.lax.ragged_dot`` became, found by name among the ten heaviest
operations of the trace.  None when they are not among the ten."""
from benchmarks.families import afmoe
from benchmarks.harness import roofline_afmoe
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

#: how the grouped products are named in a device trace (tests/data/afmoe_device_ops.json)
OP_PREFIX = "ragged-dot"


def products_seconds(run):
    ops = (run.get("trace") or {}).get("device_ops") or []
    return sum(sec for name, sec in ops if name.startswith(OP_PREFIX))


def read(run):
    ds = runs_of(run)
    steps = afmoe.steps_in(run, "slice")
    spent = products_seconds(run)
    if not ds or not steps or not spent or run.get("peaks") is None:
        return None
    least = [roofline_afmoe.experts_least_seconds(run["config"], s["counters"], run["peaks"])[0]
             for s in steps]
    return 100.0 * (sum(least) / len(least)) / (spent / len(ds))
