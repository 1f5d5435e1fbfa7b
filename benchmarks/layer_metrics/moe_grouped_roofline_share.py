"""The grouped expert products' share of their own roofline, whatever
implements them: least time for the slice's products (touched experts'
weights once, the assignments' rows; harness/roofline_afmoe.py, the count
``moe_experts_roofline_share`` holds) over the device time of the operations
named ``ragged-dot*`` (``jax.lax.ragged_dot``) PLUS those named
``expert_mlp*`` (the Pallas kernel of ``cordum_tpu/models/expert_mlp.py``),
found among the heaviest operations of the trace.  A program holds one form
or the other, so on a tree without the kernel this reads what
``moe_experts_roofline_share`` reads.  None when neither is among them."""
from benchmarks.families import afmoe
from benchmarks.harness import roofline_afmoe
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"

#: how the grouped products are named in a device trace: XLA's own
#: (tests/data/afmoe_device_ops.json) and the kernel's (``expert_mlp.KERNEL_NAME``)
OP_PREFIXES = ("ragged-dot", "expert_mlp")


def products_seconds(run):
    ops = (run.get("trace") or {}).get("device_ops") or []
    return sum(sec for name, sec in ops if name.startswith(OP_PREFIXES))


def read(run):
    ds = runs_of(run)
    steps = afmoe.steps_in(run, "slice")
    spent = products_seconds(run)
    if not ds or not steps or not spent or run.get("peaks") is None:
        return None
    least = [roofline_afmoe.experts_least_seconds(run["config"], s["counters"], run["peaks"])[0]
             for s in steps]
    return 100.0 * (sum(least) / len(least)) / (spent / len(ds))
