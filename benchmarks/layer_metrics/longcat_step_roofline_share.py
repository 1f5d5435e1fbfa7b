"""Least time the chip could take for the traced slice's steps by the longcat
family's OWN count (harness/roofline_longcat.py: both sublayers' matrices, both
dense FFNs and the router once a layer, each touched real expert once, each
row's latent once a sublayer, the walks in absorbed form, identity picks one
multiply-add a number, the head over the slice) over the device time the
ragged program took: mean least time per step / mean device time per
execution, as ``mla_step_roofline_share`` does with that family's count.
None on a program whose steps carry no identity-pick counter."""
from benchmarks.families import longcat
from benchmarks.harness import roofline_longcat
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    ds = runs_of(run)
    steps = [s for s in longcat.steps_in(run, "slice") if "moe_zero_assignments" in s["counters"]]
    if not ds or not steps or run.get("peaks") is None or "zero_expert_num" not in run["config"]:
        return None
    least = [roofline_longcat.step_least_seconds(run["config"], s["rows"], s["counters"],
                                                 run["peaks"])[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(ds) / len(ds))
