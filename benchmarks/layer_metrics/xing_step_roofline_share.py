"""Least time the chip could take for the traced slice's steps by the xing
family's OWN count (harness/roofline_xing.py: A.X-K1's step as
``roofline_mla`` counts it, unrouted weights once, each touched expert once,
each row's latent once a layer, the head once if a position is sampled, plus
the hyper-connected stream's maps in their fused form) over the device time
the ragged program took: mean least time per step / mean device time per
execution, as ``mla_step_roofline_share`` does with that family's count.  None
on a run of another family (its file has no ``hc_mult``) or of a program
without the expert counters."""
from benchmarks.families import axk1
from benchmarks.harness import roofline_xing
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    ds = runs_of(run)
    if "hc_mult" not in run["config"] or run.get("peaks") is None:
        return None
    steps = axk1.steps_in(run, "slice")
    if not ds or not steps:
        return None
    least = [roofline_xing.step_least_seconds(run["config"], s["rows"], s["counters"],
                                              run["peaks"])[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(ds) / len(ds))
