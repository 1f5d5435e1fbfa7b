"""Least time the chip could take for the traced slice's steps by the axk1
family's OWN count (harness/roofline_mla.py: unrouted weights once, each
touched expert once, each row's latent once a layer, the walks in absorbed
form, the head over the slice) over the device time the ragged program took:
mean least time per step / mean device time per execution, as
``afmoe_step_roofline_share`` does with that family's count."""
from benchmarks.families import axk1
from benchmarks.harness import roofline_mla
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    ds = runs_of(run)
    steps = axk1.steps_in(run, "slice")
    if not ds or not steps or run.get("peaks") is None or "kv_lora_rank" not in run["config"]:
        return None
    least = [roofline_mla.step_least_seconds(run["config"], s["rows"], s["counters"],
                                             run["peaks"])[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(ds) / len(ds))
