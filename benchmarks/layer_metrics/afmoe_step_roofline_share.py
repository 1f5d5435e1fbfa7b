"""Least time the chip could take for the traced slice's steps by the afmoe
family's OWN count (harness/roofline_afmoe.py: non-routed weights once, each
touched expert once, window layers' K and V up to the window) over the device
time the ragged program took: mean least time per step / mean device time per
execution, as ``step_roofline_share`` does with the llama count."""
from benchmarks.families import afmoe
from benchmarks.harness import roofline_afmoe
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    ds = runs_of(run)
    steps = afmoe.steps_in(run, "slice")
    if not ds or not steps or run.get("peaks") is None:
        return None
    least = [roofline_afmoe.step_least_seconds(run["config"], s["rows"], s["counters"],
                                               run["peaks"])[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(ds) / len(ds))
