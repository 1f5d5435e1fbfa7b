"""Least time the chip could take for the traced slice's steps by the mellum
family's OWN count (harness/roofline_mellum.py: attention and router once,
each touched expert once, window layers' K and V up to the window, full
layers' up to the row, the whole head once if a position is sampled) over the
device time the ragged program took: mean least time per step / mean device
time per execution, as ``afmoe_step_roofline_share`` does with that family's
count.  None on a run of another family (its file names no rotation a kind of
layer) or of a program without the expert counters."""
from benchmarks.families import afmoe
from benchmarks.harness import roofline_mellum
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    ds = runs_of(run)
    if "rope_parameters" not in run["config"] or run.get("peaks") is None:
        return None
    steps = afmoe.steps_in(run, "slice")
    if not ds or not steps:
        return None
    least = [roofline_mellum.step_least_seconds(run["config"], s["rows"], s["counters"],
                                                run["peaks"])[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(ds) / len(ds))
