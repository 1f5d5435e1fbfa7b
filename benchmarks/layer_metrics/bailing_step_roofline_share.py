"""Least time the chip could take for the traced slice's steps by the bailing
family's OWN count (harness/roofline_bailing.py: every unrouted matrix once,
each touched expert once, each fed row's KDA state read and written once a
KDA layer, the latent once a latent layer, the head over the slice) over the
device time the ragged program took: mean least time per step / mean device
time per execution, as ``mla_step_roofline_share`` does with that family's
count.  None on a run of another family (its file names no kept layers) or of
a program whose steps carry no state slots."""
from benchmarks.families import bailing
from benchmarks.harness import roofline_bailing
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def state_steps(run, part="slice"):
    """The noted steps of a run of this family; none of any other."""
    if "kept_layers" not in run["config"]:
        return []
    return [s for s in bailing.steps_in(run, part) if "state_slots" in s]


def read(run):
    ds = runs_of(run)
    steps = state_steps(run)
    if not ds or not steps or run.get("peaks") is None:
        return None
    least = [roofline_bailing.step_least_seconds(run["config"], s["rows"], s["counters"],
                                                 run["peaks"])[0] for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(ds) / len(ds))
