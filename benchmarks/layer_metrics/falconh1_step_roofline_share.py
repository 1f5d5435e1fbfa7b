"""Least time the chip could take for the traced slice's steps by the
falcon_h1 family's OWN count (harness/roofline_falconh1.py: every layer
matrix once, the head slice once if a position is sampled, each fed row's
state and tail read and written once a layer, K and V read up to the row's
position and the new ones written) over the device time the ragged program
took: mean least time per step / mean device time per execution, as
``bailing_step_roofline_share`` does with that family's count.  None on a run
of another family (its file names no mixer) or of a program whose steps carry
no state slots."""
from benchmarks.families import afmoe
from benchmarks.harness import roofline_falconh1
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def mixer_steps(run, part="slice"):
    """The noted steps of a run of this family (the sparse families' one
    ``STEPS`` list, which this family's tap feeds too); none of any other."""
    if "mamba_d_state" not in run["config"]:
        return []
    return [s for s in afmoe.steps_in(run, part) if "state_slots" in s]


def read(run):
    ds = runs_of(run)
    steps = mixer_steps(run)
    if not ds or not steps or run.get("peaks") is None:
        return None
    least = [roofline_falconh1.step_least_seconds(run["config"], s["rows"], run["peaks"])[0]
             for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(ds) / len(ds))
