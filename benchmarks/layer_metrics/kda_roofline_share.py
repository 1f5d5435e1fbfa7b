"""The KDA recurrence's share of its own roofline: least time for the slice's
recurrences (harness/roofline_bailing.py ``kda_least_seconds``: each fed
row's state read and written once a KDA layer, a token's operands, 7 x d_k x
d_v operations a token and head: counted from the step's ROWS, whatever form
computes them) over the device seconds of the ``kda_step`` events
(``kda_busy_share.kernel_seconds``).  None without those events."""
from benchmarks.harness import roofline_bailing
from benchmarks.layer_metrics.bailing_step_roofline_share import state_steps
from benchmarks.layer_metrics.kda_busy_share import kernel_seconds
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(run):
    ds = runs_of(run)
    steps = state_steps(run)
    spent = kernel_seconds(run)
    if not ds or not steps or not spent or run.get("peaks") is None:
        return None
    least = [roofline_bailing.kda_least_seconds(run["config"], s["rows"], run["peaks"])[0]
             for s in steps]
    return 100.0 * (sum(least) / len(least)) / (spent / len(ds))
