"""The state-space recurrence's share of its own roofline: least time for the
slice's recurrences (harness/roofline_falconh1.py ``ssd_least_seconds``: each
fed row's state read and written once a layer, a token's x, B, C, dt and y, 5 x
heads x d_head x d_state operations a token and layer: counted from the step's
ROWS, whatever form computes them) over the device seconds of the ``ssd_step``
events (``ssd_busy_share.kernel_seconds``).  None without those events."""
from benchmarks.harness import roofline_falconh1
from benchmarks.layer_metrics.falconh1_step_roofline_share import mixer_steps
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of
from benchmarks.layer_metrics.ssd_busy_share import kernel_seconds

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(run):
    ds = runs_of(run)
    steps = mixer_steps(run)
    spent = kernel_seconds(run)
    if not ds or not steps or not spent or run.get("peaks") is None:
        return None
    least = [roofline_falconh1.ssd_least_seconds(run["config"], s["rows"], run["peaks"])[0]
             for s in steps]
    return 100.0 * (sum(least) / len(least)) / (spent / len(ds))
