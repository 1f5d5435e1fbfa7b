"""The hyper-connected stream's maps' share of their own roofline: least time
for the slice's maps (harness/roofline_xing.py ``mhc_least_seconds``: the
maps' operations for the step's LIVE tokens at the chip's peak, or each
sublayer's ``phi`` once from HBM, whichever takes longer; the stream's own
bytes are NOT counted, because a serving buffer's stream stays in VMEM from
one kernel to the next and a count that had them read 144 % on the chip) over
the device seconds of the ``mhc_*`` events
(``mhc_busy_share.kernel_seconds``).  A floor no fusion can pass, and far
below what float32 vector work reaches: it reads a few per cent.  None without
both kernels' events, on another family's run, or on a slice that noted no
step."""
from benchmarks.families import axk1
from benchmarks.harness import roofline_xing
from benchmarks.layer_metrics.mhc_busy_share import kernel_seconds
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(run):
    ds = runs_of(run)
    spent = kernel_seconds(run)
    if not ds or not spent or run.get("peaks") is None or "hc_mult" not in run["config"]:
        return None
    steps = axk1.steps_in(run, "slice")
    if not steps:
        return None
    least = [roofline_xing.mhc_least_seconds(run["config"], s["rows"], run["peaks"])[0]
             for s in steps]
    return 100.0 * (sum(least) / len(least)) / (spent / len(ds))
