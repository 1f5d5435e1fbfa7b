"""The attention walks' share of the device's busy time in the traced slice.

A walk is a loop over the live groups of tiles whose body is a loop over a
group's blocks; in a device trace both are events named ``while s32[]``
(``trace_reduce.op_kind``), the inner one INSIDE the outer one, so the line of
that name among the heaviest operations sums (outer + inner).  The outer loop
is all but its inner loops (a group's trip starts and ends with a slice and an
update), so the walks' seconds are taken as HALF that line: a lower bound,
short by what the outer loops do between their inner ones (PERF.md section
7 (b)).  ``walk_seconds`` gives the seconds to the roofline reader too; the
experts' seconds beside them are ``moe_experts_roofline_share``'s
``products_seconds``.  None when the walks are not among the ten heaviest."""
from benchmarks.families import axk1

LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"

#: how the walks' loops are named in a device trace (tests/data/axk1_device_ops.json)
OP_NAME = "while s32[]"


def walk_seconds(run):
    """The slice's walks, seconds; None on a run of another family (its
    ``while`` loops are other walks, read by nothing)."""
    if not axk1.steps_in(run, "slice") or "kv_lora_rank" not in run["config"]:
        return None
    ops = (run.get("trace") or {}).get("device_ops") or []
    line = sum(sec for name, sec in ops if name == OP_NAME)
    return line / 2 if line else None


def read(run):
    spent = walk_seconds(run)
    busy = (run.get("trace") or {}).get("busy_s")
    return 100.0 * spent / busy if spent and busy else None
