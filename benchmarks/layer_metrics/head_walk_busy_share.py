"""The by-head attention walk's share of the device's busy time in the traced
slice: the seconds of the events named ``head_walk`` (the Pallas kernel that
walks a group of tiles over K and V pages by head,
``cordum_tpu/models/head_walk.py``: one event a group of tiles, layer and
step) among the heaviest operations of the trace, over busy seconds.  None
when the trace holds no such event among its ten heaviest (a latent arena, a
program without the kernel, or a cell where the walk is a small thing)."""
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"

#: the kernel's name in a device trace (``head_walk.KERNEL_NAME``)
OP_NAME = "head_walk"


def read(run):
    trace = run.get("trace") or {}
    spent = sum(sec for name, sec in trace.get("device_ops") or [] if OP_NAME in name)
    busy = trace.get("busy_s")
    return 100.0 * spent / busy if spent and busy else None
