"""The state-space recurrence's share of the device's busy time in the traced
slice: the seconds of the events named ``ssd_step`` (the Pallas kernel that
advances every fed row's state, ``cordum_tpu/models/ssd.py``: one event a
layer and step) among the heaviest operations of the trace, over busy seconds.
``kernel_seconds`` gives the seconds to the roofline reader too.  None when
the trace holds no such event among its ten heaviest (another family, or a
program without the kernel)."""
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"

#: the kernel's name in a device trace (``ssd.KERNEL_NAME``)
OP_NAME = "ssd_step"


def kernel_seconds(run):
    ops = (run.get("trace") or {}).get("device_ops") or []
    spent = sum(sec for name, sec in ops if OP_NAME in name)
    return spent or None


def read(run):
    spent = kernel_seconds(run)
    busy = (run.get("trace") or {}).get("busy_s")
    return 100.0 * spent / busy if spent and busy else None
