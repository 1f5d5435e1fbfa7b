"""The hyper-connected stream's maps' share of the device's busy time in the
traced slice: the seconds of the events named ``mhc_*`` (the Pallas kernels
``mhc_open`` and ``mhc_close`` of ``cordum_tpu/models/hyper.py``: one event
each a sublayer and step) among the heaviest operations of the trace, over
busy seconds.  ``kernel_seconds`` gives the seconds to the roofline reader
too.  None unless BOTH kernels are among the trace's ten heaviest operations
(another family, a program without the kernels, or a kernel too light to be
listed): one of the two alone would under-read this share and over-read the
roofline's, whose least time is both kernels'."""
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"

#: how the two kernels' names start in a device trace (``hyper.OPEN_KERNEL`` / ``CLOSE_KERNEL``)
OP_PREFIXES = ("mhc_open", "mhc_close")


def kernel_seconds(run):
    ops = (run.get("trace") or {}).get("device_ops") or []
    spent = [sum(sec for name, sec in ops if name.startswith(p)) for p in OP_PREFIXES]
    return sum(spent) if all(spent) else None


def read(run):
    spent = kernel_seconds(run)
    busy = (run.get("trace") or {}).get("busy_s")
    return 100.0 * spent / busy if spent and busy else None
