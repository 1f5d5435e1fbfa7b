"""Median, per trace, of ``dispatch`` start to ``execute`` start: the publish,
the bus hop and the worker's intake.  (From ``dispatch`` END it reads
negative: the loopback bus delivers inside the publish, so ``execute``
starts before the ``dispatch`` span has closed.)"""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.sched_dispatch_ms import per_trace

LAYER = "worker runtime"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"  # the TTFT metric every open-loop cell reports


def read(run):
    vals = [(t["execute"]["start_us"] - t["dispatch"]["start_us"]) / 1e3
            for t in per_trace(run["spans"]) if "execute" in t and "dispatch" in t]
    return median(vals) if vals else None
