"""Median, per trace, of ``schedule`` start to ``dispatch`` start: policy check
and placement, up to the publish to the worker."""
from benchmarks.harness.stats import median

LAYER = "scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"  # the TTFT metric every open-loop cell reports


def per_trace(spans):
    """Group the flight recorder's spans by trace id: one dict per trace,
    span name -> that trace's FIRST span of the name."""
    traces = {}
    for s in sorted(spans, key=lambda s: s["start_us"]):
        traces.setdefault(s["trace"], {}).setdefault(s["name"], s)
    return list(traces.values())


def read(run):
    vals = [(t["dispatch"]["start_us"] - t["schedule"]["start_us"]) / 1e3
            for t in per_trace(run["spans"]) if "schedule" in t and "dispatch" in t]
    return median(vals) if vals else None
