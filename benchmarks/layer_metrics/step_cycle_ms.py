"""Median duration of the sampled ``step`` spans: one whole cycle of the
serving loop, ``_admit`` start to the end of its emit phase.  The engine keeps
a cycle as a trace of its own (``step-<worker>-<n>``: root ``step`` and six
contiguous children) about four times a second, and every stalled one."""
from benchmarks.harness.stats import median

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def durations_ms(run, name):
    """Durations, in ms, of the window's flight-recorder spans called ``name``."""
    return [(s["end_us"] - s["start_us"]) / 1e3 for s in run["spans"] if s["name"] == name]


def cycles(run):
    """The sampled cycles that arrived whole: per ``step`` trace, span name ->
    duration in ms (a cycle is one trace, so its spans share a trace id)."""
    by_trace = {}
    for s in run["spans"]:
        if s["name"] == "step" or s["name"].startswith("step."):
            by_trace.setdefault(s["trace"], {})[s["name"]] = (s["end_us"] - s["start_us"]) / 1e3
    return [c for c in by_trace.values() if len(c) == 7]


def read(run):
    xs = durations_ms(run, "step")
    return median(xs) if xs else None
