"""Median duration of the gateway's ``submit`` span: admission, job-store
writes and the publish to the scheduler."""
from benchmarks.harness.stats import median

LAYER = "gateway"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"  # the TTFT metric every open-loop cell reports


def read(run):
    durs = [(s["end_us"] - s["start_us"]) / 1e3 for s in run["spans"] if s["name"] == "submit"]
    return median(durs) if durs else None
