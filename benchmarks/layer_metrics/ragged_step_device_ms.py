"""Median device duration of one execution of the jitted ragged program in
the traced slice (the ``XLA Modules`` line of the profiler trace)."""
from benchmarks.harness.stats import median

LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def runs_of(run):
    mods = (run.get("trace") or {}).get("module_runs_s") or {}
    return [d for name, ds in mods.items() if "ragged" in name for d in ds]


def read(run):
    ds = runs_of(run)
    return 1e3 * median(ds) if ds else None
