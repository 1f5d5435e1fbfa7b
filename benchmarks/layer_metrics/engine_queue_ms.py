"""Median of the per-request ``serving.queue`` spans: ``ServingEngine.submit()``
to ``_admit`` moving the session into the step loop, the wait for a session
slot and KV pages.  With ``engine_prefill_ms`` it splits ``engine_ttft_ms``."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.step_cycle_ms import durations_ms

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(run):
    xs = durations_ms(run, "serving.queue")
    return median(xs) if xs else None
