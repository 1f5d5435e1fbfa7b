"""Median of the per-request ``serving.prefill`` spans: admission to the
return of the step that sampled the first token, the prompt riding prefill
chunks under the step budget."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.step_cycle_ms import durations_ms

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(run):
    xs = durations_ms(run, "serving.prefill")
    return median(xs) if xs else None
