"""95th percentile of the per-request ``serving.queue`` spans: the admission
wait of the requests that arrive into a full engine, the part of TTFT that
the knee sets."""
from benchmarks.harness.stats import percentile
from benchmarks.layer_metrics.step_cycle_ms import durations_ms

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(run):
    xs = durations_ms(run, "serving.queue")
    return percentile(xs, 95) if xs else None
