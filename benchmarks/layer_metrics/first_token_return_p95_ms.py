"""95th percentile of the per-request ``serving.first_packet`` spans
(``first_token_return_ms`` is their median and says what they cover)."""
from benchmarks.harness.stats import percentile
from benchmarks.layer_metrics.step_cycle_ms import durations_ms

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(run):
    xs = durations_ms(run, "serving.first_packet")
    return percentile(xs, 95) if xs else None
