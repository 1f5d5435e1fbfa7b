"""Median of the per-request ``serving.first_packet`` spans: the first
token's way out of the engine, from the stamp its step's bookkeeping took
(``_first_token``, where ``serving.prefill`` and ``engine_ttft_ms`` end) to
the return of the session's first ``on_tokens`` call: the wait for the next
step's hand-over (a step's packets are told behind the NEXT step), the publish
and, on a bus that delivers at publish, the gateway's tap.  The part of the
client's TTFT that lies behind ``engine_ttft_ms``.  None on a program without
the span."""
from benchmarks.harness.stats import median
from benchmarks.layer_metrics.step_cycle_ms import durations_ms

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(run):
    xs = durations_ms(run, "serving.first_packet")
    return median(xs) if xs else None
