"""Share of the sampled cycles' time that is not ``step.wait``: 100 x
sum(``step`` - ``step.wait``) / sum(``step``), stalled cycles included.  The
host's side of ``device_idle_share``; the other ``step_*`` metrics split it by
phase."""
from benchmarks.layer_metrics.step_cycle_ms import cycles

LAYER = "serving backend"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(run):
    cs = cycles(run)
    total = sum(c["step"] for c in cs)
    return 100.0 * sum(c["step"] - c["step.wait"] for c in cs) / total if total else None
