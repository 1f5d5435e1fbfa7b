"""How late the load generator ran: 95th percentile of (actual send - due) on
the child's own clock.  A starved generator must not read as a fast server.
Open loop only: a closed-loop client has no schedule to be late for."""
from benchmarks.harness.stats import percentile

LAYER = "load generator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "ttft_p95_ms"


def read(run):
    if run["loop"] != "open" or not run["records"]:
        return None
    return 1e3 * percentile([r["sent"] - r["due"] for r in run["records"]], 95)
