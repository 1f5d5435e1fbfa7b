"""Share of the window under the collector's LONG pauses: seconds of the
window's ``runtime.gc`` spans (generation 2, or 1 ms and more: the shorter
ones add to ``ServingStats.gc_pause_seconds`` and leave no span) over the
window's, in %.  The whole process stands still in them, the step loop and the
executor thread alike.  0.0 where the program stamps them and the window had
none, None on a program without the stamps."""
from benchmarks.layer_metrics.engine_parked_share import stamped
from benchmarks.layer_metrics.step_cycle_ms import durations_ms

LAYER = "worker runtime"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(run):
    if not stamped(run):
        return None
    return 100.0 * sum(durations_ms(run, "runtime.gc")) / 1e3 / run["window_s"]
