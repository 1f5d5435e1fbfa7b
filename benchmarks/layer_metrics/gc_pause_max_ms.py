"""The longest ``runtime.gc`` span of the window: a collector pause the
served process stamped (``obs/profiler.py`` ``GC_PAUSES``, the process's one
``gc.callbacks`` entry) and the serving loop laid on the cycle, park or poll it
fell in.  Only a generation-2 collection or a pause of 1 ms and more becomes a
span (and keeps its cycle), so this reads the long ones: what a stalled step
can be put down to.  0.0 where the program stamps them and the window had
none, None on a program without the stamps."""
from benchmarks.layer_metrics.engine_parked_share import stamped
from benchmarks.layer_metrics.step_cycle_ms import durations_ms

LAYER = "worker runtime"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    return max(durations_ms(run, "runtime.gc"), default=0.0) if stamped(run) else None
