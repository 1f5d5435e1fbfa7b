"""Share of the window the serving loop POLLED: seconds of the ``serving.poll``
spans inside the window over the window's, in %.  A poll is the stretch of
cycles that had live sessions or pending work and fed nothing, a millisecond's
sleep each: ``reason`` ``pages`` (pending work and no pages yet) or ``budget``
(every live row frozen or past the step's budget).  One span from the first
such cycle to the next cycle that feeds.  Cut to the window as
``engine_parked_share`` cuts a park (its docstring says how); 0.0 where the
program stamps its idle time and never polled, None on a program without the
stamps."""
from benchmarks.layer_metrics.engine_parked_share import share

LAYER = "serving engine"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(run):
    return share(run, "serving.poll")
