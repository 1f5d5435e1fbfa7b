"""Most pages the window layers' rings held at any step of the window, over
the most the same sessions held of the full layers' kind (a page per 16
positions of the whole row: what every layer would hold without the rings)."""
from benchmarks.families import afmoe

LAYER = "serving engine"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    steps = afmoe.steps_in(run)
    full = max((s["full_pages"] for s in steps), default=0)
    return 100.0 * max(s["window_pages"] for s in steps) / full if full else None
