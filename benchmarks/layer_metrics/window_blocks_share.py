"""Blocks a window layer's walk read over blocks the full layer's walk read,
summed over the window's steps: under 100 % as far as rows are longer than
the window."""
from benchmarks.families import afmoe

LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(run):
    steps = afmoe.steps_in(run)
    full = sum(s["full_blocks"] for s in steps)
    return 100.0 * sum(s["window_blocks"] for s in steps) / full if full else None
