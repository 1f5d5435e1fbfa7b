"""Of the assignments the router made in the window (live tokens x experts per
token x expert layers), the share that went to experts held on this chip.
12.5 % when routing over all 256 is even and 32 are held: far from it, the
router is skewed or routes over the wrong width."""
from benchmarks.families import afmoe

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    steps = afmoe.steps_in(run)
    made = sum(s["counters"]["moe_assignments"] for s in steps)
    here = sum(s["counters"]["moe_assignments_here"] for s in steps)
    return 100.0 * here / made if made else None
