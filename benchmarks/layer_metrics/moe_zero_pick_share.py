"""Of the picks the router made for live tokens in the window (live tokens x
experts per token x layers), the share that fell on identity (zero-compute)
experts: each adds ``w x m`` and costs no expert's products and no weight
read.  A third when 256 of the router's 768 are identity experts and picks
fall evenly.  ``ServingStats.moe_zero_assignments / moe_assignments``, read
from the family's tap; None on a program without the counter."""
from benchmarks.families import longcat

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    steps = [s for s in longcat.steps_in(run) if "moe_zero_assignments" in s["counters"]]
    made = sum(s["counters"]["moe_assignments"] for s in steps)
    return 100.0 * sum(s["counters"]["moe_zero_assignments"] for s in steps) / made if made else None
