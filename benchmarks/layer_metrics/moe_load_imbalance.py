"""The busiest held expert's tokens over the mean of the held experts, per
expert layer and step (sum of the busiest / sum of the means): 1 is even; the
grouped products take as long as their largest group's tiles."""
from benchmarks.families import afmoe

LAYER = "model programs"
UNIT = "x"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(run):
    steps = afmoe.steps_in(run)
    busiest = sum(s["counters"]["moe_max_expert_load"] for s in steps)
    here = sum(s["counters"]["moe_assignments_here"] for s in steps)
    return busiest * run["config"]["num_experts"] / here if here else None
