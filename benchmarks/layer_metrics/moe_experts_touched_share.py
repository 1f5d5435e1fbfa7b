"""Held experts that got at least one token, per expert layer and step, over
the experts held: the share of the held experts' weights a step must read."""
from benchmarks.families import afmoe

LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(run):
    steps = afmoe.steps_in(run)
    if not steps:
        return None
    doc = run["config"]
    slots = len(steps) * (doc["num_hidden_layers"] - doc["num_dense_layers"]) * doc["num_experts"]
    return 100.0 * sum(s["counters"]["moe_experts_touched"] for s in steps) / slots
