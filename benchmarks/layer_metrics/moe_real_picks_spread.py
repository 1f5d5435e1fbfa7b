"""Mean over the window's steps and the layers of (most - fewest) REAL experts
a live token of the step picked among its ``moe_topk``: how far the expert
work of the tokens that share a step differs, as a number (0 when every token
picked as many real experts; with 12 picks over 512 real and 256 identity
experts a step of some tens of tokens spreads by 5 to 8).
``ServingStats.moe_real_picks_max - moe_real_picks_min`` over steps x layers,
read from the family's tap; None on a program without the counters."""
from benchmarks.families import longcat

LAYER = "model programs"
UNIT = "experts"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(run):
    steps = [s for s in longcat.steps_in(run) if "moe_real_picks_max" in s["counters"]]
    layers = run["config"].get("num_layers")
    if not steps or not layers:
        return None
    spread = sum(s["counters"]["moe_real_picks_max"] - s["counters"]["moe_real_picks_min"]
                 for s in steps)
    return spread / (len(steps) * layers)
