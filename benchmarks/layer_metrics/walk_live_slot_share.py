"""Of the query slots the attention walks computed in the window, a block of
keys each, the share that were FED slots needing that block: the rest is what
the tile geometry pads (a tile's empty slots, a short tile walked to its
group's longest).  ``ServingStats.attn_slots_live / attn_slots_computed``,
counted on the host by the program's own rule (``backend._count_walk``), read
from the family's tap; None on a program without the counters."""
from benchmarks.families import axk1

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(run):
    steps = [s for s in axk1.steps_in(run) if s.get("slots_computed")]
    computed = sum(s["slots_computed"] for s in steps)
    return 100.0 * sum(s["slots_live"] for s in steps) / computed if computed else None
