"""Most of the state slots that sessions held at once in the window: peak over
the window's steps of the slots in use when the step returned / the slots
there are (a model with recurrent state holds one slot a session beside its
pages, from admission to retirement; ``ServingStats.state_slots_peak`` is the
same peak over the engine's whole life).  Read from the family's tap; None on
a program without state slots."""
from benchmarks.layer_metrics.bailing_step_roofline_share import state_steps

LAYER = "serving engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    steps = state_steps(run, "window")
    if not steps:
        return None
    return 100.0 * max(s["state_slots"] for s in steps) / steps[0]["state_slots_total"]
