"""Mean rows per step over the window against ``max_sessions``:
occupancy_sum / steps / max_sessions."""
LAYER = "serving engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    d = run["stats_delta"]
    if not d["steps"]:
        return None
    return 100.0 * d["occupancy_sum"] / d["steps"] / run["max_sessions"]
