"""Most of the page pool that the rows of any one step of the window held
(the engine reserves a session's whole footprint, prompt + answer cap, when
it admits it): peak over steps of the distinct pages behind the step's rows /
pages of the pool.  What the pool holds beyond it is retired sessions kept as
prefix-cache entries, and free pages."""
LAYER = "serving engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    if not run["steps"]:
        return None
    held = max(pages for _, _, pages in run["steps"])
    return 100.0 * held / run["pool"]["pages"]
