"""Share of the window the serving loop stood PARKED: seconds of the
``serving.parked`` spans inside the window over the window's, in %.  The loop
parks when no session is live and nothing is pending (``ServingEngine._unfed``,
``await self._wake.wait()``); the span runs from the start of the cycle that
found nothing, through the telling of the last step's tokens, to the wake.
With ``engine_poll_share`` and the step cycles it covers the loop's life, so at
a fixed offered rate a loop that parks more has more room.

How a park is cut to the window: ``run["spans"]`` holds the spans that ARRIVED
inside it, a park arrives behind the first step after its wake, and its
seconds before the window's opening are cut off.  The spans are on the wall
clock and ``run["t0"]`` on the monotonic one; a span arrives no earlier than
it ends and some arrive within a millisecond, so the smallest arrival less end
over the window's spans is taken for the clocks' distance.  A park still open
at the close never arrives and is not counted.

0.0 where the program stamps its idle time and never parked (a busy cell);
None on a program without the stamps (it publishes no ``emit.wake``, which
every kept cycle of this program carries)."""

LAYER = "serving engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def stamped(run):
    """Does the served program publish the spans of its idle time at all?"""
    return any(s["name"] == "emit.wake" for s in run["spans"])


def seconds_inside(run, name):
    """Seconds of the window's spans called ``name`` that lie inside the window."""
    spans = run["spans"]
    far = min(s["at"] - s["end_us"] / 1e6 for s in spans)  # monotonic less wall
    lo = (run["t0"] - far) * 1e6
    hi = lo + run["window_s"] * 1e6
    return sum(max(0.0, min(s["end_us"], hi) - max(s["start_us"], lo))
               for s in spans if s["name"] == name) / 1e6


def share(run, name):
    return 100.0 * seconds_inside(run, name) / run["window_s"] if stamped(run) else None


def inside_cycles(run):
    """The sampled cycles that arrived whole, with what lies inside their
    phases: per ``step-`` trace, span name -> duration in ms, kept where the
    trace holds the root and its six phases (``step_cycle_ms.cycles``' rule)."""
    by_trace = {}
    for s in run["spans"]:
        if s["trace"].startswith("step-"):
            by_trace.setdefault(s["trace"], {})[s["name"]] = (s["end_us"] - s["start_us"]) / 1e3
    return [c for c in by_trace.values()
            if sum(n == "step" or n.startswith("step.") for n in c) == 7]


def read(run):
    return share(run, "serving.parked")
