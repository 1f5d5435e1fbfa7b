"""Share of the traced span in which no operation ran on the device: 1 - union
of device-operation intervals / the trace's own span (its first operation's
start to its last's end, on the device's clock).  Numerator and denominator
come from the same events, so the share cannot pass 100 % and nothing is cut
off; the span leaves out at most one idle gap at either edge of the trace."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    tr = run.get("trace") or {}
    if not tr.get("devices") or not tr.get("span_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["span_s"])
