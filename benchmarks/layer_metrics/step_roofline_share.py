"""Least time the chip could take for the traced slice's steps (the larger of
operations / peak FLOP/s and bytes / peak bandwidth, for each step's LIVE
tokens: harness/roofline.py) over the device time the ragged program took
for them: mean least time per step / mean device time per execution, so an
execution cut by the slice's edge does no harm."""
from benchmarks.harness import roofline
from benchmarks.layer_metrics.ragged_step_device_ms import runs_of

LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def least_times(run):
    """(seconds, binding peak) for each step the slice saw."""
    sl = run.get("slice") or {}
    if "t0" not in sl or run.get("peaks") is None:
        return []
    out = []
    for at, rows, _ in run["steps"]:
        if sl["t0"] <= at < sl["t1"]:
            rr = [roofline.Row(n=n, start=start, head=(draft + 1 if draft else int(sample)))
                  for n, start, sample, draft in rows]
            out.append(roofline.least_seconds(run["config"], rr, run["peaks"]))
    return out


def read(run):
    ds = runs_of(run)
    least = least_times(run)
    if not ds or not least:
        return None
    mean_least = sum(t for t, _ in least) / len(least)
    return 100.0 * mean_least / (sum(ds) / len(ds))
