"""The program's own share of ``setup_s``: seconds the start-up record's
phases cover, by the union of their intervals (the root's seconds less its
``waiting_ms``, which the record computes from that union), from the worker's
first stamp (``TPUCompute``) to the end of the first step cycle that sampled a
token.  The
record is the served process's (``cordum_tpu/obs/startup.py``: phases on
``time.time_ns()``, closed and published once as trace ``startup-<worker>``);
the harness keeps window spans only, so the readers take it from there, as
``families/afmoe.py`` ``STEPS``' readers take theirs.  What the root holds
beyond its phases is the wait for a first request (the harness's registration
and the load generator's start): the root's ``waiting_ms``.  None on a program
without the record."""
import dataclasses

LAYER = "worker runtime"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def record(run):
    """The closed record as dicts (``name``, ``start_ns``, ``end_ns``, ``id``,
    ``parent``, ``attrs``), the root last; a run may bring one of its own
    under ``startup``.  None where there is none, or it never closed."""
    rows = run.get("startup")
    if rows is None:
        try:
            from cordum_tpu.obs import startup
        except ImportError:
            return None
        rows = [dataclasses.asdict(p) for p in startup.phases()]
    return rows if rows and rows[-1]["name"] == "startup" else None


def seconds(run, *names):
    """Summed seconds of the record's phases called any of ``names``; None
    without a record."""
    rows = record(run)
    if rows is None:
        return None
    return sum(r["end_ns"] - r["start_ns"] for r in rows if r["name"] in names) / 1e9


def read(run):
    rows = record(run)
    if rows is None:
        return None
    root = rows[-1]
    return (root["end_ns"] - root["start_ns"]) / 1e9 - root["attrs"]["waiting_ms"] / 1e3
