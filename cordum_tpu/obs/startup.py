"""The start-up record: what a worker process did from its first stamp to
its first sampled token, as finished phases on ``time.time_ns()`` (the clock
of ``now_us()``, of ``backend.step_phase`` and of the profiler's host plane).

Process-wide, because start-up is the process's: one worker a process, and
the first phases begin before a ``Worker`` exists.  Code that is on no hot
path stamps a phase where the work happens (:func:`phase`); the serving
backend adds a program's phases from what JAX itself says of the trace, the
lowering and the load (:func:`program`, fed by :func:`backend_call`); the
engine closes the record with its first cycle that returned a sampled token
(:func:`close`) and publishes it once, as the trace ``startup-<worker_id>``
(docs/OBSERVABILITY.md §Serving spans and metrics).  After that, and beyond
``PHASE_LIMIT`` entries before it, nothing more is kept: what the compiler
does later goes to the step it fell in (the ``step`` span's ``compiled``).
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional

ROOT = "startup"
PHASE_LIMIT = 64

# JAX's three time-span events of one jitted function's way to the device,
# by the name of the phase each becomes.  On a hit of the persistent cache
# the backend-compile event is the key's hash, the executable's read, its
# deserialisation and its load; on a miss it is the compile
_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "load",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass(frozen=True)
class Phase:
    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: int  # the enclosing phase's id; 0 is the root's, and the root has -1
    attrs: dict[str, Any]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


@dataclass
class ProgramEvents:
    """What JAX said while one backend call was open, on the calling thread."""

    spans: list[tuple[str, int, int, str]] = field(default_factory=list)  # kind, ns, ns, fun_name
    hits: int = 0  # compile requests the persistent cache served

    def of(self, kind: str) -> list[tuple[int, int]]:
        """The kind's intervals, merged: JAX fires a trace event for every
        nested jitted function, and what lies inside another counts once."""
        merged: list[tuple[int, int]] = []
        for a, b in sorted((a, b) for k, a, b, _ in self.spans if k == kind):
            if merged and a <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], b))
            else:
                merged.append((a, b))
        return merged

    @property
    def compiles(self) -> int:
        return sum(1 for k, *_ in self.spans if k == "load")

    @property
    def compile_ns(self) -> int:
        return sum(b - a for k, a, b, _ in self.spans if k == "load")

    def counts(self) -> dict[str, int]:
        """The attrs a phase carries for the root's sums."""
        return {"programs": self.compiles, "cache_hits": self.hits}


_lock = threading.Lock()
_phases: list[Phase] = []
_ids = itertools.count(1)  # 0 is the root's
_closed = False
_listening = False
_local = threading.local()  # .open: ids of this thread's open phases; .sink: ProgramEvents


def reset() -> None:
    """Empty the record and open it again (tests)."""
    global _ids, _closed
    with _lock:
        del _phases[:]
        _ids, _closed = itertools.count(1), False


def phases() -> list[Phase]:
    """The finished phases so far, in the order they finished (children
    before their parents, the root last once the record is closed)."""
    return list(_phases)


def _keep(ph: Phase) -> None:
    with _lock:
        if not _closed and len(_phases) < PHASE_LIMIT:
            _phases.append(ph)


@contextlib.contextmanager
def phase(name: str, **attrs: Any) -> Iterator[dict[str, Any]]:
    """Stamp the enclosed block as a phase of start-up; yields its attrs, so
    the block can add what it learns.  A phase opened inside another on the
    same thread is its child.  Inert once the record is closed."""
    if _closed:
        yield attrs
        return
    stack = _local.__dict__.setdefault("open", [])
    me, parent = next(_ids), stack[-1] if stack else 0
    stack.append(me)
    t0 = time.time_ns()
    try:
        yield attrs
    finally:
        stack.pop()
        _keep(Phase(me, name, t0, max(t0, time.time_ns()), parent, attrs))


# ---------------------------------------------------------------------------
# a jitted function's way to the device, from JAX's own events

def _on_time_span(event: str, start: float, end: float, **kw: Any) -> None:
    sink = getattr(_local, "sink", None)
    kind = _KINDS.get(event)
    if sink is not None and kind is not None:
        sink.spans.append((kind, int(start * 1e9), int(end * 1e9), str(kw.get("fun_name", ""))))


def _on_event(event: str, **kw: Any) -> None:
    sink = getattr(_local, "sink", None)
    if sink is not None and event == _CACHE_HIT:
        sink.hits += 1


@contextlib.contextmanager
def backend_call() -> Iterator[ProgramEvents]:
    """Keep what JAX says of traces, lowerings and loads on this thread
    while the block runs: the serving backend holds it open round its own
    jitted calls, so another owner's programs in the same process (a
    harness's weights, a reference) are nobody's.  Registers the listeners
    at first use; they fire only when JAX traces or compiles."""
    global _listening
    if not _listening:
        from jax import monitoring

        with _lock:
            if not _listening:
                monitoring.register_event_time_span_listener(_on_time_span)
                monitoring.register_event_listener(_on_event)
                _listening = True
    outer = getattr(_local, "sink", None)
    _local.sink = sink = ProgramEvents()
    try:
        yield sink
    finally:
        _local.sink = outer


def program(entry: str, call_ns: int, events: ProgramEvents, ran_until_ns: int = 0) -> None:
    """The phases of one backend call that made JAX trace or compile:
    ``startup.program`` from the call (``call_ns``) to the last event's end
    with children ``.trace``, ``.lower``, ``.load``; and, where the call went
    on to run what it loaded until ``ran_until_ns`` (the first step: first
    execution, the arenas' donation, the first transfer),
    ``startup.first_step`` behind it."""
    if _closed or not events.spans:
        return
    stack = getattr(_local, "open", None)
    parent, me = stack[-1] if stack else 0, next(_ids)
    for kind in ("trace", "lower", "load"):
        for a, b in events.of(kind):
            _keep(Phase(next(_ids), f"startup.program.{kind}", a, b, me, {}))
    end = max(b for _, _, b, _ in events.spans)
    funs = [f for k, _, _, f in events.spans if k == "load"] or [events.spans[-1][3]]
    _keep(Phase(me, "startup.program", min(call_ns, end), end, parent, {
        "entry": entry, "fun": funs[-1], **events.counts(),
        "cache_hit": str(0 < events.compiles <= events.hits).lower(),
    }))
    if ran_until_ns > end:
        _keep(Phase(next(_ids), "startup.first_step", end, ran_until_ns, parent, {}))


def covered_ns(rows: list[Phase]) -> int:
    """Nanoseconds the phases directly under the root cover, by their union."""
    busy, edge = 0, 0
    for a, b in sorted((p.start_ns, p.end_ns) for p in rows if p.parent == 0):
        busy += max(0, b - max(a, edge))
        edge = max(edge, b)
    return busy


def close(end_ns: int, **attrs: Any) -> Optional[list[Phase]]:
    """Close the record at ``end_ns`` (the end of the first step cycle that
    returned a sampled token) and return it, the root last: ``startup`` from
    the first stamp to ``end_ns`` with ``programs`` and ``cache_hits`` (the
    compile requests of the record and those the persistent cache served)
    and ``waiting_ms`` (what no phase covers: the wait for a first request).
    None, and nothing closed, where it is closed already or no backend of
    this process has run its first step (an engine over a stand-in)."""
    global _closed
    with _lock:
        if _closed or not any(p.name == "startup.first_step" for p in _phases):
            return None
        _closed = True
        rows = list(_phases)
        start = min(p.start_ns for p in rows)
        end = max(end_ns, max(p.end_ns for p in rows))
        _phases.append(Phase(0, ROOT, start, end, -1, {
            **attrs,
            "programs": sum(p.attrs.get("programs", 0) for p in rows),
            "cache_hits": sum(p.attrs.get("cache_hits", 0) for p in rows),
            "waiting_ms": round((end - start - covered_ns(rows)) / 1e6, 3),
        }))
        return list(_phases)
