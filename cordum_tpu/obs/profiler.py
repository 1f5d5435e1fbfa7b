"""Runtime profiler: per-process event-loop lag, slow-tick stack dumps, and
GC-pause accounting — the "why is this process slow" leg of the fleet
telemetry plane (docs/OBSERVABILITY.md §Fleet telemetry).

Three probes, all off the hot path:

* **event-loop lag sampler** — an ``asyncio.sleep(tick)`` loop measures how
  late the loop woke it; the excess is scheduling lag (a blocking call, a
  long callback, CPU starvation) and feeds the
  ``cordum_eventloop_lag_seconds`` histogram;
* **slow-tick detector** — when one tick's lag exceeds ``slow_tick_s`` the
  profiler dumps every live task's stack (newest frames) with the last
  active trace/span id to the log, increments ``cordum_slow_ticks_total``
  and keeps the dump on ``last_slow_tick`` so the telemetry beacon can ship
  a summary.  The trace id names the request the process was most recently
  working for when it stalled;
* **GC-pause counters** — the process's ONE ``gc.callbacks`` entry
  (:class:`GcPauses`, ``GC_PAUSES``) times each collection into
  ``cordum_gc_pauses_total{generation}`` and ``cordum_gc_pause_seconds``
  (a generation-2 pause IS event-loop lag; correlating the two histograms
  separates GC stalls from blocking code) and keeps the last pauses with
  their wall-clock bounds, which the serving loop lays on its step cycles
  as ``runtime.gc`` spans (docs/OBSERVABILITY.md §Serving spans and metrics).

Everything flows through the process's ``Metrics`` registry, so the
exporter ships it fleet-wide for free.
"""
from __future__ import annotations

import asyncio
import gc
import time
import traceback
from collections import deque
from typing import Any, Optional

from ..infra import logging as logx
from ..infra.metrics import Metrics
from .tracer import last_active_context

DEFAULT_TICK_S = 0.25
DEFAULT_SLOW_TICK_S = 0.5
MAX_DUMP_TASKS = 12
MAX_DUMP_FRAMES = 6
GC_PAUSES_KEPT = 256


class GcPauses:
    """The process's one ``gc.callbacks`` entry, there while somebody holds
    it.  Every collection is stamped with ``time.time_ns()`` (the step
    spans' clock) at both ends: the last ``GC_PAUSES_KEPT`` stay here as
    ``(ordinal, start_ns, end_ns, generation)`` for whoever lays them on a
    timeline (``since``), and each holder that brought a ``Metrics`` has its
    two GC series fed from the same call."""

    def __init__(self) -> None:
        self.pauses: deque[tuple[int, int, int, int]] = deque(maxlen=GC_PAUSES_KEPT)
        self.count = 0  # collections seen while held: the last pause's ordinal
        self._holders: dict[int, Optional[Metrics]] = {}
        self._t0 = 0

    def hold(self, holder: object, metrics: Optional[Metrics] = None) -> None:
        if not self._holders:
            gc.callbacks.append(self._on_gc)
        self._holders[id(holder)] = metrics

    def release(self, holder: object) -> None:
        if id(holder) in self._holders:
            del self._holders[id(holder)]
            if not self._holders:
                gc.callbacks.remove(self._on_gc)

    def since(self, ordinal: int) -> list[tuple[int, int, int, int]]:
        """The pauses past ``ordinal`` (as many of them as are still kept),
        oldest first; the last one's ordinal is what to ask with next."""
        # one call copies them: a collection, on this thread or another,
        # cannot land inside it
        kept = list(self.pauses)
        n = 0
        while n < len(kept) and kept[-1 - n][0] > ordinal:
            n += 1
        return kept[len(kept) - n:]

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections do not nest, and one runs start to stop on one thread
        if phase == "start":
            self._t0 = time.time_ns()
        elif self._t0:
            t0, self._t0 = self._t0, 0
            t1 = max(t0, time.time_ns())
            gen = int(info.get("generation", 0))
            self.count += 1
            self.pauses.append((self.count, t0, t1, gen))
            for metrics in self._holders.values():
                if metrics is not None:
                    metrics.gc_pauses.inc(generation=str(gen))
                    metrics.gc_pause_seconds.observe((t1 - t0) / 1e9)


GC_PAUSES = GcPauses()


class RuntimeProfiler:
    def __init__(
        self,
        metrics: Metrics,
        *,
        service: str = "",
        tick_s: float = DEFAULT_TICK_S,
        slow_tick_s: float = DEFAULT_SLOW_TICK_S,
    ) -> None:
        self.metrics = metrics
        self.service = service
        self.tick_s = max(0.01, tick_s)
        self.slow_tick_s = slow_tick_s
        self.last_slow_tick: Optional[dict[str, Any]] = None
        self._task: Optional[asyncio.Task] = None

    # ------------------------------------------------------------------
    async def start(self) -> None:
        self._task = asyncio.ensure_future(self._loop())
        GC_PAUSES.hold(self, self.metrics)

    async def stop(self) -> None:
        GC_PAUSES.release(self)
        if self._task is not None:
            task, self._task = self._task, None
            task.cancel()
            await logx.join_task(task, name="runtime-profiler")

    # ------------------------------------------------------------------
    async def _loop(self) -> None:
        while True:
            t0 = time.monotonic()
            await asyncio.sleep(self.tick_s)
            lag = max(0.0, time.monotonic() - t0 - self.tick_s)
            self.metrics.eventloop_lag.observe(lag)
            if lag >= self.slow_tick_s:
                try:
                    self._dump_slow_tick(lag)
                except Exception as e:  # noqa: BLE001 - diagnostics must not crash the host
                    logx.warn("slow-tick dump failed", err=str(e))

    def _dump_slow_tick(self, lag_s: float) -> None:
        """The loop just stalled for ``lag_s``: record who was running."""
        self.metrics.slow_ticks.inc()
        trace_id, span_id = last_active_context()
        tasks = []
        current = asyncio.current_task()
        for task in asyncio.all_tasks():
            if task is current or task.done():
                continue
            frames = task.get_stack(limit=MAX_DUMP_FRAMES)
            if not frames:
                continue
            stack = "".join(
                traceback.format_stack(f, limit=1)[0] for f in frames
            ).rstrip()
            tasks.append({"task": task.get_name(), "stack": stack})
            if len(tasks) >= MAX_DUMP_TASKS:
                break
        self.last_slow_tick = {
            "at_monotonic": time.monotonic(),
            "lag_s": round(lag_s, 4),
            "trace_id": trace_id,
            "span_id": span_id,
            "tasks": [t["task"] for t in tasks],
        }
        logx.warn(
            "slow event-loop tick",
            service=self.service,
            lag_s=round(lag_s, 4),
            trace_id=trace_id or "-",
            span_id=span_id or "-",
            tasks=len(tasks),
        )
        for t in tasks:
            logx.warn("slow-tick task stack", task=t["task"], stack=t["stack"])

    # ------------------------------------------------------------------
    def health(self) -> dict[str, Any]:
        """Beacon fields the telemetry exporter ships (slow-tick summary)."""
        out: dict[str, Any] = {}
        if self.last_slow_tick is not None:
            out["last_slow_tick_lag_s"] = self.last_slow_tick["lag_s"]
            out["last_slow_tick_trace"] = self.last_slow_tick["trace_id"]
        return out
