"""Worker-side gang execution: rendezvous barrier, SPMD step replication,
and MPMD pipeline stages (docs/GANG.md).

A gang member job arrives on the worker's direct subject carrying the
scheduler-stamped ``cordum.gang_*`` labels (gang id, rank, size, member
list).  The member then:

1. subscribes its gang's ``sys.job.gang.<gang_id>`` subject and **beacons**
   ``GangMsg(kind="ready")`` every few hundred ms until it has seen every
   rank's beacon (fan-out subjects are not durable, so beacons repeat
   instead of relying on delivery order) — the rendezvous barrier;
2. a barrier timeout, a peer's abort, a cancel, or any local failure
   aborts the WHOLE gang: the member publishes ``kind="abort"``, peers
   stop between steps, and the scheduler releases every reserved device
   and requeues the job;
3. past the barrier it runs the **step program**:

   * **SPMD** (``mesh.pp <= 1`` or ``workers != pp``): every member runs
     the identical training program (:class:`~..worker.training.TrainRunner`
     — dense llama / moe / pipeline families) over its own slice's mesh.
     In production multi-host JAX this is one global mesh coordinated by
     ``jax.distributed``; in this CPU reproduction each member owns a full
     mesh replica and the control plane supplies what the paper's central
     controller does — admission, rendezvous, and failure semantics.
   * **MPMD pipeline** (``workers == mesh.pp > 1``): rank ``r`` owns stage
     ``r`` of the decoder (rank 0 also embeds, the last rank owns the LM
     head and the loss).  Forward activations and backward cotangents flow
     between neighbor ranks as ``kind="stage"`` messages over the bus
     (the statebus frame layer in a wire deployment) in the classic
     fill/drain GPipe schedule; every rank applies SGD to its own stage —
     stage-per-worker pipeline training driven by a central controller,
     per "Scaling DL Training with MPMD Pipeline Parallelism" (PAPERS.md).

4. on success the member publishes ``kind="done"`` with its stats; the
   scheduler aggregates all ranks into the job's single terminal result.
   Members never publish ``JobResult`` themselves — the gang owns exactly
   one job id.
"""
from __future__ import annotations

import asyncio
import collections
import contextlib
import time
from typing import Any

import numpy as np

from ..infra import logging as logx
from ..protocol import subjects as subj
from ..protocol.types import (
    BusPacket,
    GangMsg,
    JobRequest,
    LABEL_GANG_ID,
    LABEL_GANG_MEMBERS,
    LABEL_GANG_RANK,
    LABEL_GANG_SIZE,
    SERVING_OPS,
)

DEFAULT_RENDEZVOUS_TIMEOUT_S = 10.0
DEFAULT_PEER_TIMEOUT_S = 30.0
BEACON_INTERVAL_S = 0.25
_DONE_CACHE_CAP = 128


class GangAborted(Exception):
    """The gang is over (peer abort / barrier timeout / cancel) — unwind
    without publishing a member result."""


class _GangSession:
    """One member's live view of its gang: the ready set, the abort latch,
    and tag-addressed mailboxes for MPMD stage traffic."""

    def __init__(self, gang_id: str, job_id: str, rank: int, size: int,
                 trace_id: str = "") -> None:
        self.gang_id = gang_id
        self.job_id = job_id
        self.rank = rank
        self.size = size
        self.trace_id = trace_id
        self.ready: set[int] = {rank}
        self.barrier = asyncio.Event()
        self.abort = asyncio.Event()
        self.abort_reason = ""
        self._mail: dict[str, asyncio.Future] = {}
        # serving-gang replay stream (kind="step"): rank 0's broadcast
        # entry batches, drained in seq order by the follower loop
        self.steps: collections.deque[GangMsg] = collections.deque()
        self.step_event = asyncio.Event()

    def on_msg(self, msg: GangMsg) -> None:
        if msg.kind == "ready":
            self.ready.add(msg.rank)
            if len(self.ready) >= self.size:
                self.barrier.set()
        elif msg.kind == "step":
            self.steps.append(msg)
            self.step_event.set()
        elif msg.kind == "abort":
            self.abort_reason = self.abort_reason or (msg.reason or "abort")
            self.abort.set()
            for fut in self._mail.values():
                if not fut.done():
                    fut.set_exception(GangAborted(self.abort_reason))
        elif msg.kind == "stage" and msg.to_rank == self.rank:
            fut = self._mail.setdefault(
                msg.tag, asyncio.get_running_loop().create_future()
            )
            if not fut.done():
                fut.set_result((bytes(msg.data or b""), list(msg.shape or [])))

    def check_abort(self) -> None:
        if self.abort.is_set():
            raise GangAborted(self.abort_reason or "abort")

    async def recv(self, tag: str, timeout_s: float) -> tuple[bytes, list[int]]:
        """Await the stage message addressed by ``tag``.  A peer that died
        mid-step surfaces as a timeout → the member aborts the gang."""
        self.check_abort()
        fut = self._mail.setdefault(
            tag, asyncio.get_running_loop().create_future()
        )
        try:
            return await asyncio.wait_for(asyncio.shield(fut), timeout_s)
        except asyncio.TimeoutError:
            raise GangAborted(f"peer_timeout:{tag}") from None
        finally:
            self._mail.pop(tag, None)


class GangRunner:
    """Executes gang member jobs for one worker (attached via
    ``Worker.attach_gang``)."""

    def __init__(
        self,
        worker,
        *,
        trainer=None,
        rendezvous_timeout_s: float = DEFAULT_RENDEZVOUS_TIMEOUT_S,
        peer_timeout_s: float = DEFAULT_PEER_TIMEOUT_S,
        beacon_interval_s: float = BEACON_INTERVAL_S,
    ) -> None:
        self.worker = worker
        self.trainer = trainer
        self.rendezvous_timeout_s = rendezvous_timeout_s
        self.peer_timeout_s = peer_timeout_s
        self.beacon_interval_s = beacon_interval_s
        self._sessions: dict[str, _GangSession] = {}
        self._tasks: set[asyncio.Task] = set()
        # live serving gangs this worker is a member of, keyed by gang id —
        # the worker's telemetry beacon folds these into the capacity plane
        # so the fleet renders ONE fused row per gang (obs/capacity.py)
        self._serving_gangs: dict[str, dict] = {}
        # done-report cache: a member packet redelivered after completion
        # republishes the recorded GangMsg instead of re-running the step
        # program (the worker-level completed-result idempotence, gang-shaped)
        self._done: dict[str, GangMsg] = {}

    async def stop(self) -> None:
        for t in list(self._tasks):
            t.cancel()
        for t in list(self._tasks):
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await t
        self._tasks.clear()

    # ------------------------------------------------------------------
    @staticmethod
    def is_member(req: JobRequest) -> bool:
        return LABEL_GANG_ID in (req.labels or {})

    async def handle(
        self, req: JobRequest, payload: Any, *,
        trace_id: str = "", parent_span_id: str = "",
    ) -> None:
        """Run one gang member job end-to-end.  Publishes only GangMsg
        traffic — never a JobResult (the scheduler owns the job's single
        terminal result)."""
        labels = req.labels or {}
        gang_id = labels.get(LABEL_GANG_ID, "")
        try:
            rank = int(labels.get(LABEL_GANG_RANK, "-1"))
            size = int(labels.get(LABEL_GANG_SIZE, "0"))
        except ValueError:
            rank, size = -1, 0
        if not gang_id or rank < 0 or size < 1:
            logx.warn("malformed gang member labels", job_id=req.job_id)
            return
        cached = self._done.get(req.job_id)
        if cached is not None and cached.gang_id == gang_id:
            await self._publish(gang_id, cached, trace_id)
            return
        existing = self._sessions.get(req.job_id)
        if existing is not None:
            if existing.gang_id == gang_id:
                return  # redelivery of an in-flight member
            # a FRESH gang attempt for the same job: the old session's gang
            # was aborted and it is tearing down — wait it out (bounded; the
            # abort latch breaks spin/step loops promptly) so the new
            # attempt isn't mistaken for a redelivery
            deadline = time.monotonic() + self.rendezvous_timeout_s
            while self._sessions.get(req.job_id) is existing:
                if time.monotonic() > deadline:
                    logx.warn("stale gang session blocks new attempt",
                              job_id=req.job_id, old_gang=existing.gang_id,
                              new_gang=gang_id)
                    return  # the scheduler's rendezvous backstop retries
                await asyncio.sleep(0.02)
        t = asyncio.ensure_future(self._run_member(
            req, payload, gang_id, rank, size,
            trace_id=trace_id, parent_span_id=parent_span_id,
        ))
        self._tasks.add(t)
        t.add_done_callback(self._tasks.discard)
        await t

    async def _publish(self, gang_id: str, msg: GangMsg, trace_id: str) -> None:
        await self.worker.bus.publish(
            subj.gang_subject(gang_id),
            BusPacket.wrap(msg, trace_id=trace_id,
                           sender_id=self.worker.worker_id),
        )

    async def _run_member(
        self, req: JobRequest, payload: Any, gang_id: str, rank: int, size: int,
        *, trace_id: str, parent_span_id: str,
    ) -> None:
        from .runtime import JobContext

        worker = self.worker
        ctx = JobContext(request=req, payload=payload, worker=worker)
        session = _GangSession(gang_id, req.job_id, rank, size,
                               trace_id=trace_id)
        self._sessions[req.job_id] = session
        worker._active[req.job_id] = ctx
        worker._mark_busy()

        async def _on_gang_pkt(subject: str, pkt: BusPacket) -> None:
            self._route(session, pkt)

        sub = await worker.bus.subscribe(subj.gang_subject(gang_id), _on_gang_pkt)
        tracer = worker.tracer
        exec_span = tracer.begin(
            "gang-execute", trace_id=trace_id, parent_span_id=parent_span_id,
            attrs={"job_id": req.job_id, "gang_id": gang_id,
                   "rank": str(rank), "worker_id": worker.worker_id},
        )
        beacon = asyncio.ensure_future(self._beacon_loop(session, trace_id))
        abort_reason = ""
        try:
            rdv_span = tracer.begin(
                "gang-rendezvous", trace_id=trace_id,
                parent_span_id=exec_span.span_id,
                attrs={"gang_id": gang_id, "rank": str(rank)},
            )
            t0 = time.monotonic()
            await self._barrier(session, ctx)
            waited = time.monotonic() - t0
            rdv_span.attrs["members"] = str(size)
            await tracer.finish(rdv_span)
            metrics = getattr(worker, "gang_metrics", None)
            if metrics is not None:
                metrics.gang_rendezvous_seconds.observe(waited)

            step_span = tracer.begin(
                "gang-step", trace_id=trace_id,
                parent_span_id=exec_span.span_id,
                attrs={"gang_id": gang_id, "rank": str(rank)},
            )
            stats = await self._run_program(session, ctx, payload)
            if stats.get("loss") is not None:
                step_span.attrs["loss"] = f"{stats['loss']:.4f}"
            step_span.attrs["mode"] = str(stats.get("mode", ""))
            await tracer.finish(step_span)

            done = GangMsg(
                gang_id=gang_id, job_id=req.job_id, kind="done", rank=rank,
                worker_id=worker.worker_id, stats=stats,
            )
            if len(self._done) > _DONE_CACHE_CAP:
                self._done.clear()
            self._done[req.job_id] = done
            await self._publish(gang_id, done, trace_id)
            exec_span.attrs["status"] = "DONE"
            await tracer.finish(exec_span)
        except GangAborted as e:
            abort_reason = str(e) or "abort"
            exec_span.attrs["status"] = "ABORTED"
            exec_span.attrs["reason"] = abort_reason
            await tracer.finish(exec_span, status="ERROR")
            if not session.abort.is_set():
                # locally-originated abort (timeout/cancel): tell the gang
                await self._publish(gang_id, GangMsg(
                    gang_id=gang_id, job_id=req.job_id, kind="abort",
                    rank=rank, worker_id=worker.worker_id,
                    reason=abort_reason,
                ), trace_id)
        except asyncio.CancelledError:
            # worker shutdown / simulated crash: die silently, exactly like
            # SIGKILL — the scheduler watchdog recovers the gang
            raise
        except Exception as e:  # noqa: BLE001 - member failure aborts the gang
            abort_reason = f"member_failed:{type(e).__name__}"
            logx.warn("gang member failed", job_id=req.job_id,
                      gang_id=gang_id, rank=rank, err=str(e))
            exec_span.attrs["status"] = "FAILED"
            exec_span.attrs["error"] = type(e).__name__
            await tracer.finish(exec_span, status="ERROR")
            await self._publish(gang_id, GangMsg(
                gang_id=gang_id, job_id=req.job_id, kind="abort", rank=rank,
                worker_id=worker.worker_id, reason=abort_reason,
            ), trace_id)
        finally:
            beacon.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await beacon
            sub.unsubscribe()
            self._sessions.pop(req.job_id, None)
            worker._active.pop(req.job_id, None)
            worker._mark_idle()

    def _route(self, session: _GangSession, pkt: BusPacket) -> None:
        msg = pkt.gang_msg
        if msg is not None and pkt.sender_id != self.worker.worker_id:
            session.on_msg(msg)

    async def _beacon_loop(self, session: _GangSession, trace_id: str) -> None:
        """Re-publish the ready beacon until the barrier passes: fan-out
        subjects are not durable, so a beacon that raced a peer's subscribe
        is simply repeated."""
        msg = GangMsg(
            gang_id=session.gang_id, job_id=session.job_id, kind="ready",
            rank=session.rank, worker_id=self.worker.worker_id,
        )
        # beacon for the member's whole lifetime, not just until OUR barrier
        # passes: a peer that subscribed late (stale-session teardown, slow
        # dispatch) must still be able to complete ITS barrier — stopping at
        # first passage loses the race where A hears B but B never heard A.
        # The task is cancelled in the member's finally block.
        while not session.abort.is_set():
            await self._publish(session.gang_id, msg, trace_id)
            await asyncio.sleep(self.beacon_interval_s)

    async def _barrier(self, session: _GangSession, ctx) -> None:
        deadline = time.monotonic() + self.rendezvous_timeout_s
        while not session.barrier.is_set():
            session.check_abort()
            if ctx.cancelled.is_set():
                raise GangAborted("cancelled")
            if time.monotonic() > deadline:
                raise GangAborted(
                    f"rendezvous_timeout:rank{session.rank}:"
                    f"saw{len(session.ready)}of{session.size}"
                )
            with contextlib.suppress(asyncio.TimeoutError):
                await asyncio.wait_for(session.barrier.wait(), 0.1)

    # ------------------------------------------------------------------
    # step programs
    # ------------------------------------------------------------------
    async def _run_program(
        self, session: _GangSession, ctx, payload: Any
    ) -> dict:
        payload = payload if isinstance(payload, dict) else {}
        op = str(payload.get("op", "train"))
        gang_stanza = payload.get("gang") if isinstance(payload.get("gang"), dict) else {}
        if op in SERVING_OPS or str(gang_stanza.get("kind", "")) == "serving":
            return await self._run_serving(session, ctx, payload)
        if op == "train":
            mesh_req = payload.get("mesh") or {}
            pp = int(mesh_req.get("pp", 1) or 1)
            if session.size > 1 and pp == session.size:
                return await self._run_mpmd(session, ctx, payload)
            return await self._run_spmd(session, ctx, payload)
        if op == "gang_test":
            return await self._run_gang_test(session, ctx, payload)
        # barrier-only member (echo-class): proves the reserve→rendezvous→
        # result pipeline without device work — the bench's gang_jobs_per_sec
        return {"op": op, "mode": "barrier", "rank": session.rank}

    def _abort_poll(self, session: _GangSession, ctx):
        return lambda: session.abort.is_set() or ctx.cancelled.is_set()

    async def _run_spmd(self, session: _GangSession, ctx, payload: dict) -> dict:
        """Every member runs the identical training program over its own
        mesh (dense dp×tp×sp, moe dp×tp×ep, or the shard_map pipeline)."""
        if self.trainer is None:
            raise RuntimeError("gang runner has no trainer attached")
        cancelled = self._abort_poll(session, ctx)
        out = await self.worker.run_in_executor(
            lambda: self.trainer.train(payload, cancelled=cancelled)
        )
        session.check_abort()
        if ctx.cancelled.is_set():
            raise GangAborted("cancelled")
        if not out.get("completed", False):
            # the poll broke the loop: whoever set it owns the reason
            raise GangAborted(session.abort_reason or "cancelled")
        return {**out, "mode": "spmd", "rank": session.rank,
                "loss": out.get("final_loss")}

    async def _run_gang_test(
        self, session: _GangSession, ctx, payload: dict
    ) -> dict:
        """Validation/chaos op: spin for ``spin_s`` checking the abort latch
        between slices, failing outright on workers named in
        ``fail_workers`` — the harness the gang fault tests drive."""
        if self.worker.worker_id in (payload.get("fail_workers") or []):
            raise RuntimeError("gang_test: injected member failure")
        spin_s = float(payload.get("spin_s", 0.0) or 0.0)
        deadline = time.monotonic() + spin_s
        while time.monotonic() < deadline:
            session.check_abort()
            if ctx.cancelled.is_set():
                raise GangAborted("cancelled")
            await asyncio.sleep(0.02)
        return {"op": "gang_test", "mode": "spin", "rank": session.rank,
                "spin_s": spin_s}

    # ------------------------------------------------------------------
    # serving gangs: tensor-parallel ragged serving over the gang
    # (docs/SERVING.md §Sharded serving)
    # ------------------------------------------------------------------
    def serving_gang_doc(self) -> dict:
        """This worker's live serving-gang membership for the telemetry
        beacon (empty dict = not serving in a gang).  Rank 0's doc carries
        the measured fused throughput; follower docs carry only identity +
        their arena headroom (the fleet fuses min-of-ranks)."""
        for doc in self._serving_gangs.values():
            out = dict(doc)
            cb = out.pop("_live", None)
            if callable(cb):
                with contextlib.suppress(Exception):
                    out.update(cb())
            return out
        return {}

    def _serving_backend(self, session: _GangSession, payload: dict):
        """Build this rank's sharded backend from the payload's sizing
        knobs.  Every rank derives IDENTICAL params (same seed, same cfg) —
        on real hardware NamedSharding keeps only the local head slice
        resident; on the 1-chip CI fallback each rank holds a replica."""
        import dataclasses

        import jax.numpy as jnp

        from ..models import llama
        from ..serving.shard import ShardedServingBackend

        dtype_name = str(payload.get("dtype", "float32") or "float32")
        cfg = dataclasses.replace(
            llama.LlamaConfig.tiny(),
            dtype=jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32,
        )
        max_seqs = max(1, int(payload.get("max_sessions", 4) or 4))
        return ShardedServingBackend(
            cfg,
            rank=session.rank,
            tp=session.size,
            num_pages=max(2, int(payload.get("cache_pages", 64) or 64)),
            page_size=max(1, int(payload.get("page_size", 16) or 16)),
            max_seqs=max_seqs,
            max_batch_tokens=max_seqs + max(
                1, int(payload.get("prefill_budget", 16) or 16)),
            seed=int(payload.get("seed", 0) or 0),
        )

    async def _run_serving(
        self, session: _GangSession, ctx, payload: dict
    ) -> dict:
        """One serving-gang member.  Rank 0 runs the REAL serving engine
        (admission, session registry, token streaming) over its shard and
        broadcasts every ragged step's entry batch as ``kind="step"``;
        follower ranks replay the identical batches against their shards —
        same program, same arena trajectory, no lm_head (docs/SERVING.md
        §Sharded serving)."""
        labels = (ctx.request.labels or {})
        members = [m for m in labels.get(LABEL_GANG_MEMBERS, "").split(",") if m]
        backend = self._serving_backend(session, payload)
        metrics = getattr(self.worker, "gang_metrics", None)
        doc: dict[str, Any] = {
            "gang_id": session.gang_id,
            "rank": session.rank,
            "size": session.size,
            "members": members,
            "pages_total": backend.num_pages,
        }
        self._serving_gangs[session.gang_id] = doc
        if metrics is not None:
            metrics.serving_gang_members.set(
                float(session.size), gang=session.gang_id)
        try:
            if session.rank == 0:
                return await self._serve_leader(session, ctx, payload, backend)
            return await self._serve_follower(session, ctx, backend)
        finally:
            # linger_s keeps the fused row visible after the job finishes
            # (platform_smoke scrapes capacity while the gang is winding
            # down); the abort latch cuts the linger short
            linger = float(payload.get("linger_s", 0.0) or 0.0)
            deadline = time.monotonic() + linger
            while time.monotonic() < deadline and not session.abort.is_set():
                await asyncio.sleep(0.05)
            self._serving_gangs.pop(session.gang_id, None)
            if metrics is not None:
                metrics.serving_gang_members.set(0.0, gang=session.gang_id)

    async def _serve_leader(
        self, session: _GangSession, ctx, payload: dict, backend
    ) -> dict:
        from ..serving.engine import GenRequest as EngineGenRequest
        from ..serving.engine import ServingEngine
        from ..serving.shard import entry_to_wire

        worker = self.worker
        loop = asyncio.get_running_loop()
        metrics = getattr(worker, "gang_metrics", None)
        seq = 0

        def _broadcast(entries) -> None:
            # called from the step's executor thread, after the device call
            # lands: ship the EXACT entry batch so followers replay the
            # same compiled program.  Blocking on the publish keeps the
            # replay stream ordered and applies natural backpressure.
            nonlocal seq
            msg = GangMsg(
                gang_id=session.gang_id, job_id=session.job_id, kind="step",
                rank=0, worker_id=worker.worker_id,
                stats={"seq": seq,
                       "entries": [entry_to_wire(e) for e in entries]},
            )
            seq += 1
            asyncio.run_coroutine_threadsafe(
                self._publish(session.gang_id, msg, session.trace_id), loop
            ).result()
            if metrics is not None:
                metrics.serving_gang_steps.inc(role="lead")

        backend.on_step = _broadcast
        engine = ServingEngine(
            backend,
            run_blocking=worker.run_in_executor,
            max_sessions=backend.max_seqs,
            max_new_tokens_cap=int(payload.get("max_new_tokens", 16) or 16),
            metrics=metrics,
            tracer=worker.tracer,
            # CoW page copies happen outside step() and would not replay on
            # followers — the gang engine runs with prefix sharing off (the
            # single-worker engines keep it; a broadcast copy_page protocol
            # is the upgrade path)
            prefix_cache=False,
            speculative=bool(payload.get("speculative", False)),
            draft_k=int(payload.get("draft_k", 0) or 0) or 4,
        )
        engine.worker_id = worker.worker_id
        prompts = payload.get("prompts")
        if not isinstance(prompts, list) or not prompts:
            one = payload.get("prompt") or payload.get("tokens") or [1, 2, 3]
            prompts = [one]
        prompts = [[int(t) for t in p] for p in prompts if p][: backend.max_seqs]
        max_new = int(payload.get("max_new_tokens", 16) or 16)
        live = {"t0": time.monotonic(), "tokens": 0}

        def _live() -> dict:
            free = engine.allocator.free_pages
            dt = max(1e-6, time.monotonic() - live["t0"])
            return {"pages_free": free,
                    "tokens_per_s": round(live["tokens"] / dt, 3)}

        self._serving_gangs[session.gang_id]["_live"] = _live

        def _sink(first: bool):
            base = worker._token_sink(
                session.job_id,
                EngineGenRequest(prompt=[], max_new_tokens=max_new),
            ) if first else None

            async def sink(new_tokens, n_generated, done):
                live["tokens"] += len(new_tokens)
                if metrics is not None and new_tokens:
                    metrics.serving_gang_stream_tokens.inc(
                        len(new_tokens), rank="0")
                if base is not None:
                    await base(new_tokens, n_generated, done)

            return sink

        async def _drive() -> list[dict]:
            subs = [
                engine.submit(
                    EngineGenRequest(
                        prompt=p, max_new_tokens=max_new,
                        stream=(i == 0),
                    ),
                    job_id=session.job_id if i == 0
                    else f"{session.job_id}#{i}",
                    trace_id=session.trace_id,
                    on_tokens=_sink(first=(i == 0)),
                )
                for i, p in enumerate(prompts)
            ]
            return await asyncio.gather(*subs)

        drive = asyncio.ensure_future(_drive())
        abort_w = asyncio.ensure_future(session.abort.wait())
        cancel_w = asyncio.ensure_future(ctx.cancelled.wait())
        try:
            done, _ = await asyncio.wait(
                {drive, abort_w, cancel_w},
                return_when=asyncio.FIRST_COMPLETED,
            )
            if drive not in done:
                drive.cancel()
                with contextlib.suppress(asyncio.CancelledError, Exception):
                    await drive
                raise GangAborted(session.abort_reason or "cancelled")
            results = await drive
        finally:
            for w in (abort_w, cancel_w):
                w.cancel()
            with contextlib.suppress(Exception):
                await engine.stop()
            # the shutdown marker releases the follower replay loops
            with contextlib.suppress(Exception):
                await self._publish(session.gang_id, GangMsg(
                    gang_id=session.gang_id, job_id=session.job_id,
                    kind="step", rank=0, worker_id=worker.worker_id,
                    stats={"seq": seq, "final": True},
                ), session.trace_id)
        elapsed = max(1e-6, time.monotonic() - live["t0"])
        total = sum(len(r.get("tokens") or []) for r in results)
        return {
            "mode": "serving", "rank": 0, "tp": session.size,
            "sessions": len(results), "tokens": total,
            "tokens_per_s": round(total / elapsed, 3),
            "steps": seq, "compiled": backend.compiled_programs(),
            "results": results,
        }

    async def _serve_follower(
        self, session: _GangSession, ctx, backend
    ) -> dict:
        """Replay rank 0's entry batches in seq order until the shutdown
        marker.  The bus preserves per-publisher order, but the loop
        reorders defensively — a replayed batch must never run early (the
        arenas would diverge)."""
        from ..serving.shard import entry_from_wire

        metrics = getattr(self.worker, "gang_metrics", None)
        expected = 0
        pending: dict[int, dict] = {}
        replayed = 0
        while True:
            session.check_abort()
            if ctx.cancelled.is_set():
                raise GangAborted("cancelled")
            while session.steps:
                msg = session.steps.popleft()
                s = int((msg.stats or {}).get("seq", -1))
                if s >= expected:
                    pending[s] = msg.stats or {}
            progressed = False
            while expected in pending:
                stats = pending.pop(expected)
                expected += 1
                progressed = True
                if stats.get("final"):
                    return {
                        "mode": "serving", "rank": session.rank,
                        "tp": session.size, "steps_replayed": replayed,
                        "compiled": backend.compiled_programs(),
                    }
                entries = [entry_from_wire(d)
                           for d in (stats.get("entries") or [])]
                if entries:
                    await self.worker.run_in_executor(
                        lambda e=entries: backend.step(e))
                    replayed += 1
                    if metrics is not None:
                        metrics.serving_gang_steps.inc(role="replay")
            if progressed or session.steps:
                continue
            session.step_event.clear()
            if session.steps:
                continue
            try:
                await asyncio.wait_for(
                    session.step_event.wait(), self.peer_timeout_s)
            except asyncio.TimeoutError:
                raise GangAborted(f"peer_timeout:step{expected}") from None

    # ------------------------------------------------------------------
    # MPMD pipeline: one stage per worker, activations over the bus
    # ------------------------------------------------------------------
    async def _run_mpmd(self, session: _GangSession, ctx, payload: dict) -> dict:
        import jax

        rank, size = session.rank, session.size
        state = await self.worker.run_in_executor(
            lambda: _mpmd_build(payload, rank, size)
        )
        steps = int(payload.get("steps", 1) or 1)
        micro = max(1, int(payload.get("microbatches", 1) or 1))
        batch = int(payload.get("batch", 4) or 4)
        batch = max(micro, (batch // micro) * micro)
        seq = int(payload.get("seq", 16) or 16)
        lr = float(payload.get("lr", 1e-3) or 1e-3)
        losses: list[float] = []
        send_trace = session.trace_id  # stage msgs ride the job trace
        for step in range(steps):
            session.check_abort()
            if ctx.cancelled.is_set():
                raise GangAborted("cancelled")
            # every rank derives the SAME tokens deterministically — only
            # activations/cotangents cross the wire, never the batch
            key = jax.random.PRNGKey(1000 + step)
            tokens = np.asarray(jax.random.randint(
                key, (batch, seq), 0, state["vocab"]))
            mbs = tokens.reshape(micro, batch // micro, seq)
            vjps: list[Any] = []
            grads = None
            mb_losses: list[float] = []
            # fill: forward every microbatch through my stage
            for m in range(micro):
                tag_in = f"fwd:{step}:{m}:{rank}"
                if rank == 0:
                    x = None
                else:
                    data, shape = await session.recv(tag_in, self.peer_timeout_s)
                    x = np.frombuffer(data, np.float32).reshape(shape)
                out = await self.worker.run_in_executor(
                    lambda x=x, m=m: _mpmd_forward(state, mbs[m], x)
                )
                if rank == size - 1:
                    loss, g_params, _g_x_unused = out
                    mb_losses.append(float(loss))
                    vjps.append(out)
                else:
                    y, vjp = out
                    vjps.append(vjp)
                    await self._send_stage(
                        session, f"fwd:{step}:{m}:{rank + 1}", rank + 1,
                        np.asarray(y, np.float32), send_trace)
            # drain: cotangents flow back, each rank accumulates its grads
            for m in range(micro):
                if rank == size - 1:
                    loss, g_params, g_x = vjps[m]
                    if g_x is not None:
                        await self._send_stage(
                            session, f"bwd:{step}:{m}:{rank - 1}", rank - 1,
                            np.asarray(g_x, np.float32), send_trace)
                else:
                    data, shape = await session.recv(
                        f"bwd:{step}:{m}:{rank}", self.peer_timeout_s)
                    g_y = np.frombuffer(data, np.float32).reshape(shape)
                    g_params, g_x = await self.worker.run_in_executor(
                        lambda v=vjps[m], g=g_y: _mpmd_backward(v, g)
                    )
                    if rank > 0 and g_x is not None:
                        await self._send_stage(
                            session, f"bwd:{step}:{m}:{rank - 1}", rank - 1,
                            np.asarray(g_x, np.float32), send_trace)
                grads = (g_params if grads is None
                         else jax.tree.map(lambda a, b: a + b, grads, g_params))
            state["params"] = await self.worker.run_in_executor(
                lambda g=grads: _mpmd_sgd(state["params"], g, lr / micro)
            )
            if mb_losses:
                losses.append(sum(mb_losses) / len(mb_losses))
        return {
            "mode": "mpmd",
            "rank": rank,
            "steps_done": steps,
            "mesh": {"pp": size, "dp": 1},
            "microbatches": micro,
            "loss": losses[-1] if losses else None,
            "loss_first": losses[0] if losses else None,
        }

    async def _send_stage(
        self, session: _GangSession, tag: str, to_rank: int,
        arr: np.ndarray, trace_id: str,
    ) -> None:
        await self._publish(session.gang_id, GangMsg(
            gang_id=session.gang_id, job_id=session.job_id, kind="stage",
            rank=session.rank, to_rank=to_rank, tag=tag,
            data=arr.tobytes(), shape=list(arr.shape),
            worker_id=self.worker.worker_id,
        ), trace_id)


# ---------------------------------------------------------------------------
# MPMD stage math (plain float32 JAX; executor-thread blocking calls)
# ---------------------------------------------------------------------------


def _mpmd_build(payload: dict, rank: int, size: int) -> dict:
    """Deterministically initialize THIS rank's stage slice: every rank
    builds the same stacked pipeline params from the same seed and keeps
    only its stage (rank 0 the embedding, the last rank the head)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ..models import llama, pipeline

    base = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    if base.n_layers % size:
        raise ValueError(
            f"pipeline needs n_layers {base.n_layers} divisible by pp={size}"
        )
    cfg = pipeline.PipelineConfig(base=base, n_stages=size, n_microbatches=1)
    full = pipeline.init_params(
        jax.random.PRNGKey(int(payload.get("seed", 0) or 0)), cfg)
    params: dict = {
        "stage": jax.tree.map(lambda p: jnp.asarray(p[rank]), full["stages"]),
    }
    if rank == 0:
        params["embed"] = full["embed"]
    if rank == size - 1:
        params["final_norm"] = full["final_norm"]
        params["lm_head"] = full["lm_head"]
    return {"params": params, "base": base, "vocab": base.vocab_size,
            "rank": rank, "size": size}


def _mpmd_forward(state: dict, tokens_mb: np.ndarray, x_in):
    """One microbatch through this rank's stage.

    * rank 0: ``(activation, vjp)`` — vjp w.r.t. params only (tokens carry
      no gradient).
    * middle: ``(activation, vjp)`` — vjp w.r.t. (params, input).
    * last: ``(loss, param_grads, input_cotangent)`` — the backward starts
      here, so the full value-and-grad happens in one call.
    """
    import jax
    import jax.numpy as jnp

    from ..models.llama import rms_norm
    from ..models.pipeline import _stage_apply

    base = state["base"]
    params = state["params"]
    rank, size = state["rank"], state["size"]
    tokens = jnp.asarray(tokens_mb)
    mb, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (mb, t))

    if rank == 0:
        def fwd0(p):
            x = p["embed"][tokens].astype(jnp.float32)
            return _stage_apply(p["stage"], x, positions, base)

        y, vjp = jax.vjp(fwd0, params)
        return np.asarray(jax.block_until_ready(y), np.float32), vjp

    x = jnp.asarray(x_in, jnp.float32)
    if rank < size - 1:
        def fwd(p, a):
            return _stage_apply(p["stage"], a, positions, base)

        y, vjp = jax.vjp(fwd, params, x)
        return np.asarray(jax.block_until_ready(y), np.float32), vjp

    def loss_fn(p, a):
        y = _stage_apply(p["stage"], a, positions, base)
        h = rms_norm(y, p["final_norm"], base.norm_eps)
        logits = (h @ p["lm_head"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(
            logp, tokens[:, 1:][..., None], axis=-1)[..., 0]
        return jnp.mean(nll)

    (loss, (g_params, g_x)) = (
        jax.value_and_grad(loss_fn, argnums=(0, 1))(params, x)
    )
    jax.block_until_ready(loss)
    return float(loss), g_params, np.asarray(g_x, np.float32)


def _mpmd_backward(vjp, g_y: np.ndarray):
    """Pull the received cotangent through this rank's forward: returns
    (param grads, input cotangent — None on rank 0)."""
    import jax
    import jax.numpy as jnp

    out = vjp(jnp.asarray(g_y, jnp.float32))
    if len(out) == 1:  # rank 0: vjp was params-only
        return out[0], None
    g_params, g_x = out
    jax.block_until_ready(g_params)
    return g_params, np.asarray(g_x, np.float32)


def _mpmd_sgd(params: dict, grads, lr: float) -> dict:
    import jax

    if grads is None:
        return params
    return jax.tree.map(lambda p, g: p - lr * g, params, grads)


__all__ = ["GangRunner", "GangAborted"]
