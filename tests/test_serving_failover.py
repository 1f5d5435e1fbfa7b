"""Serving session failover (ISSUE 12, docs/SERVING.md §Migration, drain,
and failover): live KV-page migration (engine-level and worker-level,
token-identical to the sequential oracle), the (session, offset) resume
handshake under severed/asymmetric links, graceful drain with zero
CANCELLED sessions, scheduler-side crash failover with the forced-decode
resume prefix, and affinity eviction for dead/draining workers."""
import asyncio
import random

import pytest

from cordum_tpu.infra.config import Timeouts
from cordum_tpu.serving.engine import (
    GenRequest,
    ServingEngine,
    SessionMigrated,
    SessionRequeued,
)
from cordum_tpu.serving.migration import MigrationServer, migrate_session

from .fakes import FakeBackend, fake_ref, run_blocking


def make_engine(**kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_context", 512)
    step_delay = kw.pop("step_delay", 0.005)
    eng_kw = {k: kw.pop(k) for k in ("max_sessions", "max_new_tokens_cap")
              if k in kw}
    be = FakeBackend(step_delay=step_delay, **kw)
    return ServingEngine(be, run_blocking=run_blocking,
                         max_new_tokens_cap=eng_kw.get("max_new_tokens_cap", 600),
                         max_sessions=eng_kw.get("max_sessions", 8))


def install_into(engine, results: dict):
    """A MigrationServer install callback adopting sessions into `engine`
    and collecting their final token lists into `results`."""

    async def install(meta, state, records):
        req = GenRequest(
            prompt=meta["prompt"], max_new_tokens=meta["max_new_tokens"],
            session_key=meta["session_key"], eos_token=meta["eos_token"],
            stream=meta["stream"], resume_tokens=meta["resume_tokens"],
        )
        fut = await engine.install_session(
            req, job_id=meta["job_id"], state=state, records=records)

        async def watch():
            try:
                results[meta["job_id"]] = await fut
            except Exception as e:  # noqa: BLE001 - surfaced by the test
                results[meta["job_id"]] = e

        asyncio.ensure_future(watch())

    return install


async def wait_until(cond, timeout_s=20.0, msg="condition"):
    import time as _t

    deadline = _t.monotonic() + timeout_s
    while _t.monotonic() < deadline:
        v = cond()
        if asyncio.iscoroutine(v):
            v = await v
        if v:
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


# ------------------------------------------------------- engine-level moves


async def test_migrate_mid_decode_token_identical():
    """A session migrated mid-decode finishes on the target with EXACTLY
    the tokens an unmigrated run produces; the source's waiter sees
    SessionMigrated (publishes nothing) and both arenas end clean."""
    a, b = make_engine(step_delay=0.01), make_engine(step_delay=0.01)
    results: dict = {}
    srv = MigrationServer(install_into(b, results))
    await srv.start()
    src = asyncio.ensure_future(a.submit(
        GenRequest(prompt=[1, 2, 3], max_new_tokens=40, stream=False),
        job_id="m1"))
    await wait_until(
        lambda: (a.export_state("m1") or {}).get("pos", 0) >= 8,
        msg="session decoding")
    assert await migrate_session(a, "m1", srv.host, srv.port,
                                 metrics=a.metrics) is True
    with pytest.raises(SessionMigrated):
        await asyncio.wait_for(src, timeout=5)
    await wait_until(lambda: "m1" in results, msg="target finished")
    assert results["m1"] == fake_ref([1, 2, 3], 40)
    assert a.allocator.used_pages == 0
    assert a.stats.migrated_out == 1 and b.stats.migrated_in == 1
    await wait_until(  # what the target still holds is its prefix cache's
        lambda: b.allocator.used_pages == b.prefix.warm_pages, msg="target freed")
    await a.stop(), await b.stop(), await srv.stop()


async def test_migrate_real_backend_matches_oracle():
    """Real paged-Llama KV pages move worker→worker at their true lengths
    and the resumed session reproduces the fp32 sequential oracle exactly —
    migration is a placement change, not a math change."""
    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend

    from .test_serving import ref_greedy

    cfg = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=128,
                            dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    bea = LlamaServingBackend(cfg, num_pages=64, page_size=8,
                              params_provider=lambda: params)
    beb = LlamaServingBackend(cfg, num_pages=64, page_size=8,
                              params_provider=lambda: params)
    a = ServingEngine(bea, run_blocking=run_blocking, max_new_tokens_cap=64)
    b = ServingEngine(beb, run_blocking=run_blocking, max_new_tokens_cap=64)
    results: dict = {}
    srv = MigrationServer(install_into(b, results))
    await srv.start()
    prompt = [7, 3, 11, 19, 2, 5, 23, 1, 13]  # spans two pages
    src = asyncio.ensure_future(a.submit(
        GenRequest(prompt=prompt, max_new_tokens=24, stream=False),
        job_id="r1"))
    # migrate once several pages are live (prompt prefilled + some decode)
    await wait_until(
        lambda: (a.export_state("r1") or {}).get("pos", 0) >= 12,
        timeout_s=120, msg="multi-page decode state")
    assert await migrate_session(a, "r1", srv.host, srv.port) is True
    with pytest.raises(SessionMigrated):
        await asyncio.wait_for(src, timeout=10)
    await wait_until(lambda: "r1" in results, timeout_s=120,
                     msg="target finished")
    assert results["r1"] == ref_greedy(cfg, params, prompt, 24)
    await a.stop(), await b.stop(), await srv.stop()


async def test_forced_decode_resume_matches_oracle_real_backend():
    """Crash failover resumes by prefilling prompt + already-streamed
    tokens (forced decode): on the real paged backend the continuation is
    token-identical to the uninterrupted fp32 oracle at every cut point."""
    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend

    from .test_serving import ref_greedy

    cfg = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=128,
                            dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompt = [41, 7, 99, 3]
    oracle = ref_greedy(cfg, params, prompt, 12)
    for cut in (1, 5, 11, 12):  # incl. resume-of-a-finished-session
        be = LlamaServingBackend(cfg, num_pages=64, page_size=8,
                                 params_provider=lambda: params)
        eng = ServingEngine(be, run_blocking=run_blocking,
                            max_new_tokens_cap=64)
        out = await asyncio.wait_for(eng.submit(
            GenRequest(prompt=prompt, max_new_tokens=12, stream=False,
                       resume_tokens=oracle[:cut]),
            job_id=f"resume-{cut}"), timeout=120)
        assert out["tokens"] == oracle, f"cut={cut}"
        await eng.stop()


async def test_migrate_random_points_property():
    """Property: migrating a session at ANY point of its lifetime —
    mid-prefill, right after the first token, deep into decode — yields
    the oracle token sequence (randomized over prompts and cut points)."""
    rng = random.Random(17)
    for trial in range(4):
        a, b = make_engine(step_delay=0.002), make_engine(step_delay=0.002)
        results: dict = {}
        srv = MigrationServer(install_into(b, results))
        await srv.start()
        plen = rng.randint(1, 12)
        prompt = [rng.randrange(1, 200) for _ in range(plen)]
        n_new = rng.randint(4, 60)
        cut = rng.randint(0, plen + n_new - 2)
        jid = f"p{trial}"
        src = asyncio.ensure_future(a.submit(
            GenRequest(prompt=prompt, max_new_tokens=n_new, stream=False),
            job_id=jid))
        await wait_until(
            lambda: (a.export_state(jid) or {}).get("pos", 0) >= min(cut, 1),
            msg="session live")
        moved = await migrate_session(a, jid, srv.host, srv.port)
        if moved:
            with pytest.raises(SessionMigrated):
                await asyncio.wait_for(src, timeout=10)
            await wait_until(lambda: jid in results, msg="target finished")
            got = results[jid]
        else:
            got = (await asyncio.wait_for(src, timeout=10))["tokens"]
        assert got == fake_ref(prompt, n_new), (trial, prompt, n_new, cut)
        await a.stop(), await b.stop(), await srv.stop()


async def test_migration_handshake_resumes_from_receiver_offset():
    """The (session, offset) handshake: a sender that lost its connection
    mid page-stream reconnects, hears the receiver's record count, and
    resumes from there — the receiver ends with each page exactly once."""
    from cordum_tpu.infra.frames import encode_frame, read_frame

    b = make_engine()
    results: dict = {}
    srv = MigrationServer(install_into(b, results))
    await srv.start()
    # a first, doomed connection delivers hello + 2 page records, then dies
    reader, writer = await asyncio.open_connection(srv.host, srv.port)
    writer.write(encode_frame(["hello", {"session": "h1", "meta": {}}]))
    await writer.drain()
    ok = await read_frame(reader)
    assert ok[0] == "ok" and ok[1]["offset"] == 0
    for i in range(2):
        writer.write(encode_frame(
            ["page", {"session": "h1", "offset": i, "rec": {"i": i}}]))
    await writer.drain()
    await asyncio.sleep(0.05)
    writer.close()  # link severed mid-transfer
    # the reconnect hears offset=2 and must NOT resend records 0-1
    reader, writer = await asyncio.open_connection(srv.host, srv.port)
    writer.write(encode_frame(["hello", {"session": "h1", "meta": {}}]))
    await writer.drain()
    ok = await read_frame(reader)
    assert ok[1]["offset"] == 2, "receiver forgot its partial records"
    # duplicates below the offset are dropped, the next record appends
    writer.write(encode_frame(
        ["page", {"session": "h1", "offset": 1, "rec": {"i": "dup"}}]))
    writer.write(encode_frame(
        ["page", {"session": "h1", "offset": 2, "rec": {"i": 2}}]))
    # a commit at the wrong offset is rejected (no silent page loss)
    writer.write(encode_frame(
        ["commit", {"session": "h1", "offset": 7, "state": {}, "delta": []}]))
    await writer.drain()
    err = await read_frame(reader)
    assert err[0] == "error" and "offset" in err[1]["msg"]
    writer.close()
    assert "h1" not in results
    await b.stop()
    await srv.stop()


async def test_migration_survives_asymmetric_partition():
    """A blackholed reply path (requests arrive, acks vanish — the
    asymmetric partition ChaosProxy now models per-direction) fails the
    migration CLEANLY: the sender times out, unfreezes, and the session
    finishes locally with the oracle tokens — never stranded, never
    double-owned."""
    from cordum_tpu.infra.chaos import ChaosProxy

    a, b = make_engine(step_delay=0.005), make_engine(step_delay=0.005)
    results: dict = {}
    srv = MigrationServer(install_into(b, results))
    await srv.start()
    proxy = ChaosProxy(srv.host, srv.port)
    await proxy.start()
    src = asyncio.ensure_future(a.submit(
        GenRequest(prompt=[4, 5, 6], max_new_tokens=30, stream=False),
        job_id="asym"))
    await wait_until(
        lambda: (a.export_state("asym") or {}).get("pos", 0) >= 6,
        msg="session decoding")
    proxy.blackhole("s2c")  # hello reaches the server; the ok never returns
    moved = await migrate_session(a, "asym", proxy.listen_host, proxy.port,
                                  timeout_s=0.5)
    assert moved is False
    # the session decodes on, unfrozen, to the exact oracle output
    out = await asyncio.wait_for(src, timeout=20)
    assert out["tokens"] == fake_ref([4, 5, 6], 30)
    assert "asym" not in results  # the half-arrived transfer never installed
    proxy.restore()
    await proxy.stop(), await a.stop(), await b.stop(), await srv.stop()


async def test_install_refusal_and_crashed_loop_requeue():
    """Satellite: a target at max_sessions refuses the install (sender
    falls back, session survives locally); a crashed decode loop requeues
    its live sessions as SessionRequeued instead of failing them."""
    a = make_engine(step_delay=0.005)
    b = make_engine(step_delay=0.005, max_sessions=1)
    results: dict = {}
    srv = MigrationServer(install_into(b, results))
    await srv.start()
    # fill b's only session slot
    busy = asyncio.ensure_future(b.submit(
        GenRequest(prompt=[9], max_new_tokens=50, stream=False), job_id="busy"))
    await wait_until(lambda: b.active_sessions() == 1, msg="b busy")
    src = asyncio.ensure_future(a.submit(
        GenRequest(prompt=[1, 1], max_new_tokens=30, stream=False), job_id="rf"))
    await wait_until(
        lambda: (a.export_state("rf") or {}).get("pos", 0) >= 3,
        msg="session decoding")
    assert await migrate_session(a, "rf", srv.host, srv.port) is False
    out = await asyncio.wait_for(src, timeout=20)  # finishes locally
    assert out["tokens"] == fake_ref([1, 1], 30)
    assert (await asyncio.wait_for(busy, timeout=20))["tokens"] == fake_ref([9], 50)

    # crashed decode loop: a poisoned capacity hook escapes the step loop —
    # live sessions come back as SessionRequeued (scheduler failover), not
    # FAILED (satellite 2: bounded by the attempts counter upstream)
    class Boom:
        def observe(self, *a, **kw):
            raise RuntimeError("observer exploded")

    c = make_engine(step_delay=0.005)
    c.capacity = Boom()
    victim = asyncio.ensure_future(c.submit(
        GenRequest(prompt=[2, 2], max_new_tokens=30, stream=False), job_id="vc"))
    with pytest.raises(SessionRequeued):
        await asyncio.wait_for(victim, timeout=20)
    assert c.stats.requeued == 1 and c.stats.failed == 0
    await a.stop(), await b.stop(), await c.stop(), await srv.stop()


# ------------------------------------------------- strategy/affinity (sat 1)


def test_strategy_evicts_affinity_for_dead_and_draining_workers():
    """Affinity entries die WITH their worker: an explicit evict_worker
    (deregistration), a draining heartbeat, and a silently vanished
    registry entry all reroute the session immediately — not after the
    120s TTL — and count in the evicted outcome."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.protocol.types import Heartbeat, JobRequest, LABEL_SESSION_KEY

    from .test_serving import _affinity_fixture

    reg, strat = _affinity_fixture()
    metrics = Metrics()
    strat.metrics = metrics
    for wid in ("w-a", "w-b"):
        reg.update(Heartbeat(worker_id=wid, pool="tpu", max_parallel_jobs=16))
    req = JobRequest(job_id="t", topic="job.tpu.generate",
                     labels={LABEL_SESSION_KEY: "conv-ev"})
    assert strat.pick_subject(req) == "worker.w-a.jobs"
    # 1. explicit eviction (what the engine does when a worker deregisters:
    # registry removal + affinity eviction together)
    reg.remove("w-a")
    assert strat.evict_worker("w-a") == 1
    assert strat.session_affinity_evicted == 1
    assert strat.pick_subject(req) == "worker.w-b.jobs"
    # 2. draining heartbeat: the sticky worker is draining → entry dropped
    reg.update(Heartbeat(worker_id="w-b", pool="tpu", max_parallel_jobs=16,
                         draining=True))
    assert strat.pick_subject(req) == "job.tpu.generate"  # no live worker left
    assert strat.session_affinity_evicted == 2
    # 3. vanished worker (missed heartbeats → registry dropped it)
    reg.update(Heartbeat(worker_id="w-c", pool="tpu", max_parallel_jobs=16))
    assert strat.pick_subject(req) == "worker.w-c.jobs"
    reg.remove("w-c")
    strat.pick_subject(req)
    assert strat.session_affinity_evicted == 3
    assert metrics.session_affinity.value(outcome="evicted") == 3


async def test_scheduler_deregisters_draining_worker_on_heartbeat():
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, Heartbeat

    from .test_batching import make_stack

    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    await bus.publish(subj.HEARTBEAT, BusPacket.wrap(
        Heartbeat(worker_id="w-d", pool="tpu", max_parallel_jobs=4)))
    await bus.drain()
    assert eng.registry.get("w-d") is not None
    await bus.publish(subj.HEARTBEAT, BusPacket.wrap(
        Heartbeat(worker_id="w-d", pool="tpu", max_parallel_jobs=4,
                  draining=True)))
    await bus.drain()
    assert eng.registry.get("w-d") is None
    await eng.stop()
    await bus.close()


# --------------------------------------------- worker e2e: drain + failover


def make_serving_worker(bus, ms, wid, *, step_delay=0.01, **eng_kw):
    from cordum_tpu.worker.handlers import TPUCompute, make_tpu_handlers
    from cordum_tpu.worker.runtime import Worker

    w = Worker(bus=bus, store=ms, worker_id=wid, pool="tpu",
               topics=["job.tpu.>"], capabilities=["tpu"],
               heartbeat_interval_s=999)
    compute = TPUCompute(tp=1)
    w.register_default(make_tpu_handlers(compute))
    eng = ServingEngine(
        FakeBackend(num_pages=64, max_context=512, step_delay=step_delay),
        run_blocking=w.run_in_executor, tracer=w.tracer,
        max_new_tokens_cap=600, **eng_kw)
    w.attach_serving(eng)
    return w


class StreamTap:
    """Assembles per-job token streams by offset, asserting any replayed
    prefix agrees with what was already streamed (exactly-once check)."""

    def __init__(self):
        self.streams: dict[str, list[int]] = {}

    async def __call__(self, subject, pkt):
        pr = pkt.job_progress
        if pr is None or pr.status_hint != "stream":
            return
        buf = self.streams.setdefault(pr.job_id, [])
        off = pr.offset if pr.offset >= 0 else len(buf)
        for i, t in enumerate(pr.tokens):
            idx = off + i
            if idx == len(buf):
                buf.append(int(t))
            elif idx < len(buf):
                assert buf[idx] == int(t), (
                    f"replayed token diverges at {idx}: {buf[idx]} vs {t}")


async def test_drain_migrates_sessions_zero_cancelled():
    """ISSUE 12 drain acceptance: draining a worker with live sessions
    completes with ZERO CANCELLED/FAILED sessions — every session
    live-migrates to the peer, finishes token-identical to the oracle, and
    the client-visible stream (offset-assembled) is exactly the oracle."""
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, JobRequest

    from .test_batching import make_stack
    from .test_serving import settle

    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w1 = make_serving_worker(bus, ms, "w-dr1", step_delay=0.02)
    w2 = make_serving_worker(bus, ms, "w-dr2", step_delay=0.02)
    await w1.start()
    await w2.start()
    tap = StreamTap()
    await bus.subscribe(subj.PROGRESS, tap)
    await settle(bus)
    await w1.send_heartbeat()
    await w2.send_heartbeat()  # each worker learns the other's listener
    await settle(bus)
    n = 3
    jobs = {}
    for i in range(n):
        jid = f"dr{i}"
        prompt = [i + 1, 7, 3]
        jobs[jid] = prompt
        ptr = await ms.put_context(jid, {
            "op": "llm.generate", "tokens": prompt, "max_new_tokens": 60,
            "session_id": f"conv-dr{i}",
        })
        # pinned to w1 so the drain has real sessions to move
        await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(
            job_id=jid, topic="job.tpu.generate", context_ptr=ptr,
            labels={"preferred_worker_id": "w-dr1"})))
    await wait_until(lambda: w1.serving.active_sessions() == n,
                     msg="sessions decoding on w1")
    await wait_until(
        lambda: all(len(tap.streams.get(j, [])) >= 3 for j in jobs),
        msg="streams flowing")
    await w1.drain(timeout_s=30)
    assert w1.serving.session_count == 0
    assert w1.serving.stats.migrated_out == n
    assert w1.serving.stats.cancelled == 0 and w1.serving.stats.failed == 0
    assert w2.serving.stats.migrated_in == n

    async def all_done():
        for _ in range(2):
            await bus.drain()
        for j in jobs:
            if await js.get_state(j) != "SUCCEEDED":
                return False
        return True

    await wait_until(all_done, timeout_s=60, msg="all jobs SUCCEEDED")
    for jid, prompt in jobs.items():
        oracle = fake_ref(prompt, 60)
        res = await ms.get_result(jid)
        assert res["tokens"] == oracle, jid
        assert tap.streams[jid] == oracle, jid  # no dup/missing tokens
        events = [e.get("event") for e in await js.events(jid)]
        assert "cancelled" not in events
    # the drained worker beacons draining=True and took no new work
    assert w1.build_heartbeat().draining is True
    await w2.stop(), await w1.stop(), await eng.stop(), await bus.close()


async def test_worker_death_fails_sessions_over_with_resume_prefix():
    """ISSUE 12 crash acceptance (in-process twin of the chaos test): kill
    a serving worker mid-decode with 3 active sessions — the scheduler's
    WorkerFailover re-dispatches each to the peer with the streamed tokens
    as a forced-decode prefix, and every client-visible stream assembles to
    exactly the oracle output."""
    from cordum_tpu.controlplane.scheduler.reconciler import WorkerFailover
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, JobRequest

    from .test_batching import make_stack
    from .test_serving import settle

    kv, bus, js, ms, eng = make_stack()
    eng.registry.ttl_s = 1.0  # dead-worker detection window for the test
    await eng.start()
    w1 = make_serving_worker(bus, ms, "w-k1", step_delay=0.03)
    w2 = make_serving_worker(bus, ms, "w-k2", step_delay=0.005)
    await w1.start()
    await w2.start()
    tap = StreamTap()
    await bus.subscribe(subj.PROGRESS, tap)
    await settle(bus)
    # both workers heartbeat faster than the 1s registry TTL; w1's pump is
    # the thing the "SIGKILL" below silences
    hb1_task = asyncio.ensure_future(_heartbeat_pump(w1, 0.2))
    hb_task = asyncio.ensure_future(_heartbeat_pump(w2, 0.2))
    fo = WorkerFailover(eng, js, eng.registry,
                        Timeouts(scan_interval_s=0.2))
    await fo.start()
    n = 3
    jobs = {}
    for i in range(n):
        jid = f"kx{i}"
        prompt = [i + 2, 9, 4]
        jobs[jid] = prompt
        ptr = await ms.put_context(jid, {
            "op": "llm.generate", "tokens": prompt, "max_new_tokens": 80,
            "session_id": f"conv-kx{i}",
        })
        await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(
            job_id=jid, topic="job.tpu.generate", context_ptr=ptr,
            labels={"preferred_worker_id": "w-k1"}, tenant_id="default")))
    await wait_until(lambda: w1.serving.active_sessions() == n,
                     msg="sessions decoding on w1")
    await wait_until(
        lambda: all(len(tap.streams.get(j, [])) >= 4 for j in jobs),
        msg="streams flowing")
    hb1_task.cancel()
    await hard_kill(w1)  # SIGKILL semantics: silence, no cleanup

    async def all_done():
        for _ in range(2):
            await bus.drain()
        for j in jobs:
            if await js.get_state(j) != "SUCCEEDED":
                return False
        return True

    await wait_until(all_done, timeout_s=60, msg="sessions resumed on w-k2")
    for jid, prompt in jobs.items():
        oracle = fake_ref(prompt, 80)
        res = await ms.get_result(jid)
        assert res["tokens"] == oracle, jid
        # exactly-once client stream across the crash: the offset-assembled
        # sequence equals the oracle (the StreamTap also asserted the
        # replayed prefix agreed token-for-token)
        assert tap.streams[jid] == oracle, jid
        events = [e.get("event") for e in await js.events(jid)]
        assert "failover" in events, events
    assert eng.metrics.session_failovers.value(reason="worker_dead") >= n
    # the failed-over sessions really resumed mid-stream: w2 decoded fewer
    # tokens than the full oracle for at least one session
    assert w2.serving.stats.migrated_in == 0  # crash path ships no pages
    hb_task.cancel()
    await fo.stop()
    await w2.stop(), await eng.stop(), await bus.close()


async def _heartbeat_pump(worker, interval_s: float):
    while True:
        await asyncio.sleep(interval_s)
        try:
            await worker.send_heartbeat()
        except Exception:  # noqa: BLE001 - bus closing at teardown
            return


async def hard_kill(w):
    """SIGKILL semantics in-process: subscriptions vanish, the decode loop
    dies mid-step, and NOTHING is published — no cancels, no results, no
    final heartbeat (contrast Worker.stop / Worker.drain)."""
    for s in [*w._subs, *w._topic_subs]:
        s.unsubscribe()
    w._subs, w._topic_subs = [], []
    if w._hb_task:
        w._hb_task.cancel()
    if w._migration is not None:
        await w._migration.stop()
    eng = w._serving
    if eng is not None:
        eng._closed = True  # no restarts, no eviction publishes
        if eng._loop_task is not None:
            eng._loop_task.cancel()
        # let the dead worker's in-process coroutines unwind WITHOUT
        # publishing anything (SessionMigrated is the publish-nothing
        # path) — a real SIGKILL'd process just vanishes, but these tasks
        # share our event loop and would otherwise wedge bus.drain()
        for sess in [*eng._pending, *eng._active.values()]:
            if not sess.future.done():
                sess.future.set_exception(SessionMigrated(sess.job_id))
    w._executor.shutdown(wait=False)


async def test_drain_without_peers_requeues_and_recovers():
    """Satellite 2 end-to-end: a drain with NO migration target requeues
    its sessions (SESSION_REQUEUE, never CANCELLED); the scheduler fails
    them over, and once a worker joins, the replayer's nudge hands the job
    to it — the client's assembled stream is still exactly the oracle."""
    from cordum_tpu.controlplane.scheduler.reconciler import PendingReplayer
    from cordum_tpu.infra.jobstore import JobStore
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, JobRequest

    from .test_batching import make_stack
    from .test_serving import settle

    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w1 = make_serving_worker(bus, ms, "w-rq1", step_delay=0.02)
    await w1.start()
    tap = StreamTap()
    await bus.subscribe(subj.PROGRESS, tap)
    await settle(bus)
    rep = PendingReplayer(eng, JobStore(kv), Timeouts(
        scan_interval_s=0.2, pending_replay_s=60.0, dispatch_timeout_s=60.0,
        result_replay_s=0.5))
    await rep.start()
    ptr = await ms.put_context("rq1", {
        "op": "llm.generate", "tokens": [5, 5], "max_new_tokens": 30,
        "session_id": "conv-rq",
    })
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(
        job_id="rq1", topic="job.tpu.generate", context_ptr=ptr,
        tenant_id="default")))
    await wait_until(lambda: len(tap.streams.get("rq1", [])) >= 3,
                     msg="stream flowing on w1")
    await w1.drain(timeout_s=10)  # fleet of one: nowhere to migrate
    assert w1.serving.stats.requeued == 1
    assert w1.serving.stats.cancelled == 0 and w1.serving.stats.failed == 0
    await settle(bus)
    assert await js.get_state("rq1") == "RUNNING"  # failed over, not killed
    # a replacement worker joins; the replayer's nudge hands the job over
    w2 = make_serving_worker(bus, ms, "w-rq2", step_delay=0.005)
    await w2.start()

    async def done():
        for _ in range(2):
            await bus.drain()
        return await js.get_state("rq1") == "SUCCEEDED"

    await wait_until(done, timeout_s=30, msg="job recovered on w2")
    oracle = fake_ref([5, 5], 30)
    assert (await ms.get_result("rq1"))["tokens"] == oracle
    # the fresh run replayed from offset 0; dedupe-by-offset keeps the
    # assembled client stream exactly-once
    assert tap.streams["rq1"] == oracle
    events = [e.get("event") for e in await js.events("rq1")]
    assert "failover" in events and "cancelled" not in events, events
    await rep.stop()
    await w2.stop(), await w1.stop(), await eng.stop(), await bus.close()


# --------------------------------------------------- gateway + sdk surface


class SlowServingGwStack:
    """Gateway + scheduler + a SLOW serving worker behind live HTTP — slow
    enough that a mid-stream replay injection has a real window."""

    def __init__(self):
        from .test_gateway import GwStack

        self.inner = GwStack()

    async def __aenter__(self):
        from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
        from cordum_tpu.infra.config import parse_pool_config

        s = self.inner
        pc = parse_pool_config({
            "topics": {"job.work": "p", "job.tpu.generate": "tpu"},
            "pools": {"p": {}, "tpu": {}},
        })
        s.scheduler.strategy = LeastLoadedStrategy(s.scheduler.registry, pc)
        await s.__aenter__()
        self.worker = make_serving_worker(s.bus, s.mem, "w-slow",
                                          step_delay=0.03)
        await self.worker.start()
        await s.settle()
        return self

    async def __aexit__(self, *exc):
        await self.worker.stop()
        await self.inner.__aexit__(*exc)


async def test_sdk_drain_endpoint_and_offset_dedupe():
    """`POST /api/v1/workers/{id}/drain` publishes the drain request, and
    the SDK stream iterator dedupes replayed offsets (an injected offset-0
    replay mid-stream — what a failed-over worker emits — must not
    duplicate client tokens)."""
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import (
        BusPacket, JobProgress, STATUS_HINT_STREAM,
    )
    from cordum_tpu.sdk.client import Client

    async with SlowServingGwStack() as st:
        s = st.inner
        drains = []

        async def drain_tap(subject, pkt):
            if pkt.worker_drain is not None:
                drains.append(pkt.worker_drain.worker_id)

        await s.bus.subscribe(subj.DRAIN, drain_tap)
        oracle = fake_ref([1, 2, 3], 20)
        injected = asyncio.Event()

        async def progress_tap(subject, pkt):
            # after the 2nd real token, replay the first two at offset 0 —
            # exactly the duplicate a failover catch-up packet produces
            pr = pkt.job_progress
            if (
                pr is not None and pr.status_hint == STATUS_HINT_STREAM
                and pr.worker_id == "w-slow" and not injected.is_set()
                and pr.offset + len(pr.tokens) >= 2
            ):
                injected.set()
                await s.bus.publish(subj.PROGRESS, BusPacket.wrap(JobProgress(
                    job_id=pr.job_id, status_hint=STATUS_HINT_STREAM,
                    worker_id="fake-replayer", tokens=list(oracle[:2]),
                    offset=0,
                )))

        await s.bus.subscribe(subj.PROGRESS, progress_tap)
        c = Client(str(s.client.make_url("")), api_key="user-key")
        try:
            doc = await c.drain_worker("some-worker", reason="test")
            assert doc["draining"] is True
            await s.settle()
            assert drains == ["some-worker"]
            got = [t async for t in c.generate(
                [1, 2, 3], session_id="conv-dedupe", max_new_tokens=20,
                timeout_s=60)]
            assert injected.is_set(), "replay was never injected"
            assert got == oracle  # replay deduped, nothing duplicated
        finally:
            await c.close()
