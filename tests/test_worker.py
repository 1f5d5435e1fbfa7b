"""Worker runtime + end-to-end integration slice: gateway-role submit →
scheduler → TPU worker executing JAX ops → result pointer → terminal state.
This is the loopback equivalent of the reference's integration tests
(scheduler/integration_test.go) plus real XLA compute."""
import asyncio

import pytest

from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
from cordum_tpu.controlplane.scheduler.engine import Engine
from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
from cordum_tpu.infra.bus import LoopbackBus
from cordum_tpu.infra.config import parse_pool_config
from cordum_tpu.infra.jobstore import JobStore
from cordum_tpu.infra.kv import MemoryKV
from cordum_tpu.infra.memstore import MemoryStore
from cordum_tpu.infra.registry import WorkerRegistry
from cordum_tpu.protocol import subjects as subj
from cordum_tpu.protocol.types import BusPacket, JobCancel, JobRequest
from cordum_tpu.worker.handlers import TPUCompute, attach_default_tpu_worker
from cordum_tpu.worker.runtime import JobContext, Worker


async def settle(bus, rounds=6):
    for _ in range(rounds):
        await bus.drain()
        await asyncio.sleep(0.02)


def make_stack(policy_doc=None, pool_doc=None):
    kv = MemoryKV()
    bus = LoopbackBus()
    js = JobStore(kv)
    ms = MemoryStore(kv)
    kernel = SafetyKernel(policy_doc=policy_doc or {})
    reg = WorkerRegistry()
    pc = parse_pool_config(
        pool_doc or {"topics": {"job.default": "default", "job.tpu.>": "tpu"},
                     "pools": {"default": {}, "tpu": {"requires": ["tpu"]}}}
    )
    eng = Engine(bus=bus, job_store=js, safety=SafetyClient(kernel.check),
                 strategy=LeastLoadedStrategy(reg, pc), registry=reg)
    return kv, bus, js, ms, eng


async def test_worker_echo_roundtrip():
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w1", pool="default",
               topics=["job.default"], capabilities=["echo"], heartbeat_interval_s=999)

    async def echo(ctx: JobContext):
        return {"echo": ctx.payload}

    w.register("job.default", echo)
    await w.start()
    await settle(bus)

    ptr = await ms.put_context("j1", {"msg": "hi"})
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(job_id="j1", topic="job.default", context_ptr=ptr)))
    await settle(bus)
    assert await js.get_state("j1") == "SUCCEEDED"
    res = await ms.get_result("j1")
    assert res == {"echo": {"msg": "hi"}}
    meta = await js.get_meta("j1")
    assert meta["worker_id"] == "w1"
    assert meta["dispatch_subject"] == "worker.w1.jobs"
    await w.stop()
    await eng.stop()


async def test_blocking_sync_handler_keeps_heartbeats_flowing():
    """A plain-def handler doing blocking work is dispatched to the executor
    by the runtime, so heartbeats keep flowing while it runs (a blocking
    handler on the event loop would silently stop them)."""
    import time as _time

    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w1", pool="default",
               topics=["job.default"], heartbeat_interval_s=0.05)
    beats = []

    async def hb_tap(subject, pkt):
        if pkt.heartbeat and pkt.heartbeat.worker_id == "w1":
            beats.append((asyncio.get_running_loop().time(), pkt.heartbeat.active_jobs))

    await bus.subscribe(subj.HEARTBEAT, hb_tap)

    def blocking(ctx: JobContext):  # plain def: blocks its thread, not the loop
        _time.sleep(0.6)
        return {"ok": True}

    w.register("job.default", blocking)
    await w.start()
    await settle(bus)
    n0 = len(beats)
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(job_id="jb", topic="job.default")))
    # while the job blocks its executor thread, the loop must keep beating
    for _ in range(12):
        await bus.drain()
        await asyncio.sleep(0.06)
    assert await js.get_state("jb") == "SUCCEEDED"
    assert await ms.get_result("jb") == {"ok": True}
    during = len(beats) - n0
    assert during >= 5, f"heartbeats stalled during blocking handler ({during})"
    assert any(active > 0 for _, active in beats), "no heartbeat saw the active job"
    await w.stop()
    await eng.stop()


async def test_worker_failure_reported():
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w1", pool="default",
               topics=["job.default"], heartbeat_interval_s=999)

    async def boom(ctx):
        raise ValueError("bad payload")

    w.register("job.default", boom)
    await w.start()
    await settle(bus)
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(job_id="j1", topic="job.default")))
    await settle(bus)
    meta = await js.get_meta("j1")
    assert meta["state"] == "FAILED"
    assert meta["error_code"] == "ValueError"
    assert "bad payload" in meta["error_message"]
    dlq = [p for s, p in bus.published if s == subj.DLQ]
    assert dlq
    await w.stop(); await eng.stop()


async def test_worker_no_handler_fails_cleanly():
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w1", pool="default",
               topics=["job.default"], heartbeat_interval_s=999)
    await w.start()
    await settle(bus)
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(job_id="j1", topic="job.default")))
    await settle(bus)
    assert (await js.get_meta("j1"))["state"] == "FAILED"
    await w.stop(); await eng.stop()


async def test_worker_cancel_inflight():
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w1", pool="default",
               topics=["job.default"], heartbeat_interval_s=999)
    started = asyncio.Event()

    async def slow(ctx: JobContext):
        started.set()
        for _ in range(200):
            ctx.check_cancelled()
            await asyncio.sleep(0.01)
        return {"done": True}

    w.register("job.default", slow)
    await w.start()
    await settle(bus)
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(job_id="j1", topic="job.default")))
    await asyncio.wait_for(started.wait(), 5)
    await bus.publish(subj.CANCEL, BusPacket.wrap(JobCancel(job_id="j1", reason="test")))
    await settle(bus, rounds=12)
    # worker reported CANCELLED; store shows cancelled (scheduler cancel or result)
    assert (await js.get_meta("j1"))["state"] == "CANCELLED"
    await w.stop(); await eng.stop()


async def test_worker_redelivery_republishes_cached_result():
    """At-least-once: a redelivered completed job must republish its result
    without re-running the handler (reference worker result cache)."""
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w1", pool="default",
               topics=["job.default"], heartbeat_interval_s=999)
    runs = []

    async def handler(ctx):
        runs.append(ctx.request.job_id)
        return {"n": len(runs)}

    w.register("job.default", handler)
    await w.start()
    await settle(bus)
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(job_id="j1", topic="job.default")))
    await settle(bus)
    assert runs == ["j1"]
    # deliver the job packet again straight to the worker (simulated
    # redelivery; distinct bus msg-id so dedupe doesn't hide it)
    req = JobRequest(job_id="j1", topic="job.default", labels={"cordum.bus_msg_id": "redeliver"})
    await bus.publish("worker.w1.jobs", BusPacket.wrap(req))
    await settle(bus)
    assert runs == ["j1"]  # handler NOT re-run
    # and the result was republished on the bus
    results = [p for s, p in bus.published if s == subj.RESULT and p.job_result.job_id == "j1"]
    assert len(results) >= 2
    await w.stop(); await eng.stop()


async def test_worker_heartbeat_telemetry_flows_to_registry():
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w-tpu", pool="tpu",
               capabilities=["tpu"], heartbeat_interval_s=999)
    await w.start()
    await settle(bus)
    hb = eng.registry.get("w-tpu")
    assert hb is not None
    assert hb.chip_count == 8  # virtual CPU devices
    assert hb.devices_healthy


async def test_worker_progress_events():
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w1", pool="default",
               topics=["job.default"], heartbeat_interval_s=999)

    async def stepped(ctx: JobContext):
        await ctx.progress(50, "halfway")
        return {"ok": True}

    w.register("job.default", stepped)
    await w.start()
    await settle(bus)
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(job_id="j1", topic="job.default")))
    await settle(bus)
    evs = await js.events("j1")
    assert any(e.get("event") == "progress" and e.get("percent") == 50 for e in evs)


# ---------------------------------------------------------------- TPU ops e2e

@pytest.fixture(scope="module")
def compute():
    from cordum_tpu.models.embedder import EmbedderConfig

    return TPUCompute(tp=1, embedder_cfg=EmbedderConfig(n_layers=2, d_model=128, max_len=32))


async def test_e2e_tpu_ops(compute):
    """One worker serving echo/matmul/embed/infer ops end-to-end."""
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w-tpu", pool="tpu",
               topics=["job.tpu.>"], capabilities=["tpu"], heartbeat_interval_s=999)
    from cordum_tpu.worker.handlers import make_tpu_handlers

    w.register_default(make_tpu_handlers(compute))
    await w.start()
    await settle(bus)

    jobs = {
        "j-echo": {"op": "echo", "x": 1},
        "j-matmul": {"op": "matmul", "b": 2, "n": 64, "k": 64, "m": 64},
        "j-embed": {"op": "embed", "texts": ["hello tpu", "goodbye"]},
        "j-infer": {"op": "infer", "tokens": [[1, 2, 3], [4, 5]]},
    }
    for jid, payload in jobs.items():
        ptr = await ms.put_context(jid, payload)
        await bus.publish(subj.SUBMIT, BusPacket.wrap(
            JobRequest(job_id=jid, topic="job.tpu.ops", context_ptr=ptr)))
    for _ in range(60):
        await settle(bus, rounds=2)
        states = [await js.get_state(j) for j in jobs]
        if all(s == "SUCCEEDED" for s in states):
            break
    states = {j: await js.get_state(j) for j in jobs}
    assert all(s == "SUCCEEDED" for s in states.values()), states

    mm = await ms.get_result("j-matmul")
    assert mm["shape"] == [2, 64, 64] and mm["flops"] > 0
    embeds = await ms.get_result("j-embed")
    assert embeds["dim"] == 128 and len(embeds["embeddings"]) == 2
    inf = await ms.get_result("j-infer")
    assert len(inf["next_tokens"]) == 2
    await w.stop(); await eng.stop()


async def test_matmul_rectangular_shapes(compute):
    """k != m must not break the fori_loop carry (review regression)."""
    out = compute.matmul(2, 32, 48, 96, iters=3)
    assert out["shape"] == [2, 32, 96]
    assert out["flops"] == 2.0 * 2 * 32 * 48 * 96 * 7


async def test_result_status_not_deduped():
    """A terminal result must survive dedupe after a RUNNING hint (review
    regression)."""
    from cordum_tpu.protocol.types import JobResult

    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    reg_hb = eng.registry
    from cordum_tpu.protocol.types import Heartbeat

    reg_hb.update(Heartbeat(worker_id="w1", pool="default", max_parallel_jobs=4))
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(job_id="j1", topic="job.default")))
    await settle(bus)
    await bus.publish(subj.RESULT, BusPacket.wrap(JobResult(job_id="j1", status="RUNNING", worker_id="w1")))
    await settle(bus)
    await bus.publish(subj.RESULT, BusPacket.wrap(JobResult(job_id="j1", status="SUCCEEDED", worker_id="w1")))
    await settle(bus)
    assert await js.get_state("j1") == "SUCCEEDED"
    await eng.stop()


def test_topology_requirement_rejects_unknown_topology():
    from cordum_tpu.controlplane.scheduler.strategy import worker_satisfies
    from cordum_tpu.protocol.types import Heartbeat

    hb = Heartbeat(worker_id="w", capabilities=["tpu"], chip_count=8, slice_topology="")
    assert not worker_satisfies(hb, None, ["topology:2x2x2"])
    hb2 = Heartbeat(worker_id="w", capabilities=["tpu"], chip_count=8, slice_topology="2x2x2")
    assert worker_satisfies(hb2, None, ["topology:2x2x2"])


async def test_e2e_bad_op_fails(compute):
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w-tpu", pool="tpu",
               topics=["job.tpu.>"], capabilities=["tpu"], heartbeat_interval_s=999)
    from cordum_tpu.worker.handlers import make_tpu_handlers

    w.register_default(make_tpu_handlers(compute))
    await w.start()
    await settle(bus)
    ptr = await ms.put_context("j-bad", {"op": "nonsense"})
    await bus.publish(subj.SUBMIT, BusPacket.wrap(JobRequest(job_id="j-bad", topic="job.tpu.ops", context_ptr=ptr)))
    await settle(bus, rounds=10)
    meta = await js.get_meta("j-bad")
    assert meta["state"] == "FAILED" and "nonsense" in meta["error_message"]
    await w.stop(); await eng.stop()
