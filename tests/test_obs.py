"""Flight recorder: end-to-end span propagation through the in-process
stack, assembler critical-path math, collector retention caps, stage
histograms, and the DLQ bulk operations that ride this PR."""
import asyncio
import threading

from aiohttp.test_utils import TestClient, TestServer

from cordum_tpu.controlplane.gateway.app import Gateway
from cordum_tpu.controlplane.gateway.auth import BasicAuthProvider
from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
from cordum_tpu.controlplane.scheduler.engine import Engine as Scheduler
from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
from cordum_tpu.infra.bus import LoopbackBus
from cordum_tpu.infra.config import parse_pool_config
from cordum_tpu.infra.dlq import DLQEntry, DLQStore
from cordum_tpu.infra.jobstore import JobStore
from cordum_tpu.infra.kv import MemoryKV
from cordum_tpu.infra.memstore import MemoryStore
from cordum_tpu.infra.metrics import Histogram, Metrics
from cordum_tpu.infra.registry import WorkerRegistry
from cordum_tpu.infra.schemareg import SchemaRegistry
from cordum_tpu.obs import SpanCollector, Tracer, assemble, render_waterfall
from cordum_tpu.obs.tracer import current_trace_context
from cordum_tpu.protocol import subjects as subj
from cordum_tpu.protocol.types import BusPacket, Heartbeat, JobRequest, Span
from cordum_tpu.utils.ids import now_us
from cordum_tpu.worker.runtime import JobContext, Worker
from cordum_tpu.workflow.engine import Engine as WorkflowEngine
from cordum_tpu.workflow.store import WorkflowStore

POLICY = {
    "default_tenant": "default",
    "tenants": {"default": {"allow_topics": ["job.*", "job.>"]}},
    "rules": [],
}


class ObsStack:
    """Gateway + scheduler + embedded traced kernel + worker + collector on
    one loopback bus, behind a live HTTP server."""

    def __init__(self):
        self.kv = MemoryKV()
        self.bus = LoopbackBus()
        self.job_store = JobStore(self.kv)
        self.mem = MemoryStore(self.kv)
        self.kernel = SafetyKernel(
            policy_doc=POLICY, tracer=Tracer("safety-kernel", self.bus)
        )
        self.registry = WorkerRegistry()
        pc = parse_pool_config({"topics": {"job.work": "p"}, "pools": {"p": {}}})
        self.scheduler = Scheduler(
            bus=self.bus, job_store=self.job_store,
            safety=SafetyClient(self.kernel.check),
            strategy=LeastLoadedStrategy(self.registry, pc), registry=self.registry,
        )
        wf_store = WorkflowStore(self.kv)
        self.gw = Gateway(
            kv=self.kv, bus=self.bus, job_store=self.job_store, mem=self.mem,
            kernel=self.kernel, wf_store=wf_store,
            wf_engine=WorkflowEngine(store=wf_store, bus=self.bus, mem=self.mem),
            schemas=SchemaRegistry(self.kv), registry=self.registry,
            auth=BasicAuthProvider(["user-key"], admin_keys=["admin-key"]),
        )
        self.worker = Worker(bus=self.bus, store=self.mem, worker_id="w1", pool="p",
                             topics=["job.work"], heartbeat_interval_s=999)
        self.client = None

    async def __aenter__(self):
        async def handler(ctx: JobContext):
            p = ctx.payload if isinstance(ctx.payload, dict) else {}
            if p.get("fail"):
                raise RuntimeError("worker failure requested")
            with ctx.device_timer("device", op="test"):
                pass
            return {"done": True}

        self.worker.register("job.work", handler)
        self.registry.update(Heartbeat(worker_id="w1", pool="p", max_parallel_jobs=64))
        await self.kernel.reload()
        await self.scheduler.start()
        await self.worker.start()
        await self.gw.span_collector.start()
        self.gw._subs.append(await self.bus.subscribe(subj.DLQ, self.gw._tap_dlq))
        self.client = TestClient(TestServer(self.gw.app))
        await self.client.start_server()
        return self

    async def __aexit__(self, *exc):
        await self.client.close()
        await self.worker.stop()
        await self.scheduler.stop()
        await self.gw.span_collector.stop()
        for s in self.gw._subs:
            s.unsubscribe()
        await self.bus.close()

    async def settle(self, rounds=30):
        for _ in range(rounds):
            await self.bus.drain()
            await asyncio.sleep(0.01)

    def h(self, admin=False):
        return {"X-Api-Key": "admin-key" if admin else "user-key"}


# ---------------------------------------------------------------------------
# end-to-end propagation
# ---------------------------------------------------------------------------


async def test_span_propagation_end_to_end():
    async with ObsStack() as s:
        r = await s.client.post("/api/v1/jobs", headers=s.h(),
                                json={"topic": "job.work", "payload": {"x": 1}})
        assert r.status == 202
        doc = await r.json()
        trace_id = doc["trace_id"]
        await s.settle()
        assert await s.job_store.get_state(doc["job_id"]) == "SUCCEEDED"

        r = await s.client.get(f"/api/v1/traces/{trace_id}", headers=s.h())
        trace = await r.json()
        assert trace["span_count"] >= 5, trace
        assert {"gateway", "scheduler", "safety-kernel", "worker"} <= set(trace["services"])
        names = {sp["name"] for sp in trace["spans"]}
        assert {"submit", "schedule", "policy-check", "evaluate", "strategy",
                "dispatch", "execute", "device", "result"} <= names

        # tree consistency: every parent resolves, children start after
        # their parent, every span's clock is monotonic
        by_id = {sp["span_id"]: sp for sp in trace["spans"]}
        for sp in trace["spans"]:
            assert sp["start_us"] <= sp["end_us"]
            if sp["parent_span_id"]:
                parent = by_id.get(sp["parent_span_id"])
                assert parent is not None, f"orphan span {sp['name']}"
                assert sp["start_us"] >= parent["start_us"]
        # exactly one root: the gateway submit span
        roots = [sp for sp in trace["spans"] if not sp["parent_span_id"]]
        assert [sp["name"] for sp in roots] == ["submit"]
        assert trace["critical_path"], trace
        # stage table covers the canonical dispatch path
        assert trace["stages"]["execute"]["count"] == 1
        # the jobs grouping (legacy shape) still rides along
        assert trace["jobs"][0]["state"] == "SUCCEEDED"

        # per-stage histograms reached the gateway's /metrics
        r = await s.client.get("/metrics")
        text = await r.text()
        assert 'cordum_stage_seconds_count{service="worker",stage="execute"} 1' in text
        assert 'cordum_stage_seconds_count{service="gateway",stage="submit"} 1' in text

        # the CLI renderer consumes the same JSON
        out = render_waterfall(trace)
        assert f"trace {trace_id}" in out and "execute" in out


async def test_failed_job_span_marks_error():
    async with ObsStack() as s:
        r = await s.client.post("/api/v1/jobs", headers=s.h(),
                                json={"topic": "job.work", "payload": {"fail": True}})
        doc = await r.json()
        await s.settle()
        spans = await s.gw.span_collector.spans(doc["trace_id"])
        execute = [sp for sp in spans if sp.name == "execute"]
        assert execute and execute[0].status == "ERROR"
        assert execute[0].attrs.get("error_code") == "RuntimeError"


async def test_workflow_step_dispatch_traced(kv, bus):
    mem = MemoryStore(kv)
    store = WorkflowStore(kv)
    eng = WorkflowEngine(store=store, bus=bus, mem=mem)
    collector = SpanCollector(kv, bus)
    await collector.start()
    from cordum_tpu.workflow.models import Workflow

    wf = Workflow.from_dict({"id": "wf1", "name": "wf1",
                             "steps": {"a": {"topic": "job.work", "input": {"k": 1}}}})
    await store.put_workflow(wf)
    run = await eng.start_run("wf1", {"x": 1})
    await bus.drain()
    # the dispatched packet opened its own trace rooted at step-dispatch
    submit = [(subject, p) for subject, p in bus.published if subject == subj.SUBMIT]
    assert submit and submit[0][1].span_id
    spans = await collector.spans(submit[0][1].trace_id)
    assert [sp.name for sp in spans] == ["step-dispatch"]
    assert spans[0].attrs["run_id"] == run.run_id
    await collector.stop()


# ---------------------------------------------------------------------------
# assembler
# ---------------------------------------------------------------------------


def _mk(span_id, parent, name, start, end, service="svc"):
    return Span(span_id=span_id, parent_span_id=parent, trace_id="t",
                name=name, service=service, start_us=start, end_us=end)


def test_assembler_critical_path():
    spans = [
        _mk("a", "", "submit", 0, 100),
        _mk("b", "a", "schedule", 10, 40),
        _mk("c", "a", "dispatch", 40, 95),  # latest-ending child of a
        _mk("d", "c", "execute", 50, 90),
        _mk("e", "c", "policy-check", 45, 60),
    ]
    doc = assemble("t", spans)
    assert doc["critical_path"] == ["a", "c", "d"]
    assert doc["critical_path_us"] == 100  # root start → latest end on path
    assert doc["total_us"] == 100
    assert doc["span_count"] == 5
    depths = {sp["span_id"]: sp["depth"] for sp in doc["spans"]}
    assert depths == {"a": 0, "b": 1, "c": 1, "d": 2, "e": 2}
    assert doc["stages"]["execute"] == {"total_us": 40, "count": 1}
    # rows come back in start order
    assert [sp["span_id"] for sp in doc["spans"]] == ["a", "b", "c", "e", "d"]


def test_assembler_orphans_become_roots():
    spans = [
        _mk("x", "gone", "execute", 10, 30),
        _mk("y", "x", "device", 15, 25),
    ]
    doc = assemble("t", spans)
    assert doc["critical_path"] == ["x", "y"]
    assert doc["spans"][0]["depth"] == 0
    assert "no spans" in render_waterfall(assemble("t", []))


def test_assembler_stage_aggregation_sums_retries():
    spans = [
        _mk("a", "", "schedule", 0, 10),
        _mk("b", "", "schedule", 20, 50),
    ]
    doc = assemble("t", spans)
    assert doc["stages"]["schedule"] == {"total_us": 40, "count": 2}


# ---------------------------------------------------------------------------
# collector retention
# ---------------------------------------------------------------------------


async def test_collector_span_ring_buffer_cap(kv, bus):
    c = SpanCollector(kv, bus, max_spans_per_trace=5)
    for i in range(12):
        await c.add(_mk(f"s{i:02d}", "", "execute", i, i + 1))
    spans = await c.spans("t")
    assert len(spans) == 5
    assert [sp.span_id for sp in spans] == ["s07", "s08", "s09", "s10", "s11"]


async def test_collector_trace_eviction_cap(kv, bus):
    c = SpanCollector(kv, bus, max_traces=3)
    for i in range(6):
        sp = _mk(f"s{i}", "", "execute", i, i + 1)
        sp.trace_id = f"trace-{i}"
        await c.add(sp)
    alive = [t for t in (f"trace-{i}" for i in range(6)) if await c.spans(t)]
    assert alive == ["trace-3", "trace-4", "trace-5"]


async def test_collector_purge_older_than(kv, bus):
    c = SpanCollector(kv, bus)
    await c.add(_mk("a", "", "execute", 0, 1))
    assert await c.purge_older_than(now_us() + 1) == 1
    assert await c.spans("t") == []


async def test_collector_consumes_bus_spans(kv, bus):
    metrics = Metrics()
    c = SpanCollector(kv, bus, metrics=metrics)
    await c.start()
    t = Tracer("scheduler", bus)
    async with t.span("schedule", trace_id="tr-1"):
        pass
    await bus.drain()
    spans = await c.spans("tr-1")
    assert [sp.name for sp in spans] == ["schedule"]
    assert metrics.stage_seconds.quantile(0.5, stage="schedule", service="scheduler") is not None
    await c.stop()


# ---------------------------------------------------------------------------
# tracer context propagation
# ---------------------------------------------------------------------------


async def _sink(subject, pkt):
    return None


async def test_tracer_nested_spans_inherit_parent(bus):
    # a listener must exist: with no TRACE_SPAN subscriber the tracer
    # skips span publishing entirely (the 1×1 fast path)
    await bus.subscribe(subj.TRACE_SPAN, _sink)
    t = Tracer("svc", bus)
    async with t.span("outer", trace_id="tr") as outer:
        assert current_trace_context() == ("tr", outer.span_id)
        async with t.span("inner") as inner:
            assert inner.trace_id == "tr"
            assert inner.parent_span_id == outer.span_id
    assert current_trace_context() == ("", "")
    published = [p for s, p in bus.published if s == subj.TRACE_SPAN]
    assert [p.payload.name for p in published] == ["inner", "outer"]


async def test_tracer_untraced_spans_not_published(bus):
    await bus.subscribe(subj.TRACE_SPAN, _sink)
    t = Tracer("svc", bus)
    async with t.span("orphan") as sp:
        assert sp.trace_id == ""
    assert not [p for s, p in bus.published if s == subj.TRACE_SPAN]


async def test_tracer_error_marks_span(bus):
    await bus.subscribe(subj.TRACE_SPAN, _sink)
    t = Tracer("svc", bus)
    try:
        async with t.span("boom", trace_id="tr"):
            raise ValueError("x")
    except ValueError:
        pass
    (pkt,) = [p for s, p in bus.published if s == subj.TRACE_SPAN]
    assert pkt.payload.status == "ERROR"
    assert pkt.payload.attrs["error"] == "ValueError"


async def test_tracer_listening_and_record(bus):
    """``listening`` answers whether an emit would publish; ``record`` makes
    a finished span from the caller's own stamps, leaves the ambient context
    alone, and ``emit`` publishes it as it stands."""
    t = Tracer("worker", bus)
    assert not Tracer("worker").listening() and not t.listening()
    await bus.subscribe(subj.TRACE_SPAN, _sink)
    assert t.listening()
    sp = t.record("step.wait", trace_id="step-w-7", parent_span_id="root",
                  start_us=1_000, end_us=23_500, attrs={"k": "v"})
    assert (sp.name, sp.service, sp.trace_id, sp.parent_span_id) == (
        "step.wait", "worker", "step-w-7", "root")
    assert sp.span_id and sp.duration_us == 22_500 and sp.status == "OK"
    assert current_trace_context() == ("", "")
    root = t.record("step", trace_id="step-w-7", span_id="root",
                    start_us=1_000, end_us=24_000)
    assert root.span_id == "root"
    await t.emit(sp)
    await t.emit(root)
    got = [p.payload for s, p in bus.published if s == subj.TRACE_SPAN]
    assert [(g.name, g.start_us, g.end_us) for g in got] == [
        ("step.wait", 1_000, 23_500), ("step", 1_000, 24_000)]


async def test_serving_spans_reach_the_collector_and_assemble():
    """Through the worker: a generation's trace shows ``serving.queue`` and
    ``serving.prefill`` under its ``execute`` span, and a kept cycle is a
    stored trace of its own whose root ``step`` landed last."""
    from cordum_tpu.serving.backend import STEP_PHASES
    from tests.fakes import FakeBackend
    from tests.test_serving import make_serving_worker, make_stack, settle

    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    metrics = Metrics()
    collector = SpanCollector(kv, bus, metrics=metrics)
    await collector.start()
    w = make_serving_worker(bus, ms, backend=FakeBackend(num_pages=64, step_delay=0.002),
                            max_sessions=4)
    await w.start()
    await settle(bus)
    ptr = await ms.put_context("gen", {"op": "llm.generate", "tokens": [4, 5, 9],
                                       "max_new_tokens": 6})
    await bus.publish(subj.SUBMIT, BusPacket.wrap(
        JobRequest(job_id="gen", topic="job.tpu.generate", context_ptr=ptr),
        trace_id="tr-gen"))
    for _ in range(300):
        await settle(bus, rounds=2)
        if await js.get_state("gen") == "SUCCEEDED":
            break
    await w.stop()  # the engine flushes what it still holds
    await settle(bus)
    doc = assemble("tr-gen", await collector.spans("tr-gen"))
    by_name = {sp["name"]: sp for sp in doc["spans"]}
    execute = by_name["execute"]
    queue, prefill = by_name["serving.queue"], by_name["serving.prefill"]
    assert queue["parent_span_id"] == prefill["parent_span_id"] == execute["span_id"]
    assert execute["start_us"] <= queue["start_us"] and queue["end_us"] == prefill["start_us"]
    assert prefill["end_us"] <= execute["end_us"]
    assert not any(n.startswith("step") for n in by_name)  # cycles have their own traces
    step = await collector.spans("step-w-srv-0")
    assert [sp.name for sp in step] == [f"step.{p}" for p in STEP_PHASES] + ["step"]
    tree = assemble("step-w-srv-0", step)
    assert [sp["depth"] for sp in tree["spans"]].count(1) == 6
    assert tree["total_us"] == step[-1].duration_us == sum(sp.duration_us for sp in step[:-1])
    # the collector's stage histogram has the phases as stages
    assert 'stage="step.wait"' in metrics.render()
    await collector.stop()
    await eng.stop()


def test_gc_pauses_is_one_callback_for_all_its_holders():
    """``GcPauses``: one ``gc.callbacks`` entry while anybody holds it, every
    holder's ``Metrics`` fed from the same call, each pause kept with its
    bounds on the step spans' clock."""
    import gc
    import time

    from cordum_tpu.obs.profiler import GcPauses

    watch = GcPauses()
    before = list(gc.callbacks)
    a, b, quiet = Metrics(), Metrics(), object()
    watch.hold(a, a)
    watch.hold(b, b)
    watch.hold(quiet)  # a holder with no registry of its own (the serving engine)
    assert gc.callbacks == before + [watch._on_gc]
    t0 = time.time_ns()
    gc.collect()
    t1 = time.time_ns()
    assert watch.count == 1 and len(watch.pauses) == 1
    ordinal, start, end, gen = watch.pauses[-1]
    assert (ordinal, gen) == (1, 2) and t0 <= start <= end <= t1
    for m in (a, b):
        assert m.gc_pauses.value(generation="2") == 1
        assert sum(m.gc_pause_seconds._totals.values()) == 1
    watch.release(a)
    watch.release(a)  # releasing twice is releasing once
    gc.collect(0)
    assert a.gc_pauses.total() == 1 and b.gc_pauses.value(generation="0") == 1
    watch.release(b)
    assert gc.callbacks == before + [watch._on_gc]  # the quiet holder is still there
    watch.release(quiet)
    assert gc.callbacks == before
    gc.collect()
    assert watch.count == 2


def test_gc_pauses_since_gives_what_came_after_and_is_bounded():
    import gc

    from cordum_tpu.obs import profiler

    watch = profiler.GcPauses()
    holder = object()
    watch.hold(holder)
    try:
        gc.collect(0)
        seen = watch.count
        assert watch.since(seen) == []
        gc.collect(1)
        gc.collect(2)
        assert [(n, gen) for n, _, _, gen in watch.since(seen)] == [(2, 1), (3, 2)]
        assert [gen for _, _, _, gen in watch.since(0)] == [0, 1, 2]
        for _ in range(profiler.GC_PAUSES_KEPT + 5):
            gc.collect(0)
        assert len(watch.pauses) == profiler.GC_PAUSES_KEPT
        assert len(watch.since(seen)) == profiler.GC_PAUSES_KEPT  # the oldest fell out
        assert watch.count == profiler.GC_PAUSES_KEPT + 8
    finally:
        watch.release(holder)


async def test_the_new_serving_spans_assemble_inside_their_phases():
    """Through the collector: ``wait.fetch`` and ``emit.wake`` are
    grandchildren of a kept cycle's ``step``, and a park is a stored trace of
    its own."""
    from cordum_tpu.serving.backend import STEP_PHASES
    from cordum_tpu.serving.engine import GenRequest, ServingEngine
    from tests.fakes import run_blocking
    from tests.test_serving import ReadyFake

    kv, bus = MemoryKV(), LoopbackBus()
    collector = SpanCollector(kv, bus, metrics=Metrics())
    await collector.start()
    eng = ServingEngine(ReadyFake(num_pages=64, step_delay=0.002, max_context=256),
                        run_blocking=run_blocking, tracer=Tracer("worker", bus),
                        max_new_tokens_cap=128)
    eng.worker_id = "w-o"
    for job in ("a", "b"):
        await eng.submit(GenRequest(prompt=[4, 5], max_new_tokens=70, stream=False), job_id=job)
        await asyncio.sleep(0.01)
    await eng.stop()
    await bus.drain()
    stored = await collector.recent_trace_ids(50)
    kept = [t for t in stored if t.startswith("step-w-o-") and t != "step-w-o-0"]
    assert kept  # 140 steps of 2 ms: one at least began 250 ms after cycle 0
    tree = assemble(kept[0], await collector.spans(kept[0]))
    depth = {sp["name"]: sp["depth"] for sp in tree["spans"]}
    assert depth["step"] == 0 and {depth[f"step.{p}"] for p in STEP_PHASES} == {1}
    assert depth["wait.fetch"] == depth["emit.wake"] == 2
    (park,) = await collector.spans("loop-w-o-70")  # a's 70 steps done, b not yet there
    assert park.name == "serving.parked" and park.duration_us >= 10_000
    await collector.stop()


def test_span_wire_roundtrip():
    sp = _mk("a", "b", "execute", 1, 2)
    sp.attrs = {"k": "v"}
    pkt = BusPacket.wrap(sp, trace_id="t", sender_id="w", span_id="a", parent_span_id="b")
    decoded = BusPacket.from_wire(pkt.to_wire())
    assert decoded.span == sp
    assert decoded.span_id == "a" and decoded.parent_span_id == "b"
    # packets without span context keep the lean wire shape
    lean = BusPacket.wrap(JobRequest(job_id="j", topic="job.x"))
    assert "span_id" not in lean.to_dict()


# ---------------------------------------------------------------------------
# metrics: locked reads (satellite fix)
# ---------------------------------------------------------------------------


def test_histogram_render_during_concurrent_observe():
    h = Histogram("h_test", "x")
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            h.observe(0.001 * (i % 50), stage=f"s{i % 3}")
            i += 1

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for th in threads:
        th.start()
    try:
        for _ in range(200):
            for line in h.render():
                assert "h_test" in line
            h.quantile(0.5, stage="s0")
    except Exception as e:  # noqa: BLE001 - the assertion IS the test
        errors.append(e)
    finally:
        stop.set()
        for th in threads:
            th.join()
    assert not errors


# ---------------------------------------------------------------------------
# DLQ bulk operations (satellite)
# ---------------------------------------------------------------------------


async def test_dlq_purge_older_than(kv):
    dlq = DLQStore(kv)
    t0 = now_us()
    await dlq.add(DLQEntry(job_id="old", created_at_us=t0 - 10_000_000))
    await dlq.add(DLQEntry(job_id="new", created_at_us=t0))
    assert await dlq.purge_older_than(t0 - 5_000_000) == 1
    assert await dlq.get("old") is None
    assert await dlq.get("new") is not None


async def test_dlq_retry_all_redrives_and_keeps_failures(kv):
    dlq = DLQStore(kv)
    await dlq.add(DLQEntry(job_id="a", created_at_us=1))
    await dlq.add(DLQEntry(job_id="b", created_at_us=2))
    seen = []

    async def retry_fn(job_id):
        seen.append(job_id)
        return f"new-{job_id}" if job_id == "a" else None

    results = await dlq.retry_all(retry_fn)
    assert seen == ["a", "b"]  # oldest first
    assert dict(results) == {"a": "new-a", "b": None}
    assert await dlq.get("a") is None  # re-driven entry removed
    assert await dlq.get("b") is not None  # failed re-drive stays


async def test_dlq_bulk_routes():
    async with ObsStack() as s:
        # dead-letter a job by making the worker fail it
        r = await s.client.post("/api/v1/jobs", headers=s.h(),
                                json={"topic": "job.work", "payload": {"fail": True}})
        jid = (await r.json())["job_id"]
        await s.settle()
        assert await s.gw.dlq.count() == 1

        # non-admin denied
        r = await s.client.post("/api/v1/dlq/retry-all", headers=s.h())
        assert r.status == 403
        r = await s.client.post("/api/v1/dlq/purge", headers=s.h(admin=True), json={})
        assert r.status == 400  # cutoff required

        r = await s.client.post("/api/v1/dlq/retry-all", headers=s.h(admin=True))
        assert r.status == 202
        body = await r.json()
        assert body["count"] == 1
        assert body["retried"][0]["job_id"] == jid
        assert await s.gw.dlq.get(jid) is None
        await s.settle()  # retried job fails again → dead-lettered again
        assert await s.gw.dlq.count() == 1

        r = await s.client.post("/api/v1/dlq/purge", headers=s.h(admin=True),
                                json={"older_than_us": now_us() + 1_000_000})
        assert (await r.json())["purged"] == 1
        assert await s.gw.dlq.count() == 0
