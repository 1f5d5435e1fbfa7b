"""Capacity observatory (ISSUE 10): the worker device profiler and its
delta-encoded beacon block, the fleet throughput matrix (fold, restart,
staleness, gauges), tail-latency attribution (histogram exemplars end to
end, tail-based trace retention, cross-trace critical-path blame), the
metric label-cardinality guard, and the gateway/CLI surfaces."""
import asyncio
import random

from cordum_tpu.infra.bus import LoopbackBus
from cordum_tpu.infra.kv import MemoryKV
from cordum_tpu.infra.memstore import MemoryStore
from cordum_tpu.infra.metrics import Counter, Histogram, Metrics
from cordum_tpu.obs import (
    CapacityProfiler,
    FleetAggregator,
    SpanCollector,
    TailSampler,
    TelemetryExporter,
    Tracer,
    aggregate_critical_paths,
    assemble,
    critical_path_blame,
    render_blame,
    render_capacity_table,
)
from cordum_tpu.obs.assembler import UNTRACKED_STAGE
from cordum_tpu.protocol import subjects as subj
from cordum_tpu.protocol.types import BusPacket, JobRequest, Span
from cordum_tpu.utils.ids import now_us
from cordum_tpu.worker.runtime import JobContext, Worker
from tests.test_fleet import _FleetStack, _parse_exposition
from tests.test_worker import make_stack, settle


# ---------------------------------------------------------------------------
# worker device profiler
# ---------------------------------------------------------------------------


def test_profiler_compile_steady_split_and_rates():
    p = CapacityProfiler("TPU v5p")
    p.observe("embed", device_s=0.5, bucket="64", items=8, compiled=True)
    for _ in range(4):
        p.observe("embed", device_s=0.01, bucket="64", items=8)
    rows = {f"{r['op']}|{r['bucket']}": r for r in p.rows()}
    r = rows["embed|64"]
    assert r["n"] == 5 and r["items"] == 40
    assert r["compile_n"] == 1 and r["compile_s"] == 0.5
    # steady items/s excludes the compile call: 32 items over 0.04 s
    assert abs(r["items_per_s"] - 800.0) < 1e-6
    # the one 500 ms compile is exactly the p99 outlier the histogram keeps
    assert r["p99_ms"] == 500.0 and r["p50_ms"] <= 25.0
    assert 0 < r["ewma_ms"] < 500.0
    assert r["last_us"] > 0


def test_profiler_tokens_per_sec_and_row_overflow():
    p = CapacityProfiler("cpu", max_rows=3)
    p.observe("llm.generate", device_s=0.1, bucket="4", items=4, tokens=4)
    p.observe("llm.generate", device_s=0.1, bucket="4", items=4, tokens=4)
    rows = {r["op"]: r for r in p.rows()}
    assert abs(rows["llm.generate"]["tokens_per_s"] - 40.0) < 1e-6
    # row-count guard: unbounded (op, bucket) pairs fold into one overflow row
    for i in range(10):
        p.observe(f"op-{i}", device_s=0.001, bucket=str(i))
    rows = {f"{r['op']}|{r['bucket']}": r for r in p.rows()}
    assert len(rows) <= 4 and "overflow|-" in rows
    assert rows["overflow|-"]["n"] >= 8


def test_profiler_snapshot_delta_encoding():
    p = CapacityProfiler("cpu", full_every=4)
    p.observe("echo", device_s=0.001)
    first = p.snapshot()  # seq 0 → full
    assert first["full"] and "echo|-" in first["rows"]
    assert first["device_kind"] == "cpu" and first["ts_us"] > 0

    quiet = p.snapshot()  # nothing moved → no rows ride
    assert not quiet["full"] and quiet["rows"] == {}

    p.observe("echo", device_s=0.003)
    changed = p.snapshot()
    assert not changed["full"]
    # delta decides WHICH rows ride; the row itself is cumulative
    assert changed["rows"]["echo|-"]["n"] == 2

    p.snapshot()  # seq 3
    full_again = p.snapshot()  # seq 4 → periodic full
    assert full_again["full"] and full_again["rows"]["echo|-"]["n"] == 2


def test_profiler_gauge_callbacks_ride_snapshot():
    p = CapacityProfiler("cpu")
    p.set_kv_headroom(lambda: {"pages_total": 127, "pages_free": 100})
    p.set_occupancy(lambda: {"decode_mean": 5.5})
    blk = p.snapshot()
    assert blk["kv_pages"]["pages_free"] == 100
    assert blk["occupancy"]["decode_mean"] == 5.5


# ---------------------------------------------------------------------------
# fleet throughput matrix (fold, restart, staleness, gauges)
# ---------------------------------------------------------------------------


def _worker_beacon(agg, instance, profiler, *, started_shift=0, full=True):
    m = Metrics()
    exp = TelemetryExporter("worker", None, m, instance_id=instance)
    exp.started_at_us += started_shift
    exp.health_fn = lambda: {"role": "worker",
                             "capacity": profiler.snapshot(full=full)}
    snap = exp.build_snapshot()
    # a real beacon crosses the wire: prove msgpack round-trips the block
    decoded = BusPacket.from_wire(BusPacket.wrap(snap, sender_id=instance).to_wire())
    agg.ingest(decoded.telemetry)
    return exp


def test_capacity_matrix_folds_worker_beacons():
    agg = FleetAggregator(None)
    p1, p2 = CapacityProfiler("TPU v5p"), CapacityProfiler("cpu")
    p1.observe("embed", device_s=0.01, bucket="64", items=16)
    p1.observe("llm.generate", device_s=0.02, bucket="8", items=8, tokens=8)
    p2.observe("embed", device_s=0.1, bucket="64", items=16)
    _worker_beacon(agg, "w-tpu", p1)
    _worker_beacon(agg, "w-cpu", p2)
    doc = agg.capacity_doc()
    assert set(doc["workers"]) == {"w-tpu", "w-cpu"}
    assert doc["workers"]["w-tpu"]["device_kind"] == "TPU v5p"
    by = {(r["op"], r["worker"]): r for r in doc["matrix"]}
    # the heterogeneity signal: same op, 10x throughput gap across workers
    assert by[("embed", "w-tpu")]["items_per_s"] == 1600.0
    assert by[("embed", "w-cpu")]["items_per_s"] == 160.0
    assert by[("llm.generate", "w-tpu")]["tokens_per_s"] == 400.0
    assert doc["ops"]["embed"] == 1760.0
    # fleet exposition carries the matrix as gauges
    parsed = _parse_exposition(agg.render())
    series = parsed["cordum_capacity_items_per_sec"]
    assert series[frozenset({("op", "embed"), ("bucket", "64"),
                             ("worker", "w-tpu")})] == 1600.0
    assert parsed["cordum_capacity_tokens_per_sec"][
        frozenset({("op", "llm.generate"), ("bucket", "8"),
                   ("worker", "w-tpu")})] == 400.0
    table = render_capacity_table(doc)
    assert "embed" in table and "w-tpu" in table and "1600.0" in table


def test_capacity_rows_reset_across_worker_restart():
    """The satellite contract: a restarted worker's fresh capacity block
    replaces the dead epoch's rows instead of merging with them (counters
    fold-and-climb; capacity profiles are per-epoch rate views)."""
    agg = FleetAggregator(None)
    p = CapacityProfiler("cpu")
    for _ in range(10):
        p.observe("embed", device_s=0.01, bucket="64", items=8)
    p.observe("matmul", device_s=0.02, bucket="512x512x512", items=1)
    _worker_beacon(agg, "w0", p)
    doc = agg.capacity_doc()
    assert {r["op"] for r in doc["matrix"]} == {"embed", "matmul"}
    assert [r for r in doc["matrix"] if r["op"] == "embed"][0]["n"] == 10

    # restart: new process epoch, fresh profiler that has only seen 2 jobs
    p2 = CapacityProfiler("cpu")
    p2.observe("embed", device_s=0.01, bucket="64", items=8)
    p2.observe("embed", device_s=0.01, bucket="64", items=8)
    _worker_beacon(agg, "w0", p2, started_shift=1)
    doc = agg.capacity_doc()
    assert {r["op"] for r in doc["matrix"]} == {"embed"}  # matmul row gone
    row = doc["matrix"][0]
    assert row["n"] == 2 and row["worker"] == "w0"


def test_capacity_staleness_marks_rows_and_drops_from_totals():
    agg = FleetAggregator(None)
    p = CapacityProfiler("cpu")
    p.observe("embed", device_s=0.01, items=8)
    _worker_beacon(agg, "w-stale", p)
    inst = agg._instances[("worker", "w-stale")]
    inst.last_seen -= 3600.0  # beacon long overdue
    doc = agg.capacity_doc()
    assert doc["matrix"][0]["stale"] is True
    assert doc["ops"] == {}  # stale rows don't count toward fleet capacity
    # ... and stale rows don't become fleet gauges either
    assert "cordum_capacity_items_per_sec" not in agg.render()


# ---------------------------------------------------------------------------
# histogram exemplars (observe → exposition → telemetry → fleet)
# ---------------------------------------------------------------------------


def test_exemplar_round_trips_through_exposition_parsing():
    h = Histogram("h_ex", buckets=(0.25, 1.0))
    h.observe(0.2, exemplar="tr-fast", job_class="BATCH")
    h.observe(5.0, exemplar="tr-slow", job_class="BATCH")
    exs = {}
    parsed = _parse_exposition("\n".join(h.render()), exemplars=exs)
    assert parsed["h_ex_count"][frozenset({("job_class", "BATCH")})] == 2.0
    assert exs[("h_ex_bucket",
                frozenset({("job_class", "BATCH"), ("le", "0.25")}))] == "tr-fast"
    assert exs[("h_ex_bucket",
                frozenset({("job_class", "BATCH"), ("le", "+Inf")}))] == "tr-slow"


def test_exemplar_reaches_fleet_scope_through_telemetry():
    m = Metrics()
    m.e2e_latency.observe(0.2, exemplar="tr-e2e", job_class="BATCH")
    exp = TelemetryExporter("scheduler", None, m, instance_id="s0")
    snap = exp.build_snapshot()
    assert "exemplars" in snap.metrics["histograms"]["cordum_job_e2e_seconds"]
    agg = FleetAggregator(None)
    decoded = BusPacket.from_wire(BusPacket.wrap(snap, sender_id="s0").to_wire())
    agg.ingest(decoded.telemetry)
    exs = {}
    parsed = _parse_exposition(agg.render(), exemplars=exs)
    assert parsed["cordum_job_e2e_seconds_count"][
        frozenset({("job_class", "BATCH")})] == 1.0
    got = [tid for (name, _), tid in exs.items()
           if name == "cordum_job_e2e_seconds_bucket"]
    assert got == ["tr-e2e"]


async def test_exemplar_auto_captured_from_active_span():
    """Without an explicit exemplar, observe() picks up the active span's
    trace id via the provider cordum_tpu.obs registers at import."""
    tracer = Tracer("test", None)
    h = Histogram("h_auto", buckets=(1.0,))
    async with tracer.span("work", trace_id="tr-ambient"):
        h.observe(0.5)
    h.observe(0.5)  # outside any span: no exemplar attached
    exs = {}
    _parse_exposition("\n".join(h.render()), exemplars=exs)
    assert set(exs.values()) == {"tr-ambient"}


# ---------------------------------------------------------------------------
# label-cardinality guard
# ---------------------------------------------------------------------------


def test_counter_cardinality_guard_folds_into_overflow():
    c = Counter("c_guard", max_label_sets=10)
    for i in range(25):
        c.inc(job_id=f"job-{i}")  # the job-id-label mistake
    assert len(c._values) == 11  # 10 real series + the overflow series
    assert c.value(overflow="true") == 15.0
    assert c.total() == 25.0  # nothing lost, just folded
    # existing series keep incrementing normally after overflow
    c.inc(job_id="job-0")
    assert c.value(job_id="job-0") == 2.0
    _parse_exposition("\n".join(c.render()))  # still conformant


def test_histogram_cardinality_guard_folds_into_overflow():
    h = Histogram("h_guard", buckets=(1.0,), max_label_sets=5)
    for i in range(20):
        h.observe(0.5, key=f"k-{i}")
    assert len(h._totals) == 6
    snap = {k: total for k, _, _, total in h._snapshot()}
    assert snap[(("overflow", "true"),)] == 15
    assert sum(snap.values()) == 20
    _parse_exposition("\n".join(h.render()))


# ---------------------------------------------------------------------------
# tail-based trace retention
# ---------------------------------------------------------------------------


def test_tail_sampler_keeps_all_slow_samples_fast():
    """Steady-state 95/5 fast/slow mix: every slow trace is kept, the fast
    are sampled at ~keep_fraction, and verdicts are deterministic."""

    def run():
        s = TailSampler(0.2, window=100, min_samples=20)
        rng = random.Random(7)
        fast_verdicts, slow_verdicts = [], []
        for i in range(1200):
            # 10% slow keeps the rolling p95 firmly inside the slow band
            # (at 5% the window's 95th entry flaps across the boundary)
            slow = rng.random() < 0.10
            dur = 500_000 if slow else rng.randrange(1_000, 2_000)
            verdict = s.admit(f"t-{i}", dur)
            if i >= 200:  # let the rolling window reach steady state
                (slow_verdicts if slow else fast_verdicts).append(verdict)
        return fast_verdicts, slow_verdicts

    fast, slow = run()
    assert slow and all(slow)  # keeps-all-slow invariant
    assert 0.10 < sum(fast) / len(fast) < 0.35  # ~keep_fraction of the fast
    # deterministic: the same trace ids get the same verdicts
    fast2, slow2 = run()
    assert fast2 == fast and slow2 == slow


def test_tail_sampler_inactive_at_keep_fraction_one():
    s = TailSampler(1.0, min_samples=2)
    assert not s.active
    for i in range(100):
        assert s.admit(f"t-{i}", 1)  # everything kept: the default behavior


async def test_collector_tail_retention_drops_fast_keeps_slow():
    kv, bus, m = MemoryKV(), LoopbackBus(), Metrics()
    col = SpanCollector(kv, bus, metrics=m,
                        tail_keep_fraction=0.0, tail_min_samples=5)
    t0 = now_us()

    async def feed(tid, dur_us):
        await col.add(Span(span_id=f"{tid}-x", parent_span_id=f"{tid}-r",
                           trace_id=tid, name="execute", service="worker",
                           start_us=t0, end_us=t0 + dur_us // 2))
        await col.add(Span(span_id=f"{tid}-r", trace_id=tid, name="submit",
                           service="gateway", start_us=t0, end_us=t0 + dur_us))

    for i in range(8):  # warm the window (all kept while it warms)
        await feed(f"warm-{i}", 1000 + i)
    thr = col.tail_sampler.threshold_us()
    await feed("t-fast", 10)       # far under p95 → dropped (fraction 0.0)
    await feed("t-slow", thr * 50)  # tail → always kept
    assert await col.spans("t-fast") == []
    slow = await col.spans("t-slow")
    assert len(slow) == 2
    # a late span of the dropped trace must not resurrect it
    await col.add(Span(span_id="late", parent_span_id="t-fast-r",
                       trace_id="t-fast", name="result", service="scheduler",
                       start_us=t0, end_us=t0 + 5))
    assert await col.spans("t-fast") == []
    # accounting: 2 spans at drop time + 1 late skip
    assert m.spans_dropped.value(reason="tail_sampled") == 3.0
    # measurement is unsampled: the stage histograms saw every span
    assert m.stage_seconds.quantile(0.5, stage="submit",
                                    service="gateway") is not None
    counts = {k: t for k, _, _, t in m.stage_seconds._snapshot()}
    assert sum(counts.values()) == 21  # 16 warm + 2 fast(+late) + 2 slow


# ---------------------------------------------------------------------------
# cross-trace critical-path blame
# ---------------------------------------------------------------------------


def _chain_trace(rng, tid):
    """A random nested stage chain (occasionally an async child outliving
    its parent) → list[Span]."""
    names = ["submit", "schedule", "dispatch", "execute", "device"]
    depth = rng.randrange(2, len(names) + 1)
    t0 = rng.randrange(0, 10_000)
    total = rng.randrange(5_000, 200_000)
    spans = [Span(span_id=f"{tid}-0", trace_id=tid, name=names[0],
                  service="gateway", start_us=t0, end_us=t0 + total)]
    start, end = t0, t0 + total
    for d in range(1, depth):
        start = rng.randrange(start, end)
        if rng.random() < 0.2:
            end = end + rng.randrange(0, 5_000)  # child outlives parent
        else:
            end = rng.randrange(start + 1, end + 1)
        spans.append(Span(span_id=f"{tid}-{d}", parent_span_id=f"{tid}-{d-1}",
                          trace_id=tid, name=names[d], service="svc",
                          start_us=start, end_us=end))
    return spans


def test_blame_shares_sum_to_one_property():
    rng = random.Random(42)
    docs = [assemble(f"t{i}", _chain_trace(rng, f"t{i}")) for i in range(40)]
    agg = aggregate_critical_paths(docs)
    assert agg["traces"] == 40
    # the exact invariant: blame µs partition the critical-path time; the
    # published shares only carry 4-decimal rounding noise on top
    total = sum(s["total_us"] for s in agg["stages"].values())
    assert total == agg["critical_path_us_total"]
    share_sum = sum(s["blame_share"] for s in agg["stages"].values())
    assert abs(share_sum - 1.0) < 1e-3, agg["stages"]
    for st in agg["stages"].values():
        assert 0 <= st["p50_ms"] <= st["p99_ms"]


def test_blame_agrees_with_single_trace_assemble():
    """1-trace input: blame µs equal the trace's own critical-path exclusive
    times and sum exactly to assemble()'s critical_path_us."""
    spans = [
        Span(span_id="a", trace_id="t1", name="submit", service="gw",
             start_us=0, end_us=10_000),
        Span(span_id="b", parent_span_id="a", trace_id="t1", name="schedule",
             service="sch", start_us=1_000, end_us=4_000),
        Span(span_id="c", parent_span_id="b", trace_id="t1", name="execute",
             service="w", start_us=1_500, end_us=9_000),
    ]
    doc = assemble("t1", spans)
    assert doc["critical_path"] == ["a", "b", "c"]
    blame = critical_path_blame(doc)
    # execute owns 1500..9000; schedule owns 1000..1500; submit the rest
    assert blame == {"submit": 2_000, "schedule": 500, "execute": 7_500}
    assert sum(blame.values()) == doc["critical_path_us"]
    agg = aggregate_critical_paths([doc])
    assert {k: v["total_us"] for k, v in agg["stages"].items()} == blame
    assert agg["slowest"][0]["trace_id"] == "t1"
    out = render_blame(agg)
    assert "execute" in out and "75.0%" in out


def test_blame_untracked_gap_accounted():
    # root 0..10000 but its only child covers 1000..2000: the 8000 µs of
    # wall the root alone covers is the root's; a path GAP shows as the
    # child ending early with nothing after it
    spans = [
        Span(span_id="a", trace_id="t", name="submit", service="gw",
             start_us=0, end_us=2_000),
        Span(span_id="b", parent_span_id="a", trace_id="t", name="execute",
             service="w", start_us=500, end_us=10_000),
    ]
    doc = assemble("t", spans)
    blame = critical_path_blame(doc)
    assert blame["submit"] == 500 and blame["execute"] == 9_500
    assert UNTRACKED_STAGE not in blame
    # now a genuinely uncovered window: child detached in time
    spans[1].start_us, spans[1].end_us = 8_000, 10_000
    doc = assemble("t", spans)
    blame = critical_path_blame(doc)
    assert blame[UNTRACKED_STAGE] == 6_000  # 2000..8000 nobody measured
    assert sum(blame.values()) == doc["critical_path_us"]


def test_blame_empty_input():
    agg = aggregate_critical_paths([])
    assert agg["traces"] == 0 and agg["stages"] == {}
    assert "no traces" in render_blame(agg)


# ---------------------------------------------------------------------------
# worker runtime feeds the profiler
# ---------------------------------------------------------------------------


async def test_worker_jobs_feed_capacity_profiler():
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w1", pool="default",
               topics=["job.default"], capabilities=["echo"],
               heartbeat_interval_s=999)

    async def handler(ctx: JobContext):
        op = (ctx.payload or {}).get("op")
        if op == "timed":
            with ctx.device_timer("device", op="timed", items="4",
                                  bucket="64", compile_cached="false"):
                pass
            return {"ok": True}
        return {"echo": ctx.payload}

    w.register("job.default", handler)
    await w.start()
    await settle(bus)
    for i, payload in enumerate(({"op": "echo"}, {"op": "echo"},
                                 {"op": "timed"})):
        ptr = await ms.put_context(f"j{i}", payload)
        await bus.publish(subj.SUBMIT, BusPacket.wrap(
            JobRequest(job_id=f"j{i}", topic="job.default", context_ptr=ptr)))
    await settle(bus)
    rows = {f"{r['op']}|{r['bucket']}": r for r in w.capacity.rows()}
    # host op without a device timer: execute wall feeds the matrix
    assert rows["echo|-"]["n"] == 2 and rows["echo|-"]["device_s"] > 0
    # device-timer records carry op/items/bucket + the compile split
    timed = rows["timed|64"]
    assert timed["items"] == 4 and timed["compile_n"] == 1
    # ... and the telemetry beacon carries the block
    health = w.telemetry_health()
    assert "echo|-" in health["capacity"]["rows"]
    await w.stop()
    await eng.stop()


async def test_worker_failed_jobs_do_not_pollute_capacity():
    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = Worker(bus=bus, store=ms, worker_id="w1", pool="default",
               topics=["job.default"], heartbeat_interval_s=999)

    async def boom(ctx: JobContext):
        raise RuntimeError("nope")

    w.register("job.default", boom)
    await w.start()
    await settle(bus)
    ptr = await ms.put_context("jf", {"op": "boom"})
    await bus.publish(subj.SUBMIT, BusPacket.wrap(
        JobRequest(job_id="jf", topic="job.default", context_ptr=ptr)))
    await settle(bus)
    assert await js.get_state("jf") == "FAILED"
    assert w.capacity.rows() == []
    await w.stop()
    await eng.stop()


async def test_serving_steps_feed_capacity_profiler():
    """Every ragged mixed step reports its delivered tokens at the static
    flat-buffer bucket — ONE row per worker, not a pow2 ladder — with the
    warmup compile flagged so steady-state tokens/s excludes it."""
    from cordum_tpu.serving.engine import GenRequest, ServingEngine
    from tests.fakes import FakeBackend, run_blocking

    cap = CapacityProfiler("cpu")
    be = FakeBackend(num_pages=64)
    eng = ServingEngine(be, run_blocking=run_blocking,
                        max_sessions=4, capacity=cap)
    await asyncio.gather(*(
        eng.submit(GenRequest(prompt=[1, 2, 3], max_new_tokens=5,
                              stream=False), job_id=f"j{i}")
        for i in range(3)
    ))
    await eng.stop()
    rows = [r for r in cap.rows() if r["op"] == "llm.generate"]
    # one static shape -> one (op, bucket) row at the flat-buffer width
    assert [r["bucket"] for r in rows] == [str(be.max_batch_tokens)]
    row = rows[0]
    # 3 sessions x 5 generated tokens (the first token of each comes from
    # its prefill-completing chunk, which now rides the same mixed step)
    assert row["tokens"] == 15 and row["items"] == row["tokens"]
    assert row["tokens_per_s"] > 0
    # the fake's first step is its "compile"; the split keeps it out of
    # the steady-state rate the fleet matrix reports
    assert row["compile_n"] == 1 and row["n"] > row["compile_n"]


# ---------------------------------------------------------------------------
# gateway surfaces
# ---------------------------------------------------------------------------


async def test_gateway_capacity_endpoint():
    async with _FleetStack() as s:
        p = CapacityProfiler("cpu")
        p.observe("embed", device_s=0.01, bucket="64", items=16)
        exp = TelemetryExporter("worker", s.bus, Metrics(), instance_id="w9")
        exp.health_fn = lambda: {"role": "worker",
                                 "capacity": p.snapshot(full=True)}
        await exp.publish_once()
        await s.bus.drain()
        r = await s.client.get("/api/v1/capacity", headers=s.h())
        assert r.status == 200
        doc = await r.json()
        assert doc["workers"]["w9"]["device_kind"] == "cpu"
        assert doc["matrix"][0]["op"] == "embed"
        assert doc["matrix"][0]["items_per_s"] == 1600.0
        assert doc["ops"] == {"embed": 1600.0}
        # fleet metrics scope exposes the matrix gauges
        r = await s.client.get("/metrics?scope=fleet", headers=s.h())
        assert "cordum_capacity_items_per_sec" in await r.text()


async def test_gateway_traces_analysis_endpoint():
    async with _FleetStack() as s:
        t0 = now_us()
        for i, tid in enumerate(("tr-a", "tr-b")):
            await s.gw.span_collector.add(Span(
                span_id=f"{tid}-r", trace_id=tid, name="submit",
                service="gateway", start_us=t0, end_us=t0 + 10_000 * (i + 1)))
            await s.gw.span_collector.add(Span(
                span_id=f"{tid}-e", parent_span_id=f"{tid}-r", trace_id=tid,
                name="execute", service="worker", start_us=t0 + 1_000,
                end_us=t0 + 8_000))
        r = await s.client.get("/api/v1/traces/analysis?last=10",
                               headers=s.h())
        assert r.status == 200
        doc = await r.json()
        assert doc["traces"] == 2
        assert {"submit", "execute"} <= set(doc["stages"])
        share_sum = sum(st["blame_share"] for st in doc["stages"].values())
        assert abs(share_sum - 1.0) < 1e-6
        # the slowest trace is the exemplar entry point
        assert doc["slowest"][0]["trace_id"] == "tr-b"
        assert render_blame(doc)  # renders without error
        # the literal route must not shadow real trace ids
        r = await s.client.get("/api/v1/traces/tr-a", headers=s.h())
        assert (await r.json())["span_count"] == 2


# ---------------------------------------------------------------------------
# CapacityView decode-side fields (ISSUE 14, docs/SERVING.md §Disaggregation)
# ---------------------------------------------------------------------------


def _decode_beacon(instance, *, started=1, seq=0, rows=None, kv=None,
                   occ=None, role=None, draining=False):
    """A worker telemetry snapshot whose capacity block carries the
    decode-side serving state (the Worker.telemetry_health shape)."""
    from cordum_tpu.protocol.types import TelemetrySnapshot

    block = {"v": 1, "seq": seq, "full": True, "device_kind": "cpu",
             "rows": rows or {}}
    if kv is not None:
        block["kv_pages"] = kv
    if occ is not None:
        block["occupancy"] = occ
    if role is not None:
        block["serving_role"] = role
    if draining:
        block["draining"] = True
    return TelemetrySnapshot(service="worker", instance=instance, seq=seq,
                             started_at_us=started, interval_s=2.0,
                             health={"role": "worker", "capacity": block})


def _mk_view(clock_box):
    from cordum_tpu.obs.capacity import CapacityView

    return CapacityView(clock=lambda: clock_box[0])


def test_capacity_view_folds_decode_side_fields():
    """Occupancy, kv_pages_free, serving role and the drain flag fold from
    worker beacons next to the throughput rows (PR 13 only tested the
    items/s path) — the ServingPlacer/DecodeRebalancer read side."""
    clock = [0.0]
    view = _mk_view(clock)
    view.ingest(_decode_beacon(
        "w1",
        rows={"llm.generate|28": {"op": "llm.generate", "bucket": "28",
                                  "items_per_s": 90.0, "tokens_per_s": 90.0},
              "llm.prefill|28": {"op": "llm.prefill", "bucket": "28",
                                 "items_per_s": 400.0,
                                 "tokens_per_s": 400.0}},
        kv={"pages_total": 127, "pages_free": 40, "pages_in_use": 87},
        occ={"active_sessions": 6, "decode_mean": 5.5, "decode_max": 8},
        role="decode"))
    assert view.token_rate("w1", "llm.generate") == 90.0
    assert view.token_rate("w1", "llm.prefill") == 400.0
    assert view.kv_pages("w1") == {"pages_total": 127, "pages_free": 40,
                                   "pages_in_use": 87}
    assert view.decode_occupancy("w1")["active_sessions"] == 6
    assert view.serving_role("w1") == "decode"
    assert view.draining("w1") is False
    assert view.serving_workers() == ["w1"]
    # a later beacon flips the drain flag
    view.ingest(_decode_beacon("w1", seq=1, draining=True,
                               kv={"pages_total": 127, "pages_free": 40}))
    assert view.draining("w1") is True


def test_capacity_view_decode_fields_staleness_expiry():
    """A silent worker's decode-side state reads as unmeasured past
    stale_after_s — the rebalancer must never act on a dead beacon."""
    clock = [0.0]
    view = _mk_view(clock)
    view.ingest(_decode_beacon(
        "w1", kv={"pages_total": 127, "pages_free": 3},
        occ={"active_sessions": 9}, role="decode"))
    assert view.kv_pages("w1")["pages_free"] == 3
    clock[0] += 100.0  # beacon silent past stale_after_s (15s)
    assert view.kv_pages("w1") == {}
    assert view.decode_occupancy("w1") == {}
    assert view.serving_role("w1") == ""
    assert view.draining("w1") is False
    assert view.serving_workers() == []


def test_capacity_view_decode_fields_restart_epoch_clear():
    """A restarted worker (new started_at_us) starts a fresh fold: the dead
    epoch's occupancy/pages must not linger under the new epoch."""
    clock = [0.0]
    view = _mk_view(clock)
    view.ingest(_decode_beacon(
        "w1", started=1, kv={"pages_total": 127, "pages_free": 2},
        occ={"active_sessions": 9}, role="prefill"))
    assert view.decode_occupancy("w1")["active_sessions"] == 9
    # restart: fresh epoch, no serving state beaconed yet
    view.ingest(_decode_beacon("w1", started=999, seq=0))
    assert view.kv_pages("w1") == {}
    assert view.decode_occupancy("w1") == {}
    assert view.serving_role("w1") == ""
    # the fresh epoch's own state folds normally
    view.ingest(_decode_beacon("w1", started=999, seq=1,
                               kv={"pages_total": 127, "pages_free": 120},
                               role="mixed"))
    assert view.kv_pages("w1")["pages_free"] == 120
    assert view.serving_role("w1") == "mixed"


def test_capacity_table_renders_worker_serving_columns():
    """`cordumctl capacity` surfaces per-worker kv_pages_free, decode
    occupancy and the draining flag (the renderer used to drop them)."""
    doc = {
        "workers": {
            "w-dec": {"service": "worker", "fresh": True, "rows": 1,
                      "serving_role": "decode", "draining": True,
                      "kv_pages": {"pages_total": 127, "pages_free": 40,
                                   "pages_in_use": 87, "prefix_pages": 12},
                      "occupancy": {"active_sessions": 6,
                                    "decode_mean": 5.5,
                                    "prefix_hit_rate": 0.86,
                                    "resident_warm": 6, "resident_cold": 18,
                                    "hibernated_sessions": 18}},
            "w-plain": {"service": "worker", "fresh": True, "rows": 1},
        },
        "matrix": [{"op": "llm.generate", "bucket": "28", "worker": "w-dec",
                    "items_per_s": 90.0, "tokens_per_s": 90.0}],
        "ops": {"llm.generate": 90.0},
    }
    table = render_capacity_table(doc)
    lines = table.splitlines()
    header = next(line for line in lines if "kv_free" in line)
    assert "sessions" in header and "draining" in header and "role" in header
    assert "pfx_pages" in header and "resident" in header and "hib" in header
    row = next(line for line in lines if line.startswith("w-dec"))
    assert "decode" in row and "40" in row and "87" in row
    assert "6" in row and "yes" in row  # sessions + draining flag
    # prefix cache + tiering columns (docs/SERVING.md §Prefix cache and
    # tiering): cached-page count, hit rate, warm/cold census, hibernated
    assert "12" in row and "86%" in row and "6w/18c" in row
    # a worker that doesn't beacon the fields degrades to "-" (not a crash)
    plain_doc = {"workers": {"w-old": {
        "service": "worker", "fresh": True, "rows": 1,
        "serving_role": "mixed",
        "kv_pages": {"pages_total": 64, "pages_free": 60}}},
        "matrix": [], "ops": {}}
    old_row = next(line for line in render_capacity_table(plain_doc)
                   .splitlines() if line.startswith("w-old"))
    assert old_row.count("-") >= 3  # pfx_pages, pfx_hit, resident, hib
    # a worker with no serving state stays out of the serving section but
    # the matrix still renders
    assert not any(line.startswith("w-plain") and "yes" in line
                   for line in lines if "kv_free" not in line)
    assert any("llm.generate" in line for line in lines)
