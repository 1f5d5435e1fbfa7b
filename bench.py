"""Headline benchmark: scheduled jobs/sec end-to-end through the control
plane (BASELINE.json north star: ≥1,000 scheduled TPU jobs/sec on v5p-8).

Four benches, one JSON line:

* ``scheduled_jobs_per_sec`` — burst submit through the real pipeline
  (gateway-role submit → scheduler engine w/ safety check, strategy, state
  machine → worker → result handling) over the in-process bus + KV store.
* ``p50_e2e_ms``/``p99_e2e_ms`` — PACED open-loop submission at a fixed
  offered rate with exact per-job submit→result timing (a burst benchmark
  is queueing-dominated and says nothing about latency).
* ``selections_per_sec`` — worker-selection throughput at 1000 workers
  (reference analogue: 18,234/s, BENCHMARKS.md:131).
* TPU compute: ``embeds_per_sec`` (context-engine embedder) and
  ``model_tokens_per_sec``+``mfu`` (Llama forward).  These run in a
  SUBPROCESS with a hard watchdog, one child at a time, while this parent
  stays off jax (a chip belongs to one process): a hung or crashed PJRT
  client must never take down the control-plane numbers, and any failure
  is reported in ``embed_error``/``model_error`` — never swallowed.  The
  ``tpu`` child exits non-zero on a host with no chip; no CPU timing is
  ever reported in its place.
* Micro-batching: ``batched_embeds_per_sec`` vs ``single_job_embeds_per_sec``
  through the REAL worker path (bus → context fetch → batch queue →
  bucketed XLA flush → result publish); the acceptance bar is ≥3× the
  single-job rate on the same host.

``--smoke`` runs a fast CI-sized pass (small job counts, cpu-only child).
Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""
from __future__ import annotations

import asyncio
import json
import math
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Optional

N_JOBS = int(os.environ.get("BENCH_JOBS", "3000"))
PACED_JOBS = int(os.environ.get("BENCH_PACED_JOBS", "1500"))
PACED_RATE = float(os.environ.get("BENCH_PACED_RATE", "1000"))  # jobs/s offered
STATEBUS_JOBS = int(os.environ.get("BENCH_STATEBUS_JOBS", "600"))
TELEMETRY_JOBS = int(os.environ.get("BENCH_TELEMETRY_JOBS", "2000"))
SHARDED_JOBS = int(os.environ.get("BENCH_SHARDED_JOBS", "2000"))
SHARDS = int(os.environ.get("BENCH_SHARDS", "4"))
SB_PARTITIONS = int(os.environ.get("BENCH_STATEBUS_PARTITIONS", "2"))
JAX_TIMEOUT_S = float(os.environ.get("BENCH_JAX_TIMEOUT_S", "420"))
BASELINE_JOBS_PER_SEC = 1000.0  # BASELINE.json north-star target

# bf16 peak FLOP/s per chip by TPU generation (public spec sheets); a TPU
# whose device_kind matches no key is an error, never a silent 0
PEAK_FLOPS = {"v5e": 197e12, "v5lite": 197e12, "v5p": 459e12, "v4": 275e12,
              "v6e": 918e12}


def _peak_flops(device_kind: str) -> float:
    kind = device_kind.lower().replace(" ", "")
    for gen, flops in PEAK_FLOPS.items():
        if gen in kind:
            return flops
    raise KeyError(
        f"no peak FLOP/s known for device_kind {device_kind!r}; add it to "
        "PEAK_FLOPS with its source")


def _make_stack():
    from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
    from cordum_tpu.controlplane.scheduler.engine import Engine
    from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
    from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.jobstore import JobStore
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.protocol.types import Heartbeat

    kv = MemoryKV()
    bus = LoopbackBus()
    js = JobStore(kv)
    kernel = SafetyKernel(
        policy_doc={
            "tenants": {"default": {"allow_topics": ["job.*", "job.>"]}},
            "rules": [
                {"id": "tpu", "match": {"topics": ["job.tpu.>"]}, "decision": "allow"},
            ],
        }
    )
    reg = WorkerRegistry()
    pc = parse_pool_config({"topics": {"job.bench": "bench"}, "pools": {"bench": {"requires": []}}})
    eng = Engine(
        bus=bus, job_store=js, safety=SafetyClient(kernel.check),
        strategy=LeastLoadedStrategy(reg, pc), registry=reg,
    )
    reg.update(Heartbeat(worker_id="bench-w", pool="bench", max_parallel_jobs=1 << 30))
    return kv, bus, js, eng


async def bench_scheduler(telemetry: bool = False,
                          n_jobs: Optional[int] = None,
                          profiling: bool = False) -> dict:
    """Burst throughput: N_JOBS submitted as fast as possible.

    ``telemetry=True`` attaches the full fleet telemetry plane (ISSUE 9) to
    the same loopback stack — a TelemetryExporter on the scheduler registry
    at an aggressive 0.25 s cadence plus the gateway-role FleetAggregator +
    SLOTracker — so interleaved plain/instrumented pairs measure the export
    overhead, and the post-run fleet snapshot is checked for correctness
    (merged counter == the engine registry, SLO burn rate present).

    ``profiling=True`` additionally turns on the ISSUE 10 capacity
    observatory instrumentation: histogram exemplar capture plus a
    per-job CapacityProfiler observation on the worker leg whose block
    rides the telemetry beacon — the instrumented half of the
    ``profiling_overhead_pct`` pairs.  ``profiling=False`` disables
    exemplar capture globally so the plain half really is plain."""
    from cordum_tpu.infra import metrics as metrics_mod
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, JobRequest, JobResult

    metrics_mod.set_exemplars_enabled(profiling)
    kv, bus, js, eng = _make_stack()
    await eng.start()

    agg = tracker = exporter = capacity = None
    if profiling:
        from cordum_tpu.obs.capacity import CapacityProfiler

        capacity = CapacityProfiler("cpu")
    if telemetry:
        from cordum_tpu.infra.metrics import Metrics
        from cordum_tpu.obs import FleetAggregator, SLOTracker, TelemetryExporter

        agg = FleetAggregator(bus, metrics=Metrics(), fine_step_s=0.5)
        await agg.start()
        tracker = SLOTracker.from_config(
            {"batch": {"job_class": "BATCH", "latency_ms": 1000,
                       "latency_target": 0.95}})

        def health() -> dict:
            doc = {"role": "scheduler",
                   "jobs_scheduled": eng.metrics.jobs_dispatched.total()}
            if capacity is not None:
                doc["capacity"] = capacity.snapshot()
            return doc

        exporter = TelemetryExporter(
            "scheduler", bus, eng.metrics, instance_id="bench-sched-0",
            interval_s=0.25, health_fn=health,
        )
        await exporter.start()

    async def worker_handler(subject, pkt):
        req = pkt.job_request
        if capacity is not None:
            t_h = time.perf_counter()
        await bus.publish(
            subj.RESULT,
            BusPacket.wrap(
                JobResult(job_id=req.job_id, status="SUCCEEDED", worker_id="bench-w"),
                trace_id=pkt.trace_id, sender_id="bench-w", span_id=pkt.span_id,
            ),
        )
        if capacity is not None:
            capacity.observe("bench", device_s=time.perf_counter() - t_h,
                             bucket="-", items=1)

    await bus.subscribe(subj.direct_subject("bench-w"), worker_handler, queue="w")

    jobs_target = N_JOBS if n_jobs is None else n_jobs
    t0 = time.perf_counter()
    for i in range(jobs_target):
        req = JobRequest(job_id=f"bench-{i}", topic="job.bench", tenant_id="default")
        await bus.publish(subj.SUBMIT, BusPacket.wrap(req, sender_id="bench"))
    await bus.drain()
    deadline = time.perf_counter() + 120
    while time.perf_counter() < deadline:
        await bus.drain()
        if eng.metrics.jobs_completed.value(status="SUCCEEDED") >= jobs_target:
            break
        await asyncio.sleep(0.01)
    dt = time.perf_counter() - t0
    n = eng.metrics.jobs_completed.value(status="SUCCEEDED")
    # per-job KV chatter on the full submit→result loop (the engine binds
    # cordum_kv_roundtrips_total to its store; ISSUE 4 acceptance metric)
    roundtrips = eng.metrics.kv_roundtrips.total()
    out = {
        "jobs": int(n), "seconds": dt,
        "jobs_per_sec": n / dt if dt > 0 else 0.0,
        "kv_roundtrips_per_job": roundtrips / n if n else 0.0,
    }
    if telemetry:
        # flush one final snapshot, then verify the fleet view end to end
        await exporter.publish_once()
        await bus.drain()
        agg.sample()
        doc = agg.fleet_doc(tracker)
        merged = doc["fleet"]["jobs_dispatched_total"]
        engine_total = eng.metrics.jobs_dispatched.total()
        slo = (doc.get("slo") or [{}])[0]
        w5 = (slo.get("windows") or {}).get("5m") or {}
        out["fleet_snapshot_ok"] = float(
            doc["healthy_services"] >= 1
            and merged == engine_total
            and engine_total > 0
            and isinstance(w5.get("burn_rate"), (int, float))
            and w5.get("total", 0) > 0
        )
        out["fleet_services"] = doc["healthy_services"]
        out["slo_burn_rate_5m"] = w5.get("burn_rate", -1.0)
        out["slo_state"] = slo.get("state", "")
        if capacity is not None:
            # capacity observatory correctness: the beacon-shipped profile
            # must come back out of the aggregator as a fresh non-zero
            # throughput-matrix row for the bench op
            cap = agg.capacity_doc()
            rows = [r for r in cap["matrix"]
                    if r["op"] == "bench" and not r["stale"]]
            out["capacity_matrix_ok"] = float(
                bool(rows)
                and rows[0]["items_per_s"] > 0
                and rows[0]["n"] >= jobs_target
                and cap["ops"].get("bench", 0.0) > 0
            )
            out["capacity_ops"] = len(cap["ops"])
        await exporter.stop()
        await agg.stop()
    await eng.stop()
    await bus.close()
    metrics_mod.set_exemplars_enabled(True)  # process-global: don't leak
    return out


async def bench_latency() -> dict:
    """Open-loop paced submission at PACED_RATE jobs/s offered load, exact
    submit→result latency per job (raw list, not a capped histogram), plus a
    per-stage breakdown derived from the flight-recorder spans the pipeline
    publishes on ``sys.trace.span``."""
    from cordum_tpu.obs.tracer import Tracer
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, JobRequest, JobResult

    kv, bus, js, eng = _make_stack()
    await eng.start()

    done: dict[str, float] = {}
    submitted: dict[str, float] = {}
    all_done = asyncio.Event()
    wtracer = Tracer("worker", bus)

    async def worker_handler(subject, pkt):
        req = pkt.job_request
        async with wtracer.span(
            "execute", trace_id=pkt.trace_id, parent_span_id=pkt.span_id
        ) as sp:
            pass  # zero-work execute: the span bounds result-publish timing
        await bus.publish(
            subj.RESULT,
            BusPacket.wrap(
                JobResult(job_id=req.job_id, status="SUCCEEDED", worker_id="bench-w"),
                trace_id=pkt.trace_id, sender_id="bench-w", span_id=sp.span_id,
            ),
        )

    async def result_tap(subject, pkt):
        res = pkt.job_result
        if res and res.job_id in submitted and res.job_id not in done:
            done[res.job_id] = time.perf_counter() - submitted[res.job_id]
            if len(done) >= PACED_JOBS:
                all_done.set()

    # stage breakdown straight from the span stream (exact durations, no
    # bucketing) — the same data the collector would persist
    stage_samples: dict[str, list[float]] = {}

    async def span_tap(subject, pkt):
        sp = pkt.span
        if sp is not None:
            stage_samples.setdefault(sp.name, []).append(sp.duration_us / 1000.0)

    await bus.subscribe(subj.direct_subject("bench-w"), worker_handler, queue="w")
    await bus.subscribe(subj.RESULT, result_tap)
    await bus.subscribe(subj.TRACE_SPAN, span_tap)

    # pace in 10ms ticks to keep sleep() syscalls off the per-job path
    tick = 0.010
    per_tick = max(1, int(PACED_RATE * tick))
    i = 0
    start = time.perf_counter()
    while i < PACED_JOBS:
        tick_t0 = time.perf_counter()
        for _ in range(min(per_tick, PACED_JOBS - i)):
            jid = f"lat-{i}"
            submitted[jid] = time.perf_counter()
            await bus.publish(
                subj.SUBMIT,
                BusPacket.wrap(JobRequest(job_id=jid, topic="job.bench"), sender_id="bench"),
            )
            i += 1
        # open loop: sleep the REMAINDER of the tick regardless of completions
        rem = tick - (time.perf_counter() - tick_t0)
        if rem > 0:
            await asyncio.sleep(rem)
    try:
        await asyncio.wait_for(all_done.wait(), timeout=60)
    except asyncio.TimeoutError:
        pass
    offered_dt = time.perf_counter() - start
    await eng.stop()
    await bus.close()
    lat = sorted(done.values())
    if not lat:
        return {"paced_completed": 0}

    def q(p: float) -> float:
        return lat[min(len(lat) - 1, int(p * len(lat)))] * 1000

    # per-stage p50s from the span stream (ISSUE stage names → bench keys)
    def stage_p50(name: str) -> float:
        vals = sorted(stage_samples.get(name, []))
        return vals[len(vals) // 2] if vals else 0.0

    stages = {
        "policy": stage_p50("policy-check"),
        "schedule": stage_p50("schedule"),
        "dispatch": stage_p50("dispatch"),
        "execute": stage_p50("execute"),
        "result_publish": stage_p50("result"),
    }
    return {
        "paced_completed": len(lat),
        "paced_offered_rate": PACED_JOBS / offered_dt,
        "p50_e2e_ms": q(0.50),
        "p90_e2e_ms": q(0.90),
        "p99_e2e_ms": q(0.99),
        "stage_p50_ms": {k: round(v, 3) for k, v in stages.items()},
    }


class _PerOpPipelineKV:
    """Bench-only degraded KV: delegates every op to the wrapped StateBusKV
    but downgrades ``pipe_execute`` (the jobstore hot path calls it
    directly) to one wire call PER buffered op, plus a version read per
    watch — the pre-pipelining wire behavior, so the statebus bench can
    report before/after on the same run."""

    def __init__(self, kv):
        self._kv = kv

    def __getattr__(self, name):
        return getattr(self._kv, name)

    async def pipe_execute(self, watches, ops):
        kv = self._kv
        for key, ver in watches.items():
            if await kv.version(key) != ver:
                return False, {}
        for op in ops:
            name, *args = op
            await getattr(kv, name)(*args)
        return True, {k: await kv.version(k) for k in watches}


async def bench_statebus(pipelined: bool, n_jobs: int, *,
                         replicated: bool = False) -> dict:
    """The schedule loop against a REAL TCP StateBusServer (the deployment
    the pipelining work targets): scheduler and worker hold separate
    connections, every KV op is a genuine wire round trip.

    ``replicated`` attaches a replica SUBPROCESS (async ack mode) tailing
    the primary's committed-record stream, so the reported throughput
    carries the full replication cost — frame fan-out on the primary plus
    a competing apply/ack process (ISSUE 8; ceiling in bench_floor.json).
    """
    from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
    from cordum_tpu.controlplane.scheduler.engine import Engine
    from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
    from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.jobstore import JobStore
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.infra.statebus import StateBusServer, connect
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, Heartbeat, JobRequest, JobResult

    srv = StateBusServer(port=0)
    await srv.start()
    url = f"statebus://127.0.0.1:{srv.port}"
    replica_child = None
    if replicated:
        rport = _free_ports(1)[0]
        me = os.path.abspath(__file__)
        replica_child = subprocess.Popen(
            [sys.executable, me, "--statebus-child", str(rport), url],
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        deadline = time.monotonic() + 60
        while not srv.repl.sessions:
            if time.monotonic() > deadline:
                replica_child.kill()
                raise TimeoutError("bench replica never attached")
            await asyncio.sleep(0.05)
    skv, sbus, sconn = await connect(url)  # scheduler "process"
    wkv, wbus, wconn = await connect(url)  # worker "process"
    try:
        kv = skv if pipelined else _PerOpPipelineKV(skv)
        js = JobStore(kv)
        kernel = SafetyKernel(
            policy_doc={"tenants": {"default": {"allow_topics": ["job.*", "job.>"]}}}
        )
        reg = WorkerRegistry()
        pc = parse_pool_config(
            {"topics": {"job.bench": "bench"}, "pools": {"bench": {"requires": []}}}
        )
        eng = Engine(
            bus=sbus, job_store=js, safety=SafetyClient(kernel.check),
            strategy=LeastLoadedStrategy(reg, pc), registry=reg,
        )
        reg.update(Heartbeat(worker_id="bench-w", pool="bench", max_parallel_jobs=1 << 30))
        await eng.start()

        async def worker_handler(subject, pkt):
            req = pkt.job_request
            await wbus.publish(
                subj.RESULT,
                BusPacket.wrap(
                    JobResult(job_id=req.job_id, status="SUCCEEDED", worker_id="bench-w"),
                    sender_id="bench-w",
                ),
            )

        await wbus.subscribe(subj.direct_subject("bench-w"), worker_handler, queue="w")

        t0 = time.perf_counter()
        for i in range(n_jobs):
            await sbus.publish(
                subj.SUBMIT,
                BusPacket.wrap(
                    JobRequest(job_id=f"sb-{i}", topic="job.bench", tenant_id="default"),
                    sender_id="bench",
                ),
            )
        deadline = time.perf_counter() + 120
        while time.perf_counter() < deadline:
            if eng.metrics.jobs_completed.value(status="SUCCEEDED") >= n_jobs:
                break
            await asyncio.sleep(0.01)
        dt = time.perf_counter() - t0
        n = eng.metrics.jobs_completed.value(status="SUCCEEDED")
        roundtrips = eng.metrics.kv_roundtrips.total()
        await eng.stop()
        out = {
            "jobs": int(n),
            "jobs_per_sec": n / dt if dt > 0 else 0.0,
            "kv_roundtrips_per_job": roundtrips / n if n else 0.0,
        }
        if replicated:
            # end-of-run lag: how far the replica trails when the burst ends
            # (async mode's loss window if the primary died right now)
            out["repl_lag_ops_end"] = max(
                (srv.repl.offset - s.acked_offset
                 for s in srv.repl.sessions.values()), default=-1)
        return out
    finally:
        await sconn.close()
        await wconn.close()
        await srv.stop()
        if replica_child is not None:
            replica_child.terminate()
            try:
                replica_child.wait(timeout=10)
            except subprocess.TimeoutExpired:
                replica_child.kill()


def bench_telemetry(pairs: int = 5) -> dict:
    """Fleet telemetry export cost + snapshot correctness (ISSUE 9).

    Interleaved (plain, instrumented) scheduler-burst pairs at the FULL
    telemetry job count (smoke-sized runs finish in ~0.1 s, putting startup
    noise in the same decade as the effect — the replication-overhead
    lesson), after one discarded warmup pair; the instrumented runs carry an
    exporter at 4 Hz plus the live aggregator/SLO tracker on the same loop.
    Reports the MEDIAN same-run overhead pct (ceiling-gated ≤5% in
    bench_floor.json) and the ``fleet_snapshot_ok`` flag: the post-run
    merged fleet counter must equal the engine registry and the SLO tracker
    must report a burn rate for the configured class.
    """
    import statistics

    n = TELEMETRY_JOBS
    asyncio.run(bench_scheduler(n_jobs=n))  # warmup: imports + allocator heat
    overheads = []
    last = {}
    for _ in range(pairs):
        plain = asyncio.run(bench_scheduler(n_jobs=n))
        instr = asyncio.run(bench_scheduler(telemetry=True, n_jobs=n))
        last = instr
        if plain["jobs_per_sec"]:
            overheads.append(
                100.0 * (1.0 - instr["jobs_per_sec"] / plain["jobs_per_sec"]))
    return {
        "telemetry_overhead_pct": round(
            statistics.median(overheads), 1) if overheads else 100.0,
        "telemetry_overhead_runs": [round(o, 1) for o in overheads],
        "fleet_snapshot_ok": last.get("fleet_snapshot_ok", 0.0),
        "fleet_services": last.get("fleet_services", 0),
        "slo_burn_rate_5m": last.get("slo_burn_rate_5m", -1.0),
        "slo_state": last.get("slo_state", ""),
    }


def bench_profiling(pairs: int = 5) -> dict:
    """Capacity-observatory instrumentation cost + matrix correctness
    (ISSUE 10), same harness as ``bench_telemetry``.

    Interleaved (telemetry, telemetry+profiling) scheduler-burst pairs at
    the full telemetry job count — both halves carry the exporter/
    aggregator, so the ratio isolates the PROFILER itself (per-job
    CapacityProfiler observation + histogram exemplar capture + the
    capacity block riding each beacon) from the already-gated export cost.
    Reports the MEDIAN overhead pct (ceiling-gated ≤5% in bench_floor.json)
    and ``capacity_matrix_ok``: the instrumented run's aggregator must
    reproduce the bench op as a fresh non-zero throughput-matrix row.
    """
    import statistics

    n = TELEMETRY_JOBS
    asyncio.run(bench_scheduler(telemetry=True, n_jobs=n, profiling=True))  # warmup
    overheads = []
    last = {}
    for _ in range(pairs):
        base = asyncio.run(bench_scheduler(telemetry=True, n_jobs=n))
        instr = asyncio.run(
            bench_scheduler(telemetry=True, n_jobs=n, profiling=True))
        last = instr
        if base["jobs_per_sec"]:
            overheads.append(
                100.0 * (1.0 - instr["jobs_per_sec"] / base["jobs_per_sec"]))
    return {
        "profiling_overhead_pct": round(
            statistics.median(overheads), 1) if overheads else 100.0,
        "profiling_overhead_runs": [round(o, 1) for o in overheads],
        "capacity_matrix_ok": last.get("capacity_matrix_ok", 0.0),
        "capacity_ops": last.get("capacity_ops", 0),
    }


def bench_replication_overhead(pairs: int = 5) -> dict:
    """Async-replication cost on the statebus schedule loop (ISSUE 8).

    Runs ``pairs`` interleaved (plain, replicated) pipelined runs at the
    FULL statebus job count — short smoke-sized runs put startup noise in
    the same decade as the effect — and reports the MEDIAN same-run
    overhead ratio, so one scheduler hiccup on a shared 1-2 core CI runner
    can't fake (or mask) a regression.  The replica is a real subprocess
    tailing the primary's committed-record stream with async acks.
    """
    import statistics

    overheads, plain_rates, repl_rates, lag_end = [], [], [], 0
    for _ in range(pairs):
        plain = asyncio.run(bench_statebus(True, STATEBUS_JOBS))
        repl = asyncio.run(bench_statebus(True, STATEBUS_JOBS, replicated=True))
        plain_rates.append(plain["jobs_per_sec"])
        repl_rates.append(repl["jobs_per_sec"])
        lag_end = max(lag_end, repl.get("repl_lag_ops_end", -1))
        if plain["jobs_per_sec"]:
            overheads.append(
                100.0 * (1.0 - repl["jobs_per_sec"] / plain["jobs_per_sec"]))
    return {
        "statebus_replicated_jobs_per_sec": round(
            statistics.median(repl_rates), 1) if repl_rates else 0.0,
        "statebus_replication_overhead_pct": round(
            statistics.median(overheads), 1) if overheads else 100.0,
        "statebus_replication_overhead_runs": [round(o, 1) for o in overheads],
        "statebus_replication_lag_ops_end": lag_end,
    }


# ---------------------------------------------------------------------------
# sharded mode (ISSUE 5): S scheduler-shard PROCESSES over P statebus
# partition PROCESSES — the real multi-process control plane, keyspace-
# partitioned end to end (gateway-role submit stamps sys.job.submit.<p>,
# each shard owns its jobs' full lifecycle, workers echo the partition on
# results).  Child modes: `--statebus-child <port>` / `--shard-child i n urls`.
# ---------------------------------------------------------------------------


def _free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


async def _wait_for_stop() -> None:
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()


def _statebus_child(port: int, replica_of: str = "") -> None:
    """One statebus partition server process (optionally a replica tailing
    ``replica_of`` — the --replicated bench topology)."""
    async def run() -> None:
        from cordum_tpu.infra.statebus import StateBusServer

        srv = StateBusServer(port=port, replica_of=replica_of,
                             auto_promote=False)
        await srv.start()
        await _wait_for_stop()
        await srv.stop()

    asyncio.run(run())


def _shard_child(index: int, count: int, urls: str) -> None:
    """One scheduler shard process: engine shard `index` of `count` over the
    partitioned statebus; reports completion counts through the KV so the
    parent can observe end-to-end progress without sharing a process."""
    async def run() -> None:
        from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
        from cordum_tpu.controlplane.scheduler.engine import Engine
        from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
        from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
        from cordum_tpu.infra.config import parse_pool_config
        from cordum_tpu.infra.jobstore import JobStore
        from cordum_tpu.infra.registry import WorkerRegistry
        from cordum_tpu.infra.statebus import connect_partitioned
        from cordum_tpu.protocol.types import Heartbeat

        kv, bus, grp = await connect_partitioned(urls)
        kernel = SafetyKernel(
            policy_doc={"tenants": {"default": {"allow_topics": ["job.*", "job.>"]}}}
        )
        reg = WorkerRegistry()
        pc = parse_pool_config(
            {"topics": {"job.bench": "bench"}, "pools": {"bench": {"requires": []}}}
        )
        eng = Engine(
            bus=bus, job_store=JobStore(kv), safety=SafetyClient(kernel.check),
            strategy=LeastLoadedStrategy(reg, pc), registry=reg,
            instance_id=f"bench-shard-{index}", shard_index=index, shard_count=count,
        )
        # seed the local load view so the first dispatch cannot race the
        # parent's first heartbeat (heartbeats keep refreshing it after)
        reg.update(Heartbeat(worker_id="bench-w", pool="bench", max_parallel_jobs=1 << 30))
        await eng.start()
        await kv.set(f"bench:shard_ready:{index}", b"1")

        done = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, done.set)

        async def report() -> None:
            while not done.is_set():
                n = int(eng.metrics.jobs_completed.value(status="SUCCEEDED"))
                await kv.set(f"bench:done:{index}", str(n).encode())
                await asyncio.sleep(0.1)

        rep = asyncio.ensure_future(report())
        await done.wait()
        rep.cancel()
        try:  # best-effort final flush — the servers may already be gone
            n = int(eng.metrics.jobs_completed.value(status="SUCCEEDED"))
            await asyncio.wait_for(kv.set(f"bench:done:{index}", str(n).encode()), 2.0)
        except (ConnectionError, OSError, asyncio.TimeoutError):
            pass  # parent already read the periodic reports; flush is advisory
        await eng.stop()
        await grp.close()

    asyncio.run(run())


async def bench_sharded(shards: int, partitions: int, n_jobs: int) -> dict:
    """Keyspace-sharded schedule loop: `shards` engine processes ×
    `partitions` statebus server processes, submits stamped to
    ``sys.job.submit.<p>``, one worker role in the parent."""
    from cordum_tpu.infra.statebus import connect_partitioned
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import (
        BusPacket, Heartbeat, JobRequest, JobResult, LABEL_PARTITION,
    )

    me = os.path.abspath(__file__)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    ports = _free_ports(partitions)
    urls = ",".join(f"statebus://127.0.0.1:{p}" for p in ports)
    procs = [
        subprocess.Popen([sys.executable, me, "--statebus-child", str(p)],
                         env=env, cwd=os.path.dirname(me))
        for p in ports
    ]
    kv = bus = grp = None
    hb_task = None
    shard_procs: list[subprocess.Popen] = []
    try:
        deadline = time.monotonic() + 30
        while True:  # servers up? (connect_partitioned dials every endpoint)
            try:
                kv, bus, grp = await connect_partitioned(urls)
                break
            except (OSError, ConnectionError):
                if time.monotonic() > deadline:
                    raise
                await asyncio.sleep(0.1)
        shard_procs = [
            subprocess.Popen(
                [sys.executable, me, "--shard-child", str(i), str(shards), urls],
                env=env, cwd=os.path.dirname(me))
            for i in range(shards)
        ]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:  # every shard subscribed?
            flags = await asyncio.gather(
                *(kv.get(f"bench:shard_ready:{i}") for i in range(shards))
            )
            if all(flags):
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError("scheduler shards never became ready")

        hb = Heartbeat(worker_id="bench-w", pool="bench", max_parallel_jobs=1 << 30)

        async def heartbeats() -> None:
            while True:
                await bus.publish(subj.HEARTBEAT, BusPacket.wrap(hb, sender_id="bench-w"))
                await asyncio.sleep(1.0)

        hb_task = asyncio.ensure_future(heartbeats())

        submitted: dict[str, float] = {}
        done: dict[str, float] = {}
        all_done = asyncio.Event()

        async def worker_handler(subject, pkt):
            req = pkt.job_request
            # echo the owning shard's partition stamp → result routes
            # straight to sys.job.result.<p>, no forwarding hop
            await bus.publish(
                subj.stamped_result_subject((req.labels or {}).get(LABEL_PARTITION, "")),
                BusPacket.wrap(
                    JobResult(job_id=req.job_id, status="SUCCEEDED", worker_id="bench-w"),
                    sender_id="bench-w",
                ),
            )

        async def result_tap(subject, pkt):
            res = pkt.job_result
            if res and res.job_id in submitted and res.job_id not in done:
                done[res.job_id] = time.perf_counter() - submitted[res.job_id]
                if len(done) >= n_jobs:
                    all_done.set()

        await bus.subscribe(subj.direct_subject("bench-w"), worker_handler, queue="w")
        await bus.subscribe(subj.RESULT, result_tap)
        await bus.subscribe(f"{subj.RESULT}.>", result_tap)

        t0 = time.perf_counter()
        for i in range(n_jobs):
            jid = f"sh-{i}"
            submitted[jid] = time.perf_counter()
            await bus.publish(
                subj.submit_subject_for(jid, shards),
                BusPacket.wrap(
                    JobRequest(job_id=jid, topic="job.bench", tenant_id="default"),
                    sender_id="bench",
                ),
            )
        try:
            await asyncio.wait_for(all_done.wait(), timeout=120)
        except asyncio.TimeoutError:
            pass
        dt = time.perf_counter() - t0

        # the shards' own terminal commits (reported through the KV): proves
        # every shard drove its partition's jobs to a terminal state
        terminal = 0
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            vals = await asyncio.gather(
                *(kv.get(f"bench:done:{i}") for i in range(shards))
            )
            terminal = sum(int(v or b"0") for v in vals)
            if terminal >= n_jobs:
                break
            await asyncio.sleep(0.1)
        lat = sorted(done.values())
        return {
            "shards": shards,
            "statebus_partitions": partitions,
            "jobs": len(done),
            "jobs_per_sec": len(done) / dt if dt > 0 else 0.0,
            "p50_e2e_ms": (lat[len(lat) // 2] * 1000) if lat else 0.0,
            "terminal_total": terminal,
        }
    finally:
        if hb_task:
            hb_task.cancel()
        if grp is not None:
            await grp.close()  # before SIGTERM: no reconnect-warn churn
        # shards first (their shutdown flushes through the servers), then
        # the statebus partitions
        for batch in (shard_procs, procs):
            for p in batch:
                p.send_signal(signal.SIGTERM)
            for p in batch:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


def bench_profile() -> dict:
    """Per-layer timing breakdown (``--profile``; also emitted by --smoke):
    microbenchmarks of the four layers the 1×1 hot path decomposes into —
    routing, codec, selection, commit — so a future throughput regression
    is attributable to a layer straight from the JSON artifact (ISSUE 6).
    All numbers are microseconds per operation."""
    import random

    from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.codec import pack_record, unpack_record
    from cordum_tpu.infra.jobstore import JobStore, MetaSnapshot
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.infra.statebus import PartitionedKV
    from cordum_tpu.protocol.partition import partition_of
    from cordum_tpu.protocol.types import (
        BusPacket, Heartbeat, JobRequest, JobState,
    )

    def us_per(fn, n: int) -> float:
        fn()  # warm
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n * 1e6

    out: dict = {}

    # routing: keyspace hash + the 1×1 identity collapse
    out["routing_partition_of_us"] = round(
        us_per(lambda: partition_of("job-abcdef-123456", 8), 20000), 3)
    out["routing_unsharded_collapsed"] = PartitionedKV([MemoryKV()]).__class__ is MemoryKV

    # codec: envelope encode/decode, lazy payload, cached re-encode, records
    req = JobRequest(job_id="prof-1", topic="job.bench", tenant_id="default",
                     labels={"k": "v"}, env={"A": "B"})
    out["codec_encode_us"] = round(
        us_per(lambda: BusPacket.wrap(req, sender_id="prof").to_wire(), 5000), 3)
    wire = BusPacket.wrap(req, sender_id="prof").to_wire()
    out["codec_decode_envelope_us"] = round(
        us_per(lambda: BusPacket.from_wire(wire), 5000), 3)
    out["codec_decode_payload_us"] = round(
        us_per(lambda: BusPacket.from_wire(wire).job_request, 5000), 3)
    out["codec_reencode_cached_us"] = round(
        us_per(lambda: BusPacket.from_wire(wire).to_wire(), 5000), 3)
    rec = {"ts_us": 1, "state": JobState.RUNNING.value,
           "prev": JobState.DISPATCHED.value, "event": "running"}
    packed = pack_record(rec)
    out["codec_record_pack_us"] = round(us_per(lambda: pack_record(rec), 20000), 3)
    out["codec_record_unpack_us"] = round(
        us_per(lambda: unpack_record(packed), 20000), 3)

    # selection: the strategy pick (native scan when available)
    rng = random.Random(9)
    reg = WorkerRegistry()
    for i in range(100):
        reg.update(Heartbeat(worker_id=f"w{i:03d}", pool="bench",
                             active_jobs=rng.randint(0, 4), max_parallel_jobs=16))
    pc = parse_pool_config(
        {"topics": {"job.bench": "bench"}, "pools": {"bench": {"requires": []}}})
    strat = LeastLoadedStrategy(reg, pc)
    sreq = JobRequest(job_id="prof", topic="job.bench")
    out["selection_pick_us"] = round(us_per(lambda: strat.pick_subject(sreq), 10000), 3)

    # commit: a grouped pipelined transition chain on MemoryKV
    kv = MemoryKV()
    js = JobStore(kv)
    ops, _, _ = js.build_chain_ops(
        "prof-job", MetaSnapshot(), [(JobState.PENDING, {"topic": "job.bench"}, "submit")]
    )

    async def commit_loop(n: int) -> float:
        await kv.pipe_execute({}, ops)  # warm
        t0 = time.perf_counter()
        for _ in range(n):
            await kv.pipe_execute({}, ops)
        return (time.perf_counter() - t0) / n * 1e6

    out["commit_pipe_us"] = round(asyncio.run(commit_loop(5000)), 3)
    return out


def bench_selection() -> dict:
    """Worker-selection throughput at 1000 workers (reference analogue:
    18,234 selections/s, BENCHMARKS.md:131)."""
    import random

    from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.protocol.types import Heartbeat, JobRequest

    rng = random.Random(9)
    reg = WorkerRegistry()
    for i in range(1000):
        reg.update(Heartbeat(
            worker_id=f"w{i:05d}", pool="tpu", capabilities=["tpu"],
            chip_count=rng.choice([1, 4, 8]), active_jobs=rng.randint(0, 12),
            max_parallel_jobs=16, cpu_load=rng.uniform(0, 100),
            tpu_duty_cycle=rng.uniform(0, 100),
        ))
    pc = parse_pool_config({"topics": {"job.tpu.work": "tpu"}, "pools": {"tpu": {"requires": ["tpu"]}}})
    strat = LeastLoadedStrategy(reg, pc)
    req = JobRequest(job_id="j", topic="job.tpu.work")
    strat.pick_subject(req)
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        strat.pick_subject(req)
    dt = time.perf_counter() - t0
    return {"selections_per_sec": n / dt, "native": strat._packed is not None}


# ---------------------------------------------------------------------------
# Gang scheduling bench (ISSUE 15, docs/GANG.md) — run via
# `python bench.py --gang-child [smoke]` in a subprocess that forces an
# 8-device CPU host platform BEFORE jax initializes (the MULTICHIP mesh).
# The child drives an in-process fleet through the REAL
# submit → reserve → rendezvous → step → result pipeline:
#   * a burst of barrier-only gangs measures the control-plane gang rate
#     (gang_jobs_per_sec);
#   * the three MULTICHIP dryrun flows (dense dp×tp×sp, moe dp×tp×ep,
#     MPMD pipeline dp×pp with one stage per worker) run as scheduled
#     gang jobs (gang_flows_ok + per-flow losses);
#   * gang spans (reserve/rendezvous/step/release) must land in the trace
#     stream (gang_spans_ok) and cordum_gang_* metrics in the fleet
#     exposition (gang_metrics_ok);
#   * gang_partial_reservations re-reads the ledger invariant counter
#     (ceiling 0 in bench_floor.json).
# ---------------------------------------------------------------------------


def _gang_child(smoke: bool) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import faulthandler

    faulthandler.dump_traceback_later(max(60.0, JAX_TIMEOUT_S), exit=True)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from cordum_tpu.parallel.mesh import configure_compile_cache

    configure_compile_cache()
    print(json.dumps(asyncio.run(_bench_gang(smoke))))


async def _bench_gang(smoke: bool) -> dict:
    from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
    from cordum_tpu.controlplane.scheduler.engine import Engine
    from cordum_tpu.controlplane.scheduler.gang import GangScheduler
    from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
    from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.jobstore import JobStore
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.memstore import MemoryStore
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.obs import FleetAggregator, TelemetryExporter
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import (
        BusPacket, JobRequest, LABEL_GANG_WORKERS,
    )
    from cordum_tpu.worker.gang import GangRunner
    from cordum_tpu.worker.runtime import Worker
    from cordum_tpu.worker.training import TrainRunner

    kv = MemoryKV()
    bus = LoopbackBus()
    js = JobStore(kv)
    kernel = SafetyKernel(policy_doc={
        "tenants": {"default": {"allow_topics": ["job.*", "job.>"]}}})
    reg = WorkerRegistry()
    pc = parse_pool_config({"topics": {"job.gang": "gangpool"},
                            "pools": {"gangpool": {}}})
    eng = Engine(bus=bus, job_store=js, safety=SafetyClient(kernel.check),
                 strategy=LeastLoadedStrategy(reg, pc), registry=reg)
    gangs = GangScheduler(eng, pc, rendezvous_timeout_s=10.0,
                          watch_interval_s=0.05)
    await eng.start()
    await gangs.start()
    spans: list = []

    async def collect_span(subject, pkt):
        spans.append(pkt.payload)

    await bus.subscribe(subj.TRACE_SPAN, collect_span)
    agg = FleetAggregator(bus, metrics=Metrics(), fine_step_s=0.5)
    await agg.start()
    exporter = TelemetryExporter(
        "scheduler", bus, eng.metrics, instance_id="gang-sched",
        interval_s=0.5,
        health_fn=lambda: {"role": "scheduler", "gangs": gangs.doc(),
                           "gang_queue_depth": len(gangs._fifo)},
    )
    store = MemoryStore(kv)
    workers = []
    for i in range(4):
        w = Worker(bus=bus, store=store, worker_id=f"gw{i}", pool="gangpool",
                   heartbeat_interval_s=0.5)
        w.attach_gang(GangRunner(
            w, trainer=TrainRunner(), rendezvous_timeout_s=10.0,
            peer_timeout_s=60.0, beacon_interval_s=0.05,
        ), metrics=eng.metrics)
        await w.start()
        workers.append(w)
    await asyncio.sleep(0.1)

    out: dict = {}

    async def submit(job_id: str, payload: dict, n_workers: int) -> None:
        ptr = await store.put_context(job_id, payload)
        await bus.publish(subj.SUBMIT, BusPacket.wrap(
            JobRequest(job_id=job_id, topic="job.gang", tenant_id="default",
                       context_ptr=ptr,
                       labels={LABEL_GANG_WORKERS: str(n_workers)}),
            sender_id="bench"))

    async def wait_done(job_ids, timeout_s: float) -> dict:
        deadline = time.monotonic() + timeout_s
        states = {}
        while time.monotonic() < deadline:
            states = {j: await js.get_state(j) for j in job_ids}
            if all(s in ("SUCCEEDED", "FAILED", "DENIED", "CANCELLED")
                   for s in states.values()):
                break
            await asyncio.sleep(0.05)
        return states

    try:
        # -- 1. control-plane gang rate: barrier-only gangs of 2 over 4
        # workers (two gangs run concurrently; the rest queue FIFO)
        n_echo = 8 if smoke else 20
        t0 = time.perf_counter()
        for i in range(n_echo):
            await submit(f"ge-{i}", {"op": "gang_echo"}, 2)
        states = await wait_done([f"ge-{i}" for i in range(n_echo)], 120.0)
        elapsed = time.perf_counter() - t0
        ok = sum(1 for s in states.values() if s == "SUCCEEDED")
        out["gang_echo_gangs"] = ok
        out["gang_jobs_per_sec"] = round(ok / elapsed, 2) if elapsed else 0.0
        if ok < n_echo:
            out["gang_error"] = f"echo gangs: {states}"

        # -- 2. the three MULTICHIP dryrun flows as scheduled gang jobs
        flows = {
            "dense": {"op": "train", "model": "llama-tiny", "steps": 1,
                      "batch": 4, "seq": 16, "mesh": {"tp": 2, "sp": 2},
                      "gang": {"workers": 2}},
            "moe": {"op": "train", "model": "moe", "steps": 1,
                    "batch": 4, "seq": 16, "mesh": {"tp": 2, "ep": 2},
                    "gang": {"workers": 2}},
            "pipeline": {"op": "train", "model": "pipeline", "steps": 1,
                         "batch": 4, "seq": 16, "microbatches": 2,
                         "mesh": {"dp": -1, "pp": 2},
                         "gang": {"workers": 2}},
        }
        flows_ok = 1.0
        for name, payload in flows.items():
            await submit(f"gf-{name}", payload, 2)
            states = await wait_done([f"gf-{name}"], 600.0)
            if states.get(f"gf-{name}") != "SUCCEEDED":
                flows_ok = 0.0
                out["gang_error"] = (
                    out.get("gang_error", "")
                    + f" flow {name}: {states.get(f'gf-{name}')}"
                ).strip()
                continue
            res = await store.get_result(f"gf-{name}")
            loss = res.get("loss")
            out[f"gang_{name}_loss"] = loss
            out[f"gang_{name}_mode"] = res.get("mode")
            if loss is None or not math.isfinite(float(loss)):
                flows_ok = 0.0
                out["gang_error"] = (
                    out.get("gang_error", "") + f" flow {name}: loss={loss}"
                ).strip()
        out["gang_flows_ok"] = flows_ok

        # -- 3. gang spans in the trace stream (the waterfall's source)
        for _ in range(20):
            await bus.drain()
            await asyncio.sleep(0.01)
        names = {sp.name for sp in spans}
        want = {"gang-reserve", "gang-dispatch", "gang-rendezvous",
                "gang-step", "gang-release"}
        out["gang_spans_ok"] = 1.0 if want <= names else 0.0
        if want - names:
            out["gang_error"] = (
                out.get("gang_error", "")
                + f" missing spans: {sorted(want - names)}"
            ).strip()

        # -- 4. cordum_gang_* metrics in the fleet exposition
        await exporter.publish_once()
        await bus.drain()
        text = agg.render()
        out["gang_metrics_ok"] = 1.0 if (
            "cordum_gang_admissions_total" in text
            and "cordum_gang_rendezvous_seconds" in text
        ) else 0.0
        gdoc = agg.gangs_doc()
        out["gang_table_rows"] = len(gdoc.get("gangs") or [])

        # -- 5. the all-or-nothing invariant counter (ceiling 0)
        gangs.ledger.verify()
        out["gang_partial_reservations"] = (
            eng.metrics.gang_partial_reservations.total())
        out.setdefault("gang_error", "")
    finally:
        await exporter.stop()
        await agg.stop()
        await gangs.stop()
        await eng.stop()
        for w in workers:
            await w.stop()
        await bus.close()
    return out


_GANG_KEYS = (
    "gang_jobs_per_sec", "gang_echo_gangs", "gang_flows_ok",
    "gang_dense_loss", "gang_dense_mode", "gang_moe_loss", "gang_moe_mode",
    "gang_pipeline_loss", "gang_pipeline_mode", "gang_spans_ok",
    "gang_metrics_ok", "gang_table_rows", "gang_partial_reservations",
    "gang_error",
)


def bench_gang(*, smoke: bool = False) -> dict:
    """Run the gang bench in a child process (it must force the 8-device
    CPU host platform before jax initializes; the parent may already hold
    an initialized single-device backend)."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--gang-child"]
            + (["smoke"] if smoke else []),
            capture_output=True, text=True, timeout=max(600.0, JAX_TIMEOUT_S),
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        line = (proc.stdout.strip().splitlines() or [""])[-1]
        child = json.loads(line) if line.startswith("{") else {}
        if not child:
            tail = (proc.stderr or proc.stdout or "")[-600:]
            return {"gang_error": f"gang child rc={proc.returncode}: {tail}",
                    "gang_jobs_per_sec": 0.0, "gang_flows_ok": 0.0,
                    "gang_partial_reservations": 0.0}
        return {k: child[k] for k in _GANG_KEYS if k in child}
    except subprocess.TimeoutExpired:
        return {"gang_error": "gang child timed out",
                "gang_jobs_per_sec": 0.0, "gang_flows_ok": 0.0,
                "gang_partial_reservations": 0.0}
    except Exception as ex:  # noqa: BLE001 - bench must report, not crash
        return {"gang_error": f"{type(ex).__name__}: {ex}"[:300],
                "gang_jobs_per_sec": 0.0, "gang_flows_ok": 0.0,
                "gang_partial_reservations": 0.0}


# ---------------------------------------------------------------------------
# sharded serving gangs (bench.py --tp): the SAME session set served by a
# TP=2 in-process serving gang (docs/SERVING.md §Sharded serving) vs a
# single-rank worker, same model, same process tree.  The contract metrics
# are exact: tp_token_identity (TP is a placement change, not a math
# change — the gang's streams must equal the single-rank fp32 run token
# for token), tp_compile_per_rank (every rank compiles exactly ONE ragged
# program), and tp_speedup as the same-run wall ratio.  On a 1-2 core CI
# host both gang ranks time-share the only core, so the observed ratio
# sits near 0.5 — the bench_floor.json floor is a COLLAPSE guard (a gang
# that serializes rank steps or recompiles per rank lands far below it);
# the real >=1.5x bar needs one chip per rank (see ROADMAP).
# ---------------------------------------------------------------------------

_TP_KEYS = (
    "tp_tokens_per_sec", "tp_single_tokens_per_sec", "tp_speedup",
    "tp_token_identity", "tp_compile_per_rank", "tp_single_compile_count",
    "tp_ranks", "tp_sessions", "tp_new_tokens", "tp_error",
)


def _tp_child(smoke: bool) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import faulthandler

    faulthandler.dump_traceback_later(max(60.0, JAX_TIMEOUT_S), exit=True)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from cordum_tpu.parallel.mesh import configure_compile_cache

    configure_compile_cache()
    print(json.dumps(asyncio.run(_bench_tp(smoke))))


async def _bench_tp(smoke: bool) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend
    from cordum_tpu.serving.engine import GenRequest, ServingEngine
    from cordum_tpu.serving.shard import ServingGangGroup

    async def run_blocking(fn, *args):
        return await asyncio.get_running_loop().run_in_executor(None, fn, *args)

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    sessions = 4 if smoke else 8
    n_new = 8 if smoke else 24
    prompts = [
        [(7 * i + 3 * j + 1) % cfg.vocab_size for j in range(9 + i % 4)]
        for i in range(sessions)
    ]

    async def serve(backend) -> tuple[list[list[int]], float]:
        # prefix cache off: the oracle run must prefill every prompt in
        # full, same as the gang's replayed entry stream
        eng = ServingEngine(backend, run_blocking=run_blocking,
                            max_new_tokens_cap=max(64, n_new),
                            prefix_cache=False)
        t0 = time.perf_counter()
        outs = await asyncio.gather(*[
            eng.submit(GenRequest(prompt=p, max_new_tokens=n_new,
                                  stream=False), job_id=f"tp-{i}")
            for i, p in enumerate(prompts)
        ])
        dt = time.perf_counter() - t0
        await eng.stop()
        return [o["tokens"] for o in outs], (sessions * n_new) / max(dt, 1e-9)

    single = LlamaServingBackend(cfg, num_pages=96, page_size=8,
                                 params_provider=lambda: params)
    gang = ServingGangGroup(cfg, tp=2, num_pages=96, page_size=8,
                            params_provider=lambda: params)
    toks_single, rate_single = await serve(single)
    toks_gang, rate_gang = await serve(gang)
    return {
        "tp_ranks": 2,
        "tp_sessions": sessions,
        "tp_new_tokens": sessions * n_new,
        "tp_tokens_per_sec": round(rate_gang, 1),
        "tp_single_tokens_per_sec": round(rate_single, 1),
        "tp_speedup": round(rate_gang / rate_single, 3) if rate_single else 0.0,
        "tp_token_identity": 1 if toks_gang == toks_single else 0,
        "tp_compile_per_rank": max(gang.compiled_per_rank()),
        "tp_single_compile_count": single.compiled_programs(),
        "tp_error": "",
    }


def bench_tp(*, smoke: bool = False) -> dict:
    """Run the TP serving bench in a child process (it must force the
    8-device CPU host platform before jax initializes; the parent may
    already hold an initialized single-device backend)."""
    fail = {"tp_tokens_per_sec": 0.0, "tp_speedup": 0.0,
            "tp_token_identity": 0.0, "tp_compile_per_rank": 99.0}
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--tp-child"]
            + (["smoke"] if smoke else []),
            capture_output=True, text=True, timeout=max(600.0, JAX_TIMEOUT_S),
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        line = (proc.stdout.strip().splitlines() or [""])[-1]
        child = json.loads(line) if line.startswith("{") else {}
        if not child:
            tail = (proc.stderr or proc.stdout or "")[-600:]
            return {**fail, "tp_error": f"tp child rc={proc.returncode}: {tail}"}
        return {k: child[k] for k in _TP_KEYS if k in child}
    except subprocess.TimeoutExpired:
        return {**fail, "tp_error": "tp child timed out"}
    except Exception as ex:  # noqa: BLE001 - bench must report, not crash
        return {**fail, "tp_error": f"{type(ex).__name__}: {ex}"[:300]}


# ---------------------------------------------------------------------------
# TPU compute benches — run via `python bench.py --jax-child [tpu|cpu]` in a
# subprocess so a hung or crashed PJRT client can't hang the control-plane
# benches. The child prints ONE json line.  `tpu` needs a chip and exits
# non-zero with a one-line reason without one; `cpu` is the explicit child
# `--smoke` asks for and names its device.
# ---------------------------------------------------------------------------


def _jax_child(device: str) -> None:
    import faulthandler

    # watchdog: if the PJRT client wedges, die with a traceback instead of
    # hanging the driver
    faulthandler.dump_traceback_later(max(30.0, JAX_TIMEOUT_S - 30.0), exit=True)
    if device == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    out: dict = {}
    import jax

    if device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if device == "tpu" and dev.platform != "tpu":
        sys.stderr.write(
            f"bench --jax-child tpu: no chip (jax backend is {dev.platform!r})\n")
        sys.exit(2)
    from cordum_tpu.parallel.mesh import configure_compile_cache

    configure_compile_cache()
    out["device"] = dev.device_kind
    # mfu is a device metric: only the tpu child computes it
    peak = _peak_flops(dev.device_kind) if device == "tpu" else 0.0

    # --- embedder (context-engine path; headline embeds/sec) ---
    try:
        from cordum_tpu.models.embedder import Embedder, EmbedderConfig

        if device == "cpu":  # CPU smoke shape (single-core CI boxes)
            cfg = EmbedderConfig(n_layers=2, d_model=128, max_len=64)
            batch, iters = 32, 2
        else:
            cfg = EmbedderConfig()
            batch, iters = 256, 8
        e = Embedder(cfg, seed=0)
        texts = [f"document {i}: control plane scheduling latency report" for i in range(batch)]
        e.embed(texts)  # warm compile
        t0 = time.perf_counter()
        for _ in range(iters):
            e.embed(texts)
        dt = time.perf_counter() - t0
        out["embeds_per_sec"] = iters * batch / dt
    except Exception as ex:  # noqa: BLE001
        out["embed_error"] = f"{type(ex).__name__}: {ex}"[:300]

    # --- llama forward (tokens/s + MFU) ---
    try:
        from cordum_tpu.models import llama

        if device == "cpu":
            cfg = llama.LlamaConfig(vocab_size=2048, d_model=128, n_layers=2,
                                    n_heads=4, n_kv_heads=2, d_ff=384)
            b, s, iters = 2, 128, 2
        else:
            # matmul-dominated shape that fits a single chip's HBM comfortably
            cfg = llama.LlamaConfig(vocab_size=32000, d_model=2048, n_layers=16,
                                    n_heads=16, n_kv_heads=8, d_ff=7168)
            b, s, iters = 8, 1024, 6
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
        fwd = jax.jit(lambda p, t: llama.forward(p, t, cfg))
        jax.block_until_ready(fwd(params, tokens))  # warm compile
        t0 = time.perf_counter()
        for _ in range(iters):
            y = fwd(params, tokens)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        toks = b * s * iters
        # analytic FLOPs: 2 flops/param/token over every dense matmul weight
        # (embed lookup excluded, lm_head included) + attention score/value
        # matmuls 2*2*S*h*hd per token per layer (causal → /2)
        dense_params = sum(
            x.size for x in jax.tree.leaves(params)
            if hasattr(x, "ndim") and x.ndim == 2
        ) - cfg.vocab_size * cfg.d_model  # embed table
        attn = cfg.n_layers * 2 * 2 * s * cfg.n_heads * cfg.head_dim / 2
        flops_per_tok = 2 * dense_params + attn
        out["model_tokens_per_sec"] = toks / dt
        out["model_params_m"] = round(
            sum(x.size for x in jax.tree.leaves(params) if hasattr(x, "size")) / 1e6, 1)
        out["model_achieved_tflops"] = toks * flops_per_tok / dt / 1e12
        if peak:
            out["mfu"] = round(toks * flops_per_tok / dt / peak, 4)
    except Exception as ex:  # noqa: BLE001
        out["model_error"] = f"{type(ex).__name__}: {ex}"[:300]

    # --- micro-batching: the REAL worker path, single-job vs batched ---
    # (ISSUE 3 acceptance: batched_embeds_per_sec >= 3x the single-job path)
    try:
        out.update(asyncio.run(_bench_worker_embeds(device)))
    except Exception as ex:  # noqa: BLE001
        out["batched_error"] = f"{type(ex).__name__}: {ex}"[:300]

    # --- serving: continuous-batching decode vs sequential per-session ---
    # (ISSUE 7 acceptance: decode_tokens_per_sec >= 2x sequential)
    try:
        out.update(asyncio.run(_bench_worker_serving(device)))
    except Exception as ex:  # noqa: BLE001
        out["serving_error"] = f"{type(ex).__name__}: {ex}"[:300]

    # --- disaggregated prefill/decode serving (ISSUE 14): co-located vs
    # post-prefill hand-off over a 2-worker heterogeneous fleet ---
    try:
        out.update(asyncio.run(_bench_disagg(device)))
    except Exception as ex:  # noqa: BLE001
        out["disagg_error"] = f"{type(ex).__name__}: {ex}"[:300]

    # --- multi-turn chat: prefix-cache TTFT + session tiering (ISSUE 18) ---
    try:
        out.update(asyncio.run(_bench_chat(device)))
    except Exception as ex:  # noqa: BLE001
        out["chat_error"] = f"{type(ex).__name__}: {ex}"[:300]

    # --- self-speculative decoding inside the ragged step (ISSUE 19) ---
    try:
        out.update(asyncio.run(_bench_spec(device)))
    except Exception as ex:  # noqa: BLE001
        out["spec_error"] = f"{type(ex).__name__}: {ex}"[:300]

    print(json.dumps(out), flush=True)


async def _bench_worker_embeds(device: str) -> dict:
    """Drive 1-text embed jobs through a real Worker twice — micro-batcher
    off (one XLA dispatch per job) then on (bucketed coalesced calls) — and
    report both rates.  This is the end-to-end worker path: bus delivery,
    context-pointer fetch, batch queueing, executor dispatch, result publish.
    """
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.memstore import MemoryStore
    from cordum_tpu.models.embedder import EmbedderConfig
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, JobRequest
    from cordum_tpu.worker.handlers import (
        TPUCompute, make_micro_batcher, make_tpu_handlers,
    )
    from cordum_tpu.worker.runtime import Worker

    if device == "cpu":
        cfg = EmbedderConfig(n_layers=2, d_model=128, max_len=64)
        n_jobs = 96
    else:
        cfg = EmbedderConfig()
        n_jobs = 512
    text = "control plane scheduling latency report for document"

    async def run_pass(batched: bool) -> dict:
        kv = MemoryKV()
        bus = LoopbackBus()
        ms = MemoryStore(kv)
        worker = Worker(bus=bus, store=ms, worker_id="bench-w",
                        pool="bench", heartbeat_interval_s=999)
        compute = TPUCompute(tp=1, embedder_cfg=cfg)
        worker.register_default(make_tpu_handlers(compute))
        if batched:
            worker.attach_batcher(make_micro_batcher(
                compute, worker, max_batch_rows=32, max_wait_ms=5.0))
        await worker.start()
        # warm the XLA programs both paths will hit so the timed loop
        # measures dispatch, not compilation
        compute.embedder.embed([text])
        compute.embed_batch([text] * 32, seq_len=16)
        compute.embed_batch([text], seq_len=16)

        done = asyncio.Event()
        seen = set()

        async def tap(subject, pkt):
            res = pkt.job_result
            if res is not None and res.status == "SUCCEEDED":
                seen.add(res.job_id)
                if len(seen) >= n_jobs:
                    done.set()

        sub = await bus.subscribe(subj.RESULT, tap)
        prefix = "b" if batched else "s"
        ptrs = []
        for i in range(n_jobs):
            jid = f"{prefix}{i}"
            ptrs.append((jid, await ms.put_context(jid, {"op": "embed", "texts": [text]})))
        t0 = time.perf_counter()
        for jid, ptr in ptrs:
            await bus.publish(
                subj.direct_subject("bench-w"),
                BusPacket.wrap(JobRequest(job_id=jid, topic="job.tpu.embed",
                                          context_ptr=ptr)),
            )
        await asyncio.wait_for(done.wait(), timeout=JAX_TIMEOUT_S / 2)
        dt = time.perf_counter() - t0
        stats = worker.batcher.stats if worker.batcher else None
        sub.unsubscribe()
        await worker.stop()
        await bus.close()
        return {
            "embeds_per_sec": n_jobs / dt if dt > 0 else 0.0,
            "flushes": stats.flushes if stats else 0,
            "max_batch": stats.max_batch_rows_seen if stats else 0,
        }

    single = await run_pass(False)
    batched = await run_pass(True)
    return {
        "single_job_embeds_per_sec": round(single["embeds_per_sec"], 1),
        "batched_embeds_per_sec": round(batched["embeds_per_sec"], 1),
        "batched_speedup": round(
            batched["embeds_per_sec"] / single["embeds_per_sec"], 2
        ) if single["embeds_per_sec"] else 0.0,
        "batch_flushes": batched["flushes"],
        "max_batch_rows": batched["max_batch"],
    }


async def _bench_worker_serving(device: str) -> dict:
    """Multi-session ``llm.generate`` decode through a real Worker twice —
    sequential (one session at a time: the no-continuous-batching baseline)
    then open-loop (every session submitted at once, ragged continuous
    batching) — reporting decode token rates, p50/p99 inter-token latency,
    mean step occupancy, and the TOTAL XLA program count of the run
    (ISSUE 11: the ragged mixed prefill+decode entry point compiles exactly
    once — the bucketed backend paid one program per prompt-length bucket
    plus one per pow2 decode-batch bucket for the same session mix)."""
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.memstore import MemoryStore
    from cordum_tpu.models import llama
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, JobRequest
    from cordum_tpu.worker.handlers import (
        TPUCompute, make_serving_engine, make_tpu_handlers,
    )
    from cordum_tpu.worker.runtime import Worker

    if device == "cpu":
        lcfg = llama.LlamaConfig.tiny()
        n_sessions, max_new = 12, 40
    else:
        lcfg = llama.LlamaConfig(vocab_size=32000, d_model=1024, n_layers=8,
                                 n_heads=8, n_kv_heads=4, d_ff=3584,
                                 max_seq_len=512)
        n_sessions, max_new = 32, 64
    prompt_len, page_size = 8, 16
    pages_per = -(-(prompt_len + max_new) // page_size)
    cache_pages = n_sessions * pages_per + 8  # +null page +slack

    async def run_pass(concurrent: bool) -> dict:
        bus = LoopbackBus()
        ms = MemoryStore(MemoryKV())
        worker = Worker(bus=bus, store=ms, worker_id="bench-s",
                        pool="bench", heartbeat_interval_s=999)
        compute = TPUCompute(tp=1, llama_cfg=lcfg)
        worker.register_default(make_tpu_handlers(compute))
        worker.attach_serving(make_serving_engine(
            compute, worker, cache_pages=cache_pages, page_size=page_size,
            # the baseline pass admits ONE session at a time: the decode
            # loop degenerates to per-session autoregression (what the
            # fleet does without continuous batching)
            max_sessions=n_sessions if concurrent else 1,
            max_new_tokens=max_new,
        ))
        await worker.start()
        be = worker.serving.backend
        # warm the XLA program: ONE call — the single ragged entry point is
        # every program there is (any prefill-chunk/decode mix reuses it),
        # so the timed window measures steady-state steps.  The bucketed
        # backend needed the whole prefill-bucket + pow2-batch ladder here.
        warm = [1, 2, 3]
        be.prefill(list(range(2, prompt_len + 2)), warm)
        waiters = {f"{'c' if concurrent else 'q'}{i}": asyncio.Event()
                   for i in range(n_sessions)}

        async def tap(subject, pkt):
            res = pkt.job_result
            if res is not None and res.job_id in waiters:
                assert res.status == "SUCCEEDED", (res.job_id, res.status, res.error_message)
                waiters[res.job_id].set()

        sub = await bus.subscribe(subj.RESULT, tap)
        reqs = []
        for i, jid in enumerate(waiters):
            ptr = await ms.put_context(jid, {
                "op": "llm.generate",
                "tokens": [(i * 7 + j) % lcfg.vocab_size for j in range(prompt_len)],
                "max_new_tokens": max_new, "session_id": f"conv-{i}",
                "stream": False,
            })
            reqs.append((jid, ptr))
        # both passes are open-loop (all sessions offered upfront); the
        # baseline's max_sessions=1 admission is what serializes it, so the
        # comparison isolates continuous batching itself
        t0 = time.perf_counter()
        for jid, ptr in reqs:
            await bus.publish(
                subj.direct_subject("bench-s"),
                BusPacket.wrap(JobRequest(job_id=jid, topic="job.tpu.generate",
                                          context_ptr=ptr)),
            )
        await asyncio.wait_for(
            asyncio.gather(*(w.wait() for w in waiters.values())),
            timeout=JAX_TIMEOUT_S / 2,
        )
        dt = time.perf_counter() - t0
        st = worker.serving.stats
        steps = sorted(st.step_seconds)
        ttfts = sorted(st.ttft_seconds)
        sub.unsubscribe()
        await worker.stop()
        await bus.close()
        return {
            "tokens_per_sec": st.decoded_tokens / dt if dt > 0 else 0.0,
            # prompt-ingestion rate, reported separately from decode so
            # disaggregation gains are attributable (ISSUE 14)
            "prefill_tokens_per_sec": st.prefill_tokens / dt if dt > 0 else 0.0,
            "p50_step_ms": (steps[len(steps) // 2] * 1000.0) if steps else 0.0,
            "p99_step_ms": (
                steps[min(len(steps) - 1, int(len(steps) * 0.99))] * 1000.0
            ) if steps else 0.0,
            "p50_ttft_ms": (ttfts[len(ttfts) // 2] * 1000.0) if ttfts else 0.0,
            "mean_occupancy": st.mean_occupancy,
            "steps": st.steps,
            # total XLA programs this pass compiled (warmup included): the
            # ragged entry point makes this exactly 1 — the gated number
            # behind the "no bucket-recompile cliff" claim
            "compiles": be.compiled_programs(),
        }

    seq = await run_pass(False)
    cont = await run_pass(True)
    out = {
        "decode_tokens_per_sec": round(cont["tokens_per_sec"], 1),
        "prefill_tokens_per_sec": round(cont["prefill_tokens_per_sec"], 1),
        "serving_ttft_p50_ms": round(cont["p50_ttft_ms"], 2),
        "sequential_decode_tokens_per_sec": round(seq["tokens_per_sec"], 1),
        "serving_speedup": round(
            cont["tokens_per_sec"] / seq["tokens_per_sec"], 2
        ) if seq["tokens_per_sec"] else 0.0,
        "p50_inter_token_ms": round(cont["p50_step_ms"], 2),
        "inter_token_p99_ms": round(cont["p99_step_ms"], 2),
        "serving_mean_occupancy": round(cont["mean_occupancy"], 2),
        "serving_steps": cont["steps"],
        "serving_sessions": n_sessions,
        "serving_compile_count": cont["compiles"],
    }
    out.update(await _bench_session_migration())
    return out


async def _bench_session_migration() -> dict:
    """Live KV-page migration pause (ISSUE 12): ping-pong ONE decoding
    session between two warmed paged backends over the real TCP migration
    listener and report the p50 decode pause (freeze → target commit) —
    the only window where the session's tokens stop.  The bulk page phase
    streams while decode continues, so the pause should stay in the
    single-digit-to-tens-of-ms range on any host; bench_floor.json gates a
    collapse of that property."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend
    from cordum_tpu.serving.engine import (
        GenRequest, ServingEngine, SessionMigrated,
    )
    from cordum_tpu.serving.migration import MigrationServer, migrate_session

    async def run_blocking(fn, *args):
        return await asyncio.get_running_loop().run_in_executor(None, fn, *args)

    metrics = Metrics()
    lcfg = llama.LlamaConfig.tiny()
    engines, servers = [], []
    done = asyncio.Event()
    final: dict = {}
    for _ in range(2):
        be = LlamaServingBackend(lcfg, num_pages=32, page_size=16)
        be.prefill([1, 2, 3, 4], [1])  # warm: the freeze never waits a compile
        eng = ServingEngine(be, run_blocking=run_blocking,
                            max_new_tokens_cap=1024, metrics=metrics)
        engines.append(eng)

        async def install(meta, state, records, eng=eng):
            req = GenRequest(prompt=meta["prompt"],
                             max_new_tokens=meta["max_new_tokens"],
                             stream=False,
                             resume_tokens=meta["resume_tokens"])
            fut = await eng.install_session(
                req, job_id=meta["job_id"], state=state, records=records)

            def _done(f: "asyncio.Future") -> None:
                if f.cancelled() or isinstance(f.exception(), SessionMigrated):
                    return  # bounced onward; the next owner reports
                if f.exception() is None:
                    final["tokens"] = f.result()
                done.set()

            fut.add_done_callback(_done)

        srv = MigrationServer(install)
        await srv.start()
        servers.append(srv)

    jid = "mig-bench"
    waiter = asyncio.ensure_future(engines[0].submit(
        GenRequest(prompt=[5, 9, 2, 7], max_new_tokens=100, stream=False),
        job_id=jid))
    migrations, src = 0, 0
    while migrations < 6 and not done.is_set():
        eng = engines[src]
        for _ in range(200):
            if eng.describe_session(jid) is not None or done.is_set():
                break
            await asyncio.sleep(0.005)
        if done.is_set() or eng.describe_session(jid) is None:
            break
        await asyncio.sleep(0.03)  # let some pages fill between hops
        tgt = 1 - src
        if await migrate_session(eng, jid, servers[tgt].host,
                                 servers[tgt].port, metrics=metrics):
            migrations += 1
            src = tgt
        else:
            break
    try:
        await asyncio.wait_for(waiter, timeout=60)
    except SessionMigrated:
        await asyncio.wait_for(done.wait(), timeout=60)
    for eng in engines:
        await eng.stop()
    for srv in servers:
        await srv.stop()
    if migrations < 2:
        raise RuntimeError(f"only {migrations} migrations completed")
    p50_s = metrics.serving_migration_pause.quantile(0.5) or 0.0
    return {
        "migration_pause_p50_ms": round(p50_s * 1000.0, 2),
        "migrations_done": migrations,
    }


async def _bench_chat(device: str) -> dict:
    """Prefix-cache + session-tiering chat serving (ISSUE 18), three legs
    on the real paged backend:

      * **prefix TTFT**: N chat sessions sharing a 48-token system prompt,
        run cold (``prefix_cache=False``) then against a primed cache in a
        fresh engine — the hit pass prefills only the post-divergence
        tokens, so its TTFT p50 must beat the cold pass (the
        ``chat_prefix_ttft_speedup`` floor) while staying token-identical
        (sharing is a placement change, not a math change).
      * **residency**: M conversations with page-sized unique histories on
        a small device arena, hibernated to the host-RAM cold arena by the
        idle sweep between waves — the resident-conversation count must
        exceed what the device arena could hold warm
        (``chat_resident_over_capacity`` floor).
      * **restore**: second turns for a sample of hibernated conversations
        re-warm their cold pages; ``chat_restore_pause_p50_ms`` is the p50
        alloc+scatter pause (ceiling in bench_floor.json)."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend
    from cordum_tpu.serving.engine import GenRequest, ServingEngine

    async def run_blocking(fn, *args):
        return await asyncio.get_running_loop().run_in_executor(None, fn, *args)

    if device == "cpu":
        lcfg = llama.LlamaConfig.tiny()
        n_chat, n_resident, n_restore = 6, 24, 4
    else:
        lcfg = llama.LlamaConfig(vocab_size=32000, d_model=1024, n_layers=8,
                                 n_heads=8, n_kv_heads=4, d_ff=3584,
                                 max_seq_len=512)
        n_chat, n_resident, n_restore = 16, 48, 8
    page_size, max_new = 16, 8
    vocab = lcfg.vocab_size
    metrics = Metrics()

    def make_engine(num_pages: int, prefix: bool,
                    hibernate: float = 0.0) -> ServingEngine:
        be = LlamaServingBackend(lcfg, num_pages=num_pages,
                                 page_size=page_size)
        be.prefill([1, 2, 3], [1])  # warm: TTFT never includes the compile
        return ServingEngine(be, run_blocking=run_blocking,
                             max_new_tokens_cap=max_new, prefix_cache=prefix,
                             hibernate_after_s=hibernate, metrics=metrics)

    # --- leg 1: prefix-hit TTFT vs cold, token-identical ---
    system = [((i * 31) % (vocab - 2)) + 1 for i in range(48)]  # 3 full pages
    prompts = [system + [((i * 7 + j) % 97) + 5 for j in range(4)]
               for i in range(n_chat)]
    arena = 8 + n_chat * (-(-(len(prompts[0]) + max_new) // page_size))

    async def run_turns(eng, tag, plist, keyed=True):
        outs = []
        for i, p in enumerate(plist):
            r = await asyncio.wait_for(eng.submit(
                GenRequest(prompt=p, max_new_tokens=max_new, stream=False,
                           session_key=f"{tag}-{i}" if keyed else ""),
                job_id=f"{tag}{i}"), timeout=JAX_TIMEOUT_S / 4)
            outs.append(r["tokens"])
        return outs

    cold_eng = make_engine(arena, prefix=False)
    cold_outs = await run_turns(cold_eng, "cold", prompts)
    cold_ttfts = sorted(cold_eng.stats.ttft_seconds)
    await cold_eng.stop()

    hit_eng = make_engine(arena, prefix=True)
    await run_turns(hit_eng, "prime", [system])  # populate the radix cache
    hit_outs = await run_turns(hit_eng, "hit", prompts)
    hit_ttfts = sorted(list(hit_eng.stats.ttft_seconds)[1:])  # drop the prime
    st = hit_eng.stats
    looked = st.prefix_hits + st.prefix_misses
    hit_rate = st.prefix_hits / looked if looked else 0.0
    identical = int(hit_outs == cold_outs)
    await hit_eng.stop()

    # --- legs 2+3: residency above the device arena + restore pause ---
    # per-conversation history = 2 unique full pages; a 32-page arena holds
    # at most capacity//2 conversations warm, so residency beyond that is
    # hibernation working, not slack
    eng = make_engine(32, prefix=True, hibernate=3600.0)
    capacity_sessions = (32 - 1) // 2
    convo: dict[int, list[int]] = {}
    for i in range(n_resident):
        p = [((i * 131 + j * 17) % (vocab - 2)) + 1 for j in range(36)]
        r = await asyncio.wait_for(eng.submit(
            GenRequest(prompt=p, max_new_tokens=max_new, stream=False,
                       session_key=f"conv-{i}"),
            job_id=f"res{i}"), timeout=JAX_TIMEOUT_S / 4)
        convo[i] = p + r["tokens"]
        if (i + 1) % 6 == 0:  # idle sweep: demote everything to cold
            await eng.tiering.sweep(now=time.monotonic() + 7200.0)
    await eng.tiering.sweep(now=time.monotonic() + 7200.0)
    warm, cold = eng.tiering.tier_counts()
    resident = warm + cold
    for i in range(n_restore):  # turn 2: cold pages re-warm on admission
        p2 = convo[i] + [7]
        await asyncio.wait_for(eng.submit(
            GenRequest(prompt=p2, max_new_tokens=4, stream=False,
                       session_key=f"conv-{i}"),
            job_id=f"res2-{i}"), timeout=JAX_TIMEOUT_S / 4)
    pf = eng.prefix.stats
    restore_p50_s = metrics.serving_hibernate_pause.quantile(0.5) or 0.0
    await eng.stop()

    def p50_ms(vals) -> float:
        return vals[len(vals) // 2] * 1000.0 if vals else 0.0

    cold_p50, hit_p50 = p50_ms(cold_ttfts), p50_ms(hit_ttfts)
    return {
        "chat_ttft_cold_p50_ms": round(cold_p50, 2),
        "chat_ttft_hit_p50_ms": round(hit_p50, 2),
        "chat_prefix_ttft_speedup": round(cold_p50 / hit_p50, 2) if hit_p50 else 0.0,
        "chat_prefix_hit_rate": round(hit_rate, 3),
        "chat_token_identical": identical,
        "chat_sessions": n_chat,
        "chat_resident_sessions": resident,
        "chat_device_session_capacity": capacity_sessions,
        "chat_resident_over_capacity": round(resident / capacity_sessions, 2),
        "chat_hibernated_pages": pf.hibernated_pages,
        "chat_restored_pages": pf.restored_pages,
        "chat_restore_pause_p50_ms": round(restore_p50_s * 1000.0, 2),
    }


async def _bench_spec(device: str) -> dict:
    """Self-speculative decoding inside the ragged step (ISSUE 19): the
    zero-extra-weights n-gram drafter on a templated agent-style workload —
    repeated instruction motifs, the pattern tool-call loops and
    form-filling chains produce — run twice on the real paged backend:
    once speculation-off (the sequential one-token-per-step baseline), once
    speculation-on (draft rows verified as k+1-token prefill-shaped rows).

      * ``spec_decode_speedup``: baseline wall / speculative wall for the
        identical prompt set (floor in bench_floor.json) — static shapes
        make a k+1-token row cost roughly one step, so the speedup tracks
        the mean accepted burst length.
      * ``spec_token_identity``: greedy accept-longest-prefix is a
        schedule change, not a math change — outputs must match the
        baseline token-for-token (floor 1.0, i.e. always).
      * ``spec_compile_count``: draft rows reuse the ONE ragged program
        (prefill-shaped rows already exist); any second program is a
        recompile-cliff regression."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend
    from cordum_tpu.serving.engine import GenRequest, ServingEngine

    async def run_blocking(fn, *args):
        return await asyncio.get_running_loop().run_in_executor(None, fn, *args)

    if device == "cpu":
        lcfg = llama.LlamaConfig.tiny()
        n_sessions = 4
    else:
        lcfg = llama.LlamaConfig(vocab_size=32000, d_model=1024, n_layers=8,
                                 n_heads=8, n_kv_heads=4, d_ff=3584,
                                 max_seq_len=512)
        n_sessions = 8
    page_size, max_new, draft_k = 8, 80, 4
    # templated prompts: an 8-token instruction motif repeated 4× plus a
    # per-session suffix — greedy continuations of this seed settle into
    # cycles the n-gram drafter predicts near-perfectly, the same shape as
    # templated agent loops (PAPER.md §workloads)
    motif = [5, 9, 14, 23, 7, 11, 3, 19]
    prompts = [motif * 4 + [i + 1] for i in range(n_sessions)]

    async def run_pass(speculative: bool) -> dict:
        metrics = Metrics()
        be = LlamaServingBackend(lcfg, num_pages=192, page_size=page_size,
                                 max_batch_tokens=64, seed=2, metrics=metrics)
        eng = ServingEngine(be, run_blocking=run_blocking,
                            max_new_tokens_cap=max_new,
                            speculative=speculative, draft_k=draft_k,
                            metrics=metrics)
        # warm the ragged program so neither pass pays compile in its wall
        await asyncio.wait_for(eng.submit(
            GenRequest(prompt=[1, 2, 3], max_new_tokens=2, stream=False),
            job_id="spec-warm"), timeout=JAX_TIMEOUT_S / 4)
        steps0, decoded0 = eng.stats.steps, eng.stats.decoded_tokens
        t0 = time.perf_counter()
        results = await asyncio.gather(*[
            asyncio.wait_for(eng.submit(
                GenRequest(prompt=p, max_new_tokens=max_new, stream=False),
                job_id=f"spec-{int(speculative)}-{i}"),
                timeout=JAX_TIMEOUT_S / 2)
            for i, p in enumerate(prompts)
        ])
        wall = time.perf_counter() - t0
        st = eng.stats
        out = {
            "outs": [r["tokens"] for r in results],
            "wall": wall,
            "steps": st.steps - steps0,
            "decoded": st.decoded_tokens - decoded0,
            "drafted": st.drafted_tokens,
            "accepted": st.accepted_tokens,
            "rolled_back": st.rolled_back_tokens,
            "compiles": be.compiled_programs(),
        }
        await eng.stop()
        return out

    base = await run_pass(False)
    spec = await run_pass(True)
    speedup = base["wall"] / spec["wall"] if spec["wall"] else 0.0
    accept = (spec["accepted"] / spec["drafted"]) if spec["drafted"] else 0.0
    return {
        "spec_decode_speedup": round(speedup, 2),
        "spec_token_identity": int(spec["outs"] == base["outs"]),
        "spec_accept_rate": round(accept, 3),
        "spec_decode_tokens_per_s": round(spec["decoded"] / spec["wall"], 1)
        if spec["wall"] else 0.0,
        "spec_base_tokens_per_s": round(base["decoded"] / base["wall"], 1)
        if base["wall"] else 0.0,
        "spec_steps": spec["steps"],
        "spec_base_steps": base["steps"],
        "spec_drafted_tokens": spec["drafted"],
        "spec_accepted_tokens": spec["accepted"],
        "spec_rolled_back_tokens": spec["rolled_back"],
        "spec_compile_count": spec["compiles"],
        "spec_sessions": n_sessions,
    }


async def _bench_disagg(device: str) -> dict:
    """Disaggregated prefill/decode serving (ISSUE 14): a 2-worker
    in-process fleet — one prefill-biased (large ``serving_prefill_budget``,
    4 concurrent prefill chunks), one decode-biased (budget 4) — under
    mixed long-prompt + streaming load, run twice in the same process:

      * **co-located**: jobs round-robin across both workers, no hand-off
        (every session prefills AND decodes wherever it lands — long
        prompt chunks share ragged steps with streaming decode rows);
      * **disaggregated**: every job routes to the prefill worker (the
        ServingPlacer policy), which live-migrates each session to the
        decode worker once its prompt finishes prefilling.

    Same two workers, same workload — the delta is the deployment policy.
    The measured class is the STREAMING sessions; the long prompts are the
    non-streaming BATCH disturbance.  The ragged entry point's shapes are
    static (ISSUE 11), so a mixed worker pays its prefill budget's flat-
    buffer slots on EVERY decode step forever — the co-location tax is
    structural — while disaggregation's costs (the hand-off blip, the
    ingestion burst) are transient.  The headline is therefore the
    STEADY-STATE stream inter-token p99: gaps from the second half of each
    stream, after the hand-offs and the long-prompt waves have passed —
    the co-located fleet is still paying the mixed-program tax there, the
    decode worker is running the right-sized program.  Also reported:
    stream TTFT p50, long-job completion p50, the full co/disagg ratios,
    and the hand-off migration count (floor-gated: a disaggregated pass
    that never migrates is not disaggregated)."""
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.memstore import MemoryStore
    from cordum_tpu.models import llama
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import (
        BusPacket, JobRequest, STATUS_HINT_STREAM,
    )
    from cordum_tpu.worker.handlers import (
        TPUCompute, make_serving_engine, make_tpu_handlers,
    )
    from cordum_tpu.worker.runtime import Worker

    if device == "cpu":
        # tiny-plus: big enough that flat-buffer slots dominate step cost
        # (T=16 ≈ 21ms vs T=28 ≈ 31ms vs T=60 ≈ 71ms per step measured on
        # the 1-core host — the program-size tax being measured), small
        # enough that two warmed backends fit a CI runner
        lcfg = llama.LlamaConfig(vocab_size=256, d_model=128, n_layers=4,
                                 n_heads=4, n_kv_heads=2, d_ff=256,
                                 max_seq_len=512)
        n_long, n_stream = 4, 6
        long_prompt, long_new = 96, 4
        stream_prompt, stream_new = 8, 192
    else:
        lcfg = llama.LlamaConfig(vocab_size=32000, d_model=1024, n_layers=8,
                                 n_heads=8, n_kv_heads=4, d_ff=3584,
                                 max_seq_len=512)
        n_long, n_stream = 8, 8
        long_prompt, long_new = 256, 8
        stream_prompt, stream_new = 8, 192
    n_jobs = n_long + n_stream
    page_size = 16
    pages_per = -(-(long_prompt + max(long_new, stream_new)) // page_size)
    cache_pages = n_jobs * pages_per + 8  # every session fits either worker

    async def run_pass(disagg: bool) -> dict:
        bus = LoopbackBus()
        ms = MemoryStore(MemoryKV())
        workers = []
        # co-located = the uniform mixed fleet (default prefill budget on
        # both workers, no hand-off); disaggregated = the SAME two workers
        # redeployed as one prefill-biased ingester (budget 48, 4
        # concurrent chunks — affordable precisely because it stops
        # decoding) + one decode-biased generator (budget 4), with every
        # session migrating to the decoder post-prefill
        specs = (
            (("w-pre", "prefill", 48, 4), ("w-dec", "decode", 4, 1))
            if disagg else
            (("w-pre", "mixed", 16, 2), ("w-dec", "mixed", 16, 2))
        )
        for wid, role, budget, prefills in specs:
            w = Worker(bus=bus, store=ms, worker_id=wid, pool="bench",
                       heartbeat_interval_s=999, serving_role=role)
            compute = TPUCompute(tp=1, llama_cfg=lcfg)
            w.register_default(make_tpu_handlers(compute))
            w.attach_serving(make_serving_engine(
                compute, w, cache_pages=cache_pages, page_size=page_size,
                max_sessions=n_jobs,
                max_new_tokens=max(long_new, stream_new),
                max_concurrent_prefills=prefills, prefill_budget=budget))
            await w.start()
            workers.append(w)
        for w in workers:
            # warm the single ragged program so the timed window measures
            # the policy, not XLA compilation
            w.serving.backend.prefill(list(range(2, 10)), [1])
        for w in workers:
            # peers learn each other's migration listener + role + headroom
            await w.send_heartbeat()
        await asyncio.sleep(0)

        submit_at: dict = {}
        ttft: dict = {}
        seen: dict = {}
        last_arrival: dict = {}
        gaps: list = []
        long_done_ms: list = []
        done = asyncio.Event()
        finished = set()

        async def tap_progress(subject, pkt):
            pr = pkt.job_progress
            if pr is None or pr.status_hint != STATUS_HINT_STREAM:
                return
            if pr.job_id not in submit_at or not pr.tokens:
                return
            now = time.perf_counter()
            if pr.offset < seen.get(pr.job_id, 0):
                return  # handover replay of already-streamed tokens
            tok_idx = pr.offset + len(pr.tokens)
            seen[pr.job_id] = tok_idx
            if pr.job_id not in ttft:
                ttft[pr.job_id] = now - submit_at[pr.job_id]
            elif pr.job_id in last_arrival:
                # (token index, gap): the steady-state p99 keeps only the
                # second half of each stream — past the hand-off blip and
                # the long-prompt ingestion window
                gaps.append((tok_idx, now - last_arrival[pr.job_id]))
            last_arrival[pr.job_id] = now

        async def tap_result(subject, pkt):
            res = pkt.job_result
            if res is not None and res.job_id in submit_at:
                assert res.status == "SUCCEEDED", (
                    res.job_id, res.status, res.error_message)
                if res.job_id.endswith("L"):
                    long_done_ms.append(
                        (time.perf_counter() - submit_at[res.job_id]) * 1000.0)
                finished.add(res.job_id)
                if len(finished) >= n_jobs:
                    done.set()

        subs = [await bus.subscribe(subj.PROGRESS, tap_progress),
                await bus.subscribe(subj.RESULT, tap_result)]
        tag = "d" if disagg else "c"

        async def submit(i: int, is_long: bool) -> None:
            jid = f"{tag}{i}{'L' if is_long else 'S'}"
            plen = long_prompt if is_long else stream_prompt
            ptr = await ms.put_context(jid, {
                "op": "llm.generate",
                "tokens": [(i * 13 + j) % lcfg.vocab_size
                           for j in range(plen)],
                "max_new_tokens": long_new if is_long else stream_new,
                "session_id": f"{tag}conv-{i}",
                # streams are the measured latency class; the long-prompt
                # BATCH jobs are the disturbance (no token stream — their
                # cost is step-budget theft, measured via completion time)
                "stream": not is_long,
            })
            # disaggregated: everything routes to the prefill worker (the
            # ServingPlacer policy); co-located: round-robin spread over
            # the uniform fleet
            target = "w-pre" if disagg else ("w-pre", "w-dec")[i % 2]
            submit_at[jid] = time.perf_counter()
            await bus.publish(
                subj.direct_subject(target),
                BusPacket.wrap(JobRequest(
                    job_id=jid, topic="job.tpu.generate", context_ptr=ptr,
                    priority="BATCH" if is_long else "INTERACTIVE",
                )),
            )

        t0 = time.perf_counter()
        for i in range(n_stream):
            await submit(i, False)
        # long-prompt waves land on top of the running streams early: the
        # disturbance (and the hand-offs it triggers) plays out inside the
        # streams' first half, leaving the second half steady-state
        for wave in range(2):
            await asyncio.sleep(0.15)
            for k in range(n_long // 2):
                await submit(n_stream + wave * (n_long // 2) + k, True)
        await asyncio.wait_for(done.wait(), timeout=JAX_TIMEOUT_S / 2)
        dt = time.perf_counter() - t0
        migrations = sum(w.serving.stats.migrated_in for w in workers)
        decoded = sum(w.serving.stats.decoded_tokens for w in workers)
        for s in subs:
            s.unsubscribe()
        for w in workers:
            await w.stop()
        await bus.close()
        ttfts = sorted(ttft.values())
        steady = sorted(g for idx, g in gaps if idx > stream_new // 2)
        longs_sorted = sorted(long_done_ms)
        return {
            "ttft_p50_ms": (ttfts[len(ttfts) // 2] * 1000.0) if ttfts else 0.0,
            "inter_token_p99_ms": (
                steady[min(len(steady) - 1,
                           int(len(steady) * 0.99))] * 1000.0
            ) if steady else 0.0,
            "long_job_p50_ms": (
                longs_sorted[len(longs_sorted) // 2] if longs_sorted else 0.0
            ),
            "migrations": migrations,
            "tokens_per_sec": decoded / dt if dt > 0 else 0.0,
        }

    co = await run_pass(False)
    dis = await run_pass(True)
    return {
        "disagg_ttft_p50_ms": round(dis["ttft_p50_ms"], 2),
        "colocated_ttft_p50_ms": round(co["ttft_p50_ms"], 2),
        "disagg_ttft_gain": round(
            co["ttft_p50_ms"] / dis["ttft_p50_ms"], 2
        ) if dis["ttft_p50_ms"] > 0 else 0.0,
        "disagg_inter_token_p99_ms": round(dis["inter_token_p99_ms"], 2),
        "colocated_inter_token_p99_ms": round(co["inter_token_p99_ms"], 2),
        "disagg_inter_token_gain": round(
            co["inter_token_p99_ms"] / dis["inter_token_p99_ms"], 2
        ) if dis["inter_token_p99_ms"] > 0 else 0.0,
        "disagg_long_job_p50_ms": round(dis["long_job_p50_ms"], 2),
        "colocated_long_job_p50_ms": round(co["long_job_p50_ms"], 2),
        "disagg_migrations_done": dis["migrations"],
        "disagg_decode_tokens_per_sec": round(dis["tokens_per_sec"], 1),
        "colocated_decode_tokens_per_sec": round(co["tokens_per_sec"], 1),
    }


def bench_session_affinity(n_sessions: int = 32, turns: int = 20,
                           workers: int = 4) -> dict:
    """Scheduler-side session-affinity hit rate: interleaved decode turns of
    ``n_sessions`` conversations over a ``workers``-worker pool.  Steady
    state (every turn after a session's first routing) must ride to the
    worker holding the session's KV pages — the ISSUE 7 bar is ≥95%.
    Pure control-plane: no jax, runs in the parent process."""
    from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.protocol.types import Heartbeat, JobRequest, LABEL_SESSION_KEY

    reg = WorkerRegistry()
    pc = parse_pool_config({"topics": {"job.tpu.generate": "tpu"},
                            "pools": {"tpu": {}}})
    strat = LeastLoadedStrategy(reg, pc)
    for w in range(workers):
        reg.update(Heartbeat(worker_id=f"w{w}", pool="tpu",
                             max_parallel_jobs=256))
    routed: dict[str, set] = {}
    for turn in range(turns):
        for s in range(n_sessions):
            subject = strat.pick_subject(JobRequest(
                job_id=f"s{s}t{turn}", topic="job.tpu.generate",
                labels={LABEL_SESSION_KEY: f"conv-{s}"},
            ))
            routed.setdefault(f"conv-{s}", set()).add(subject)
    steady = strat.session_affinity_hits + strat.session_affinity_misses
    return {
        "serving_affinity_hit_rate": round(
            strat.session_affinity_hits / steady, 4) if steady else 0.0,
        "serving_affinity_sessions_smeared": sum(
            1 for subs in routed.values() if len(subs) > 1),
    }


async def _storm_pass(*, admission: bool, duration_s: float,
                      settle_s: float = 5.0) -> dict:
    """One storm run: an open-loop multi-tenant generator overdrives a
    two-worker heterogeneous fleet at ~2× its measured capacity through the
    REAL admission→engine→worker pipeline (AdmissionController fed by a
    live FleetAggregator + SLOTracker, ThroughputAwareStrategy over a live
    CapacityView).  ``admission=False`` is the control run: same storm, no
    shedding — proving the controller, not slack, holds interactive p99.

    Latency accounting is censorship-honest: jobs still queued when the
    settle window closes contribute their AGE as a lower-bound latency, so
    a collapsed control run cannot fake a good p99 by never finishing."""
    from cordum_tpu.controlplane.gateway.admission import AdmissionController
    from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
    from cordum_tpu.controlplane.scheduler.engine import Engine
    from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
    from cordum_tpu.controlplane.scheduler.strategy import ThroughputAwareStrategy
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.jobstore import JobStore
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.loadgen import LoadGen, TenantSpec
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.obs import FleetAggregator, SLOTracker, TelemetryExporter
    from cordum_tpu.obs.capacity import CapacityProfiler, CapacityView
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import (
        BusPacket, Heartbeat, JobRequest, JobResult, LABEL_OP,
    )

    kv = MemoryKV()
    bus = LoopbackBus()
    js = JobStore(kv)
    kernel = SafetyKernel(policy_doc={
        "tenants": {"default": {"allow_topics": ["job.*", "job.>"]}},
    })
    reg = WorkerRegistry()
    pc = parse_pool_config({"topics": {"job.storm": "storm"},
                            "pools": {"storm": {"requires": []}}})
    cap_view = CapacityView(stale_after_s=30.0)
    await cap_view.start(bus)
    strategy = ThroughputAwareStrategy(reg, pc, capacity=cap_view)
    eng = Engine(bus=bus, job_store=js, safety=SafetyClient(kernel.check),
                 strategy=strategy, registry=reg)
    await eng.start()

    # -- two heterogeneous simulated workers (fast 2× the slow one): each
    # runs serial (parallel=1) so the profiler's device-time items/s IS the
    # worker's true service rate and the measured matrix equals capacity
    service_ms = {"w-fast": {"chat": 8.0, "embed": 16.0},
                  "w-slow": {"chat": 16.0, "embed": 32.0}}
    submit_t: dict[str, tuple[float, str]] = {}  # job_id → (t0, class)
    latencies: dict[str, list[float]] = {"INTERACTIVE": [], "BATCH": []}
    completed: dict[str, int] = {"INTERACTIVE": 0, "BATCH": 0}
    exporters = []
    profs: dict[str, CapacityProfiler] = {}
    for wid, services in service_ms.items():
        prof = profs[wid] = CapacityProfiler("cpu", full_every=2)
        sem = asyncio.Semaphore(1)
        reg.update(Heartbeat(worker_id=wid, pool="storm",
                             max_parallel_jobs=1 << 30))

        def make_handler(prof=prof, sem=sem, services=services, wid=wid):
            async def handler(subject, pkt):
                req = pkt.job_request
                if req is None:
                    return
                op = (req.labels or {}).get(LABEL_OP, "chat")
                service_s = services.get(op, 0.01) / 1000.0
                async with sem:
                    await asyncio.sleep(service_s)
                prof.observe(op, device_s=service_s, items=1)
                t0, klass = submit_t.pop(req.job_id, (None, "BATCH"))
                if t0 is not None:
                    latencies[klass].append(time.perf_counter() - t0)
                    completed[klass] += 1
                await bus.publish(subj.RESULT, BusPacket.wrap(
                    JobResult(job_id=req.job_id, status="SUCCEEDED",
                              worker_id=wid),
                    trace_id=pkt.trace_id, sender_id=wid))
            return handler

        await bus.subscribe(subj.direct_subject(wid), make_handler(), queue=wid)
        exporters.append(TelemetryExporter(
            "worker", bus, Metrics(), instance_id=wid, interval_s=0.5,
            health_fn=(lambda prof=prof: {"role": "worker",
                                          "capacity": prof.snapshot()}),
        ))
    # scheduler beacon: the aggregator needs the engine registry (SLO burn
    # sources) and the queue-depth fallback signal
    exporters.append(TelemetryExporter(
        "scheduler", bus, eng.metrics, instance_id="storm-sched",
        interval_s=0.5,
        health_fn=lambda: {"role": "scheduler", "queue_depth": eng._inflight},
    ))
    agg = FleetAggregator(bus, metrics=Metrics(), fine_step_s=0.5)
    await agg.start()
    for ex in exporters:
        await ex.start()
    tracker = SLOTracker.from_config({
        "interactive": {"job_class": "INTERACTIVE", "latency_ms": 500,
                        "latency_target": 0.9},
        "batch": {"job_class": "BATCH", "latency_ms": 5000,
                  "latency_target": 0.5},
    })
    controller = AdmissionController(
        fleet=agg, slo_tracker=tracker,
        config={
            "enabled": admission, "safety_factor": 0.7,
            "queue_depth_limit": 200,
            "tenants": {"default": {"rate_rps": 0, "burst": 0}},
        },
        metrics=Metrics(), bus=bus, instance_id="storm-gw",
    )

    # -- warm the matrix: feed each worker's true per-op service time into
    # its profiler (what a short calibration pass would measure), beacon,
    # fold — so admission starts analytic and routing starts skew-aware
    for wid, services in service_ms.items():
        for op, ms in services.items():
            for _ in range(20):
                profs[wid].observe(op, device_s=ms / 1000.0, items=1)
    for ex in exporters:
        await ex.publish_once()
    await bus.drain()
    controller.refresh()
    capacity_chat = controller._capacity.get("chat", 0.0) / max(
        0.01, controller.safety_factor)  # un-scaled measured items/s

    seq = 0

    async def submit_job(op: str, klass: str) -> str:
        nonlocal seq
        seq += 1
        jid = f"storm-{'a' if admission else 'c'}-{seq}"
        submit_t[jid] = (time.perf_counter(), klass)
        req = JobRequest(job_id=jid, topic="job.storm", priority=klass,
                         tenant_id="default", labels={LABEL_OP: op})
        await bus.publish(subj.SUBMIT, BusPacket.wrap(req, sender_id="storm"))
        return jid

    # -- controller refresh loop (the gateway's _admission_loop equivalent)
    tier_max = 0

    async def refresh_loop() -> None:
        nonlocal tier_max
        while True:
            await asyncio.sleep(0.5)
            controller.refresh()
            tier_max = max(tier_max, controller.tier)
            await controller.publish_pressure()

    refresh_task = asyncio.ensure_future(refresh_loop())

    # -- the storm: offered ≈ 2× measured chat capacity, mixed classes
    offered_rate = 2.0 * max(50.0, capacity_chat)
    shed: dict[str, int] = {"INTERACTIVE": 0, "BATCH": 0}
    offered: dict[str, int] = {"INTERACTIVE": 0, "BATCH": 0}

    async def storm_submit(spec, session_id, turn) -> None:
        klass = spec.job_class
        offered[klass] = offered.get(klass, 0) + 1
        verdict = controller.admit(op=spec.op, job_class=klass,
                                   tenant="default")
        if not verdict.allowed:
            shed[klass] = shed.get(klass, 0) + 1
            return
        await submit_job(spec.op, klass)

    tenants = [
        TenantSpec(name="chat-users", job_class="INTERACTIVE", op="chat",
                   rate_rps=0.08 * offered_rate, session_turns=3,
                   think_time_s=0.2, diurnal_period_s=4.0, diurnal_amp=0.25),
        TenantSpec(name="batch-flood", job_class="BATCH", op="chat",
                   rate_rps=0.72 * offered_rate, burst_factor=2.0,
                   burst_every_s=3.0, burst_len_s=0.5),
        TenantSpec(name="embed-feed", job_class="BATCH", op="embed",
                   rate_rps=0.04 * offered_rate),
    ]
    gen = LoadGen(storm_submit, tenants, duration_s=duration_s)
    t_start = time.perf_counter()
    await gen.run()
    storm_wall = time.perf_counter() - t_start

    # settle: bounded drain, then censor still-queued jobs at their age
    deadline = time.perf_counter() + settle_s
    while time.perf_counter() < deadline and submit_t:
        await bus.drain()
        await asyncio.sleep(0.05)
    now = time.perf_counter()
    for jid, (t0, klass) in submit_t.items():
        latencies[klass].append(now - t0)

    refresh_task.cancel()
    try:
        await refresh_task
    except asyncio.CancelledError:
        pass
    for ex in exporters:
        await ex.stop()
    await agg.stop()
    await eng.stop()
    await cap_view.stop()
    await bus.close()

    def p(q: float, vals: list[float]) -> float:
        if not vals:
            return 0.0
        s = sorted(vals)
        return s[min(len(s) - 1, int(q * (len(s) - 1)))] * 1000.0

    total_shed = shed["INTERACTIVE"] + shed["BATCH"]
    return {
        "interactive_p50_ms": round(p(0.50, latencies["INTERACTIVE"]), 2),
        "interactive_p99_ms": round(p(0.99, latencies["INTERACTIVE"]), 2),
        "batch_p99_ms": round(p(0.99, latencies["BATCH"]), 2),
        "interactive_offered": offered["INTERACTIVE"],
        "interactive_shed": shed["INTERACTIVE"],
        "interactive_shed_rate": round(
            shed["INTERACTIVE"] / offered["INTERACTIVE"], 4
        ) if offered["INTERACTIVE"] else 0.0,
        "batch_offered": offered["BATCH"],
        "batch_shed": shed["BATCH"],
        "batch_shed_share": round(shed["BATCH"] / total_shed, 4)
        if total_shed else 1.0,
        "batch_goodput": round(completed["BATCH"] / storm_wall, 1),
        "interactive_completed": completed["INTERACTIVE"],
        "batch_completed": completed["BATCH"],
        "capacity_measured": round(capacity_chat, 1),
        "offered_rate": round(offered_rate, 1),
        "brownout_tier_max": tier_max,
        "preempt_requested": int(
            eng.metrics.preemptions.value(reason="requested")),
        "unfinished": len(submit_t),
    }


async def bench_storm(smoke: bool = True) -> dict:
    """Multi-tenant storm harness (docs/ADMISSION.md §Storm harness): the
    ISSUE 13 judgment call — at ~2× measured fleet capacity with mixed
    classes, interactive p99 holds and interactive shed ≈ 0 while BATCH
    absorbs the shedding; the admission-disabled control run degrades,
    proving the controller (not slack) holds the line.  Floor keys:
    ``storm_interactive_p99_ms`` (ceiling), ``storm_interactive_shed_rate``
    (ceiling ≈ 0), ``storm_batch_goodput`` (floor),
    ``storm_control_vs_admitted_p99`` (floor > 1)."""
    duration = 6.0 if smoke else 12.0
    admitted = await _storm_pass(admission=True, duration_s=duration)
    control = await _storm_pass(admission=False, duration_s=duration)
    ratio = (
        control["interactive_p99_ms"] / admitted["interactive_p99_ms"]
        if admitted["interactive_p99_ms"] > 0 else 0.0
    )
    return {
        "storm_interactive_p50_ms": admitted["interactive_p50_ms"],
        "storm_interactive_p99_ms": admitted["interactive_p99_ms"],
        "storm_interactive_shed_rate": admitted["interactive_shed_rate"],
        "storm_interactive_offered": admitted["interactive_offered"],
        "storm_interactive_completed": admitted["interactive_completed"],
        "storm_batch_shed_share": admitted["batch_shed_share"],
        "storm_batch_goodput": admitted["batch_goodput"],
        "storm_batch_p99_ms": admitted["batch_p99_ms"],
        "storm_capacity_measured": admitted["capacity_measured"],
        "storm_offered_rate": admitted["offered_rate"],
        "storm_brownout_tier_max": admitted["brownout_tier_max"],
        "storm_preempt_requested": admitted["preempt_requested"],
        "storm_control_interactive_p99_ms": control["interactive_p99_ms"],
        "storm_control_unfinished": control["unfinished"],
        "storm_control_vs_admitted_p99": round(ratio, 2),
    }


async def bench_agents(smoke: bool = True) -> dict:
    """Agent-loop storm (ISSUE 17, docs/WORKFLOWS.md §Storm harness):
    loadgen-driven multi-step agent workflows — llm.generate → context.update
    → context.window (RAG) → llm.generate — through the REAL pipeline:
    gateway-style admission at run start, workflow engine dispatch, scheduler
    session/batch-affinity routing, simulated serving workers that track
    per-session prefill state, context embeds as pool jobs (BusEmbedder),
    and workflow resume via the queue-group result consumer + reconciler.

    The agent-serving invariants under load:
      * ``agents_affinity_hit_rate`` — steady-state generate turns route to
        the worker already holding the session's KV pages;
      * ``agents_reprefills`` — sessions that cold-prefilled on a second
        worker (the no-re-prefill acceptance bar: 0);
      * ``agents_workflow_steps_per_sec`` / ``agents_step_p99_ms`` — the
        control plane's step engine keeps up (floors in bench_floor.json);
      * ``agents_context_embeds_per_sec`` — context embeds ride the real
        worker path as micro-batchable pool jobs."""
    from cordum_tpu.context.service import BusEmbedder, ContextService
    from cordum_tpu.controlplane.gateway.admission import AdmissionController
    from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
    from cordum_tpu.controlplane.scheduler.engine import Engine as SchedEngine
    from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
    from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
    from cordum_tpu.controlplane.workflowengine.service import (
        WorkflowEngineService,
    )
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.jobstore import JobStore
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.loadgen import LoadGen, TenantSpec
    from cordum_tpu.infra.memstore import MemoryStore
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.obs import FleetAggregator
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import (
        BusPacket, Heartbeat, JobResult, LABEL_OP, LABEL_SESSION_KEY,
        LABEL_SLO_CLASS,
    )
    from cordum_tpu.workflow import models as WM
    from cordum_tpu.workflow.engine import Engine as WfEngine
    from cordum_tpu.workflow.models import Workflow
    from cordum_tpu.workflow.store import WorkflowStore

    kv = MemoryKV()
    bus = LoopbackBus()
    mem = MemoryStore(kv)
    js = JobStore(kv)
    kernel = SafetyKernel(policy_doc={
        "tenants": {"default": {"allow_topics": ["job.*", "job.>"]}},
    })
    reg = WorkerRegistry()
    pc = parse_pool_config({
        "topics": {"job.tpu.generate": "tpu", "job.tpu.embed": "tpu"},
        "pools": {"tpu": {}},
    })
    strategy = LeastLoadedStrategy(reg, pc)
    sched = SchedEngine(bus=bus, job_store=js, safety=SafetyClient(kernel.check),
                        strategy=strategy, registry=reg)
    await sched.start()

    # -- simulated serving workers: per-session prefill state makes cold
    # starts observable — a session's first generate on a worker pays a
    # prefill; any LATER prefill of the same session is a re-prefill (the
    # affinity miss the tentpole forbids)
    n_workers = 3
    session_workers: dict[str, set] = {}
    prefills = [0]
    embedded = [0]
    decode_ms, prefill_ms, embed_ms = 2.0, 8.0, 2.0
    for w in range(n_workers):
        wid = f"agent-w{w}"
        reg.update(Heartbeat(worker_id=wid, pool="tpu",
                             max_parallel_jobs=1 << 30))

        def make_handler(wid=wid):
            async def handler(subject, pkt):
                req = pkt.job_request
                if req is None:
                    return
                t0 = time.perf_counter()
                op = (req.labels or {}).get(LABEL_OP, "")
                if op == "llm.generate":
                    skey = (req.labels or {}).get(LABEL_SESSION_KEY, "")
                    if skey:
                        owners = session_workers.setdefault(skey, set())
                        if wid not in owners:
                            owners.add(wid)
                            prefills[0] += 1
                            await asyncio.sleep(prefill_ms / 1000.0)
                    await asyncio.sleep(decode_ms / 1000.0)
                    out = {"text": f"gen:{req.job_id}", "tokens": 8}
                elif op == "embed":
                    payload = await mem.get_context(req.context_ptr) or {}
                    texts = payload.get("texts") or []
                    await asyncio.sleep(embed_ms / 1000.0)
                    out = {"embeddings": [[0.3] * 8 for _ in texts], "dim": 8}
                    embedded[0] += len(texts)
                else:
                    await asyncio.sleep(0.001)
                    out = {"ok": True}
                ptr = await mem.put_result(req.job_id, out)
                await bus.publish(subj.RESULT, BusPacket.wrap(
                    JobResult(
                        job_id=req.job_id, status="SUCCEEDED",
                        result_ptr=ptr, worker_id=wid,
                        execution_ms=int((time.perf_counter() - t0) * 1000),
                    ),
                    trace_id=pkt.trace_id, sender_id=wid))
            return handler

        await bus.subscribe(subj.direct_subject(wid), make_handler(), queue=wid)

    # -- workflow plane: engine + queue-group result consumer + reconciler,
    # context steps in-engine with embeds dispatched back to the pool
    embedder = BusEmbedder(bus, mem, timeout_s=30.0)
    ctx_svc = ContextService(kv, embedder=embedder)
    wf_store = WorkflowStore(kv)
    wf_metrics = Metrics()
    wf_engine = WfEngine(store=wf_store, bus=bus, mem=mem, metrics=wf_metrics,
                         instance_id="agents-wf", context_svc=ctx_svc)
    wf_svc = WorkflowEngineService(engine=wf_engine, bus=bus, job_store=js,
                                   instance_id="agents-wf",
                                   reconcile_interval_s=0.5)
    await wf_svc.start()

    # gateway-equivalent admission at run start (tier 0 without fleet
    # pressure — the run still pays the controller's book-keeping path)
    controller = AdmissionController(
        fleet=FleetAggregator(bus, metrics=Metrics()),
        config={"enabled": True, "queue_depth_limit": 10_000,
                "tenants": {"default": {"rate_rps": 0, "burst": 0}}},
        metrics=Metrics(), instance_id="agents-gw",
    )

    # the 4-step agent loop: generate → remember (context.update, embeds its
    # note chunk) → window (context.window RAG, embeds the query) → generate
    # with the window output in scope
    await wf_store.put_workflow(Workflow.from_dict({
        "id": "agent-loop",
        "slo_class": "INTERACTIVE",
        "steps": {
            "plan": {"topic": "job.tpu.generate",
                     "input": {"op": "llm.generate",
                               "prompt": "${input.goal}"}},
            "remember": {"topic": "job.tpu.context",
                         "depends_on": ["plan"],
                         "input": {"op": "context.update",
                                   "user_payload": "${input.goal}",
                                   "model_response": "${steps.plan.text}",
                                   "chunks": [{"file_path": "notes",
                                               "content": "${steps.plan.text}"}]}},
            "window": {"topic": "job.tpu.context",
                       "depends_on": ["remember"],
                       "input": {"op": "context.window", "mode": "RAG",
                                 "query": "${input.goal}"}},
            "act": {"topic": "job.tpu.generate",
                    "depends_on": ["window"],
                    "input": {"op": "llm.generate",
                              "prompt": "ctx ${steps.window.message_count}: "
                                        "${steps.plan.text}"}},
        },
    }))

    run_ids: list[str] = []
    shed = [0]

    async def start_agent_turn(spec, session_id, turn) -> None:
        verdict = controller.admit(op="workflow.run",
                                   job_class="INTERACTIVE", tenant="default")
        if not verdict.allowed:
            shed[0] += 1
            return
        run = await wf_engine.start_run(
            "agent-loop", {"goal": f"goal {session_id} t{turn}"},
            org_id="default",
            # every turn of one agent shares the session key (and thus the
            # memory + the serving worker): turn N resumes where N-1 left off
            labels={LABEL_SESSION_KEY: f"agent-{session_id}"},
        )
        run_ids.append(run.run_id)

    duration_s = 3.5 if smoke else 8.0
    rate = 6.0 if smoke else 25.0
    tenants = [TenantSpec(name="agents", job_class="INTERACTIVE",
                          op="llm.generate", rate_rps=rate,
                          session_turns=2, think_time_s=0.3)]
    gen = LoadGen(start_agent_turn, tenants, duration_s=duration_s)
    t_start = time.perf_counter()
    await gen.run()

    # settle: drive the pipeline until every started run is terminal
    deadline = time.perf_counter() + (10.0 if smoke else 20.0)
    terminal = set(WM.RUN_TERMINAL)
    runs = []
    while time.perf_counter() < deadline:
        await bus.drain()
        await wf_engine.drain_context_steps()
        runs = await wf_store.get_runs(run_ids)
        if runs and all(r is not None and r.status in terminal for r in runs):
            break
        await asyncio.sleep(0.05)
    wall = time.perf_counter() - t_start

    await wf_svc.stop()
    await embedder.stop()
    await sched.stop()
    await bus.close()

    step_ms: list[float] = []
    steps_done = 0
    runs_ok = runs_failed = 0
    for r in runs:
        if r is None:
            continue
        if r.status == WM.SUCCEEDED:
            runs_ok += 1
        elif r.status in terminal:
            runs_failed += 1
        for sr in r.steps.values():
            if sr.status == WM.SUCCEEDED:
                steps_done += 1
                if sr.finished_at_us and sr.started_at_us:
                    step_ms.append((sr.finished_at_us - sr.started_at_us) / 1e3)

    def p(q: float, vals: list) -> float:
        if not vals:
            return 0.0
        s = sorted(vals)
        return s[min(len(s) - 1, int(q * (len(s) - 1)))]

    # strategy counters: the first route of a session is "new" (neither hit
    # nor miss), so hits/(hits+misses) IS the steady-state affinity rate
    hits, misses = strategy.session_affinity_hits, strategy.session_affinity_misses
    sessions = len(session_workers)
    steady = hits + misses
    reprefills = sum(len(ws) - 1 for ws in session_workers.values() if len(ws) > 1)
    return {
        "agents_workflow_steps_per_sec": round(steps_done / wall, 1) if wall else 0.0,
        "agents_step_p50_ms": round(p(0.50, step_ms), 2),
        "agents_step_p99_ms": round(p(0.99, step_ms), 2),
        "agents_steps_completed": steps_done,
        "agents_runs_started": len(run_ids),
        "agents_runs_completed": runs_ok,
        "agents_runs_failed": runs_failed,
        "agents_runs_shed": shed[0],
        "agents_sessions": sessions,
        "agents_affinity_hit_rate": round(hits / steady, 4) if steady else 1.0,
        "agents_affinity_hits": hits,
        "agents_affinity_misses": misses,
        "agents_reprefills": reprefills,
        "agents_prefills": prefills[0],
        "agents_context_embeds": embedded[0],
        "agents_context_embeds_per_sec": round(embedded[0] / wall, 1) if wall else 0.0,
        "agents_context_embed_jobs": embedder.jobs_total,
    }


def bench_jax(*, smoke: bool = False) -> dict:
    """Run the compute bench child: the ``tpu`` child, or the explicit
    ``cpu`` child that ``--smoke`` asks for (it names its device).

    The parent stays off jax while the child holds the chip.  A host with
    no chip makes the ``tpu`` child exit non-zero, which surfaces here as
    ``embed_error``/``model_error`` and flags the run ``degraded`` — a CPU
    timing is never reported in a device metric's place.  The full child
    traceback rides along in ``child_traceback`` (CL002 applied to the
    bench harness)."""
    device = "cpu" if smoke else "tpu"
    # a chip belongs to one process: a parent that touched jax would hold it
    assert device == "cpu" or "jax" not in sys.modules, "bench parent must stay off jax"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--jax-child", device],
            capture_output=True, text=True, timeout=JAX_TIMEOUT_S,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line = (proc.stdout.strip().splitlines() or [""])[-1]
        child = json.loads(line) if line.startswith("{") else {}
        if not child:
            tail = (proc.stderr or proc.stdout or "")[-300:]
            child = {"embed_error": f"child rc={proc.returncode}: {tail}",
                     "model_error": f"child rc={proc.returncode}"}
        if ("embed_error" in child or "model_error" in child) and proc.stderr:
            # full crash context, not just the one-line summary
            child["child_traceback"] = proc.stderr[-8000:]
    except subprocess.TimeoutExpired as te:
        child = {"embed_error": f"{device} bench timed out after {JAX_TIMEOUT_S}s",
                 "model_error": "timeout"}
        partial = te.stderr.decode(errors="replace") if isinstance(te.stderr, bytes) else (te.stderr or "")
        if partial:
            child["child_traceback"] = partial[-8000:]
    except Exception as ex:  # noqa: BLE001
        child = {"embed_error": f"{type(ex).__name__}: {ex}"[:300]}
    return child


def main() -> None:
    global N_JOBS, PACED_JOBS, PACED_RATE, JAX_TIMEOUT_S
    # hermetic placement: the bench itself saturates the host, and real
    # loadavg-derived cpu_load would flip its in-process workers to
    # overloaded (breaking the affinity-hit floors it gates on)
    os.environ.setdefault("CORDUM_HOST_LOAD", "0")
    if len(sys.argv) >= 2 and sys.argv[1] == "--jax-child":
        _jax_child(sys.argv[2] if len(sys.argv) > 2 else "tpu")
        return
    if len(sys.argv) >= 2 and sys.argv[1] == "--statebus-child":
        _statebus_child(int(sys.argv[2]),
                        sys.argv[3] if len(sys.argv) > 3 else "")
        return
    if "--replicated" in sys.argv:
        # statebus replication overhead mode (ISSUE 8): one JSON line, keys
        # match the full bench's statebus section so bench_floor.json gates
        # both surfaces identically.
        out = {"metric": "statebus_replication_overhead_pct", "unit": "%"}
        out.update(bench_replication_overhead())
        out["value"] = out["statebus_replication_overhead_pct"]
        print(json.dumps(out))
        return
    if len(sys.argv) >= 2 and sys.argv[1] == "--shard-child":
        _shard_child(int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
        return
    if len(sys.argv) >= 2 and sys.argv[1] == "--gang-child":
        _gang_child("smoke" in sys.argv[2:])
        return
    if len(sys.argv) >= 2 and sys.argv[1] == "--tp-child":
        _tp_child("smoke" in sys.argv[2:])
        return
    if "--tp" in sys.argv:
        # sharded serving gang mode (ISSUE 20): the same session set on a
        # TP=2 in-process gang vs a single-rank worker — token identity,
        # one compiled ragged program per rank, same-run wall ratio.  One
        # JSON line, same tp_* keys as the full bench so bench_floor.json
        # gates both surfaces.
        out = {"metric": "tp_tokens_per_sec", "unit": "tokens/s"}
        out.update(bench_tp(smoke="--smoke" in sys.argv))
        out["value"] = out.get("tp_tokens_per_sec", 0.0)
        print(json.dumps(out))
        return
    if "--gang" in sys.argv:
        # gang-scheduling mode (ISSUE 15): barrier-only gang throughput +
        # the three MULTICHIP dryrun flows (dense/moe/MPMD-pipeline) as
        # scheduled gang jobs through the real submit → reserve →
        # rendezvous → result pipeline.  One JSON line, same gang_* keys
        # as the full bench so bench_floor.json gates both surfaces.
        out = {"metric": "gang_jobs_per_sec", "unit": "gangs/s"}
        out.update(bench_gang(smoke="--smoke" in sys.argv))
        out["value"] = out.get("gang_jobs_per_sec", 0.0)
        print(json.dumps(out))
        return
    if "--storm" in sys.argv:
        # storm-only mode (ISSUE 13): the multi-tenant overload harness —
        # admission on vs the control run.  One JSON line, same storm_*
        # keys as the full bench so bench_floor.json gates both surfaces.
        out = {"metric": "storm_interactive_p99_ms", "unit": "ms"}
        out.update(asyncio.run(bench_storm(smoke="--smoke" in sys.argv)))
        out["value"] = out["storm_interactive_p99_ms"]
        print(json.dumps(out))
        return
    if "--agents" in sys.argv:
        # agent-workflow mode (ISSUE 17): the agent-loop storm — concurrent
        # multi-step workflows with think time through admission → affinity-
        # routed serving → context embeds on the pool → workflow resume.
        # One JSON line, same agents_* keys as the full bench so
        # bench_floor.json gates both surfaces.
        out = {"metric": "agents_workflow_steps_per_sec", "unit": "steps/s"}
        out.update(asyncio.run(bench_agents(smoke="--smoke" in sys.argv)))
        out["value"] = out["agents_workflow_steps_per_sec"]
        print(json.dumps(out))
        return
    if "--serving" in sys.argv:
        # serving-only mode (ISSUE 7): the continuous-batching worker bench
        # (in-process; set JAX_PLATFORMS=cpu off-TPU) + the scheduler
        # session-affinity hit rate.  One JSON line, same keys as the full
        # bench's serving section.
        out = {"metric": "decode_tokens_per_sec"}
        out.update(asyncio.run(_bench_worker_serving(
            "cpu" if os.environ.get("JAX_PLATFORMS", "") == "cpu" else "tpu")))
        out.update(bench_session_affinity())
        out["value"] = out["decode_tokens_per_sec"]
        out["unit"] = "tokens/s"
        print(json.dumps(out))
        return
    if "--chat" in sys.argv:
        # chat mode (ISSUE 18): prefix-cache TTFT speedup + session-tiering
        # residency/restore on the real paged backend.  One JSON line, same
        # chat_* keys as the full bench so bench_floor.json gates both
        # surfaces.
        out = {"metric": "chat_prefix_ttft_speedup", "unit": "x"}
        out.update(asyncio.run(_bench_chat(
            "cpu" if os.environ.get("JAX_PLATFORMS", "") == "cpu" else "tpu")))
        out["value"] = out.get("chat_prefix_ttft_speedup", 0.0)
        print(json.dumps(out))
        return
    if "--spec" in sys.argv:
        # speculative-decoding mode (ISSUE 19): the self-drafted
        # multi-token verification bench — speculation-off vs -on on the
        # identical templated workload, token-identity gated.  One JSON
        # line, same spec_* keys as the full bench so bench_floor.json
        # gates both surfaces.
        out = {"metric": "spec_decode_speedup", "unit": "x"}
        out.update(asyncio.run(_bench_spec(
            "cpu" if os.environ.get("JAX_PLATFORMS", "") == "cpu" else "tpu")))
        out["value"] = out.get("spec_decode_speedup", 0.0)
        print(json.dumps(out))
        return
    if "--disagg" in sys.argv:
        # disaggregation-only mode (ISSUE 14): co-located vs disaggregated
        # prefill/decode over a 2-worker in-process fleet, same run.  One
        # JSON line, same disagg_* keys as the full bench so
        # bench_floor.json gates both surfaces.
        out = {"metric": "disagg_ttft_p50_ms", "unit": "ms"}
        out.update(asyncio.run(_bench_disagg(
            "cpu" if os.environ.get("JAX_PLATFORMS", "") == "cpu" else "tpu")))
        out["value"] = out["disagg_ttft_p50_ms"]
        print(json.dumps(out))
        return
    smoke = "--smoke" in sys.argv
    profile = "--profile" in sys.argv or smoke  # smoke ships the breakdown in CI
    if smoke:
        # CI sanity mode: small sizes, cpu-only compute child, same JSON shape
        N_JOBS = min(N_JOBS, 400)
        PACED_JOBS = min(PACED_JOBS, 200)
        PACED_RATE = min(PACED_RATE, 500.0)
        JAX_TIMEOUT_S = min(JAX_TIMEOUT_S, 240.0)
    sb_jobs = min(STATEBUS_JOBS, 150) if smoke else STATEBUS_JOBS
    # smoke: 2 shards × 2 statebus partitions (the CI topology); full mode
    # defaults to 4 × 2 (the ISSUE 5 acceptance topology)
    shards = min(SHARDS, 2) if smoke else SHARDS
    sh_jobs = min(SHARDED_JOBS, 300) if smoke else SHARDED_JOBS
    sched = asyncio.run(bench_scheduler())
    lat = asyncio.run(bench_latency())
    sb_pipe = asyncio.run(bench_statebus(True, sb_jobs))
    sb_perop = asyncio.run(bench_statebus(False, sb_jobs))
    sb_repl = bench_replication_overhead()
    tele = bench_telemetry()
    capprof = bench_profiling()
    sharded = asyncio.run(bench_sharded(shards, SB_PARTITIONS, sh_jobs))
    sharded_single = asyncio.run(bench_sharded(1, 1, sh_jobs))
    sel = bench_selection()
    prof = bench_profile() if profile else None
    affinity = bench_session_affinity()
    storm = asyncio.run(bench_storm(smoke=smoke))
    agents = asyncio.run(bench_agents(smoke=smoke))
    gang = bench_gang(smoke=smoke)
    tp = bench_tp(smoke=smoke)
    jx = bench_jax(smoke=smoke)
    out = {
        "metric": "scheduled_jobs_per_sec",
        "value": round(sched["jobs_per_sec"], 1),
        "unit": "jobs/s",
        "vs_baseline": round(sched["jobs_per_sec"] / BASELINE_JOBS_PER_SEC, 3),
        "jobs": sched["jobs"],
        # KV round-trip budget (ISSUE 4): submit→result chatter per job
        "kv_roundtrips_per_job": round(sched["kv_roundtrips_per_job"], 1),
        # statebus mode: the same schedule loop over a real TCP statebus,
        # pipelined vs. downgraded-to-per-op-calls on the same run
        "statebus_jobs_per_sec": round(sb_pipe["jobs_per_sec"], 1),
        "statebus_unpipelined_jobs_per_sec": round(sb_perop["jobs_per_sec"], 1),
        "statebus_pipeline_speedup": round(
            sb_pipe["jobs_per_sec"] / sb_perop["jobs_per_sec"], 2
        ) if sb_perop["jobs_per_sec"] else 0.0,
        "statebus_kv_roundtrips_per_job": round(sb_pipe["kv_roundtrips_per_job"], 1),
        "statebus_unpipelined_kv_roundtrips_per_job": round(
            sb_perop["kv_roundtrips_per_job"], 1
        ),
        # replication overhead (ISSUE 8): median over interleaved
        # plain/replicated pairs with a live replica subprocess tailing the
        # primary (async acks); same-run ratios so host speed cancels
        # (ceiling in bench_floor.json)
        **sb_repl,
        # fleet telemetry plane (ISSUE 9): export overhead over interleaved
        # plain/instrumented pairs + post-run fleet-snapshot correctness
        # (merged counter == engine registry, SLO burn rate present);
        # overhead ceiling + fleet_snapshot_ok floor live in bench_floor.json
        **tele,
        # capacity observatory (ISSUE 10): profiler cost over interleaved
        # telemetry/telemetry+profiling pairs + the post-run throughput-
        # matrix correctness flag (profiling_overhead_pct ceiling +
        # capacity_matrix_ok floor live in bench_floor.json)
        **capprof,
        # keyspace-sharded control plane (ISSUE 5): S scheduler-shard
        # processes over P statebus partition processes, vs the same
        # multi-process harness at 1×1
        "sharded_jobs_per_sec": round(sharded["jobs_per_sec"], 1),
        "sharded_p50_e2e_ms": round(sharded["p50_e2e_ms"], 2),
        "sharded_shards": sharded["shards"],
        "sharded_statebus_partitions": sharded["statebus_partitions"],
        "sharded_jobs": sharded["jobs"],
        "sharded_jobs_terminal": sharded["terminal_total"],
        "sharded_single_jobs_per_sec": round(sharded_single["jobs_per_sec"], 1),
        "sharded_single_p50_e2e_ms": round(sharded_single["p50_e2e_ms"], 2),
        "sharded_speedup": round(
            sharded["jobs_per_sec"] / sharded_single["jobs_per_sec"], 2
        ) if sharded_single["jobs_per_sec"] else 0.0,
        "p50_e2e_ms": round(lat.get("p50_e2e_ms", 0.0), 2),
        "p99_e2e_ms": round(lat.get("p99_e2e_ms", 0.0), 2),
        "stage_p50_ms": lat.get("stage_p50_ms", {}),
        "paced_rate_offered": round(lat.get("paced_offered_rate", 0.0), 1),
        "paced_completed": lat.get("paced_completed", 0),
        "selections_per_sec": round(sel["selections_per_sec"], 1),
        "native_scan": sel["native"],
        # TPU compute: always present, errors never swallowed
        "embeds_per_sec": round(jx.get("embeds_per_sec", 0.0), 1),
        "embed_error": jx.get("embed_error", ""),
        "model_tokens_per_sec": round(jx.get("model_tokens_per_sec", 0.0), 1),
        "model_error": jx.get("model_error", ""),
        "mfu": jx.get("mfu", None),
        "model_achieved_tflops": round(jx.get("model_achieved_tflops", 0.0), 2),
        "embed_device": jx.get("device", ""),
        # micro-batching: the real worker path, per-job vs coalesced
        "single_job_embeds_per_sec": jx.get("single_job_embeds_per_sec", 0.0),
        "batched_embeds_per_sec": jx.get("batched_embeds_per_sec", 0.0),
        "batched_speedup": jx.get("batched_speedup", 0.0),
        "batch_flushes": jx.get("batch_flushes", 0),
        "batched_error": jx.get("batched_error", ""),
        # serving (ISSUE 7): continuous-batching decode through the real
        # worker path, vs sequential per-session decode of the same workload
        "decode_tokens_per_sec": jx.get("decode_tokens_per_sec", 0.0),
        "prefill_tokens_per_sec": jx.get("prefill_tokens_per_sec", 0.0),
        "serving_ttft_p50_ms": jx.get("serving_ttft_p50_ms", 0.0),
        "sequential_decode_tokens_per_sec": jx.get(
            "sequential_decode_tokens_per_sec", 0.0),
        "serving_speedup": jx.get("serving_speedup", 0.0),
        "p50_inter_token_ms": jx.get("p50_inter_token_ms", 0.0),
        "inter_token_p99_ms": jx.get("inter_token_p99_ms", 0.0),
        "serving_mean_occupancy": jx.get("serving_mean_occupancy", 0.0),
        "serving_sessions": jx.get("serving_sessions", 0),
        "serving_compile_count": jx.get("serving_compile_count", 0),
        # live KV-page migration (ISSUE 12): decode pause per session hop
        "migration_pause_p50_ms": jx.get("migration_pause_p50_ms", 0.0),
        "migrations_done": jx.get("migrations_done", 0),
        "serving_error": jx.get("serving_error", ""),
        # disaggregated prefill/decode serving (ISSUE 14): co-located vs
        # post-prefill hand-off over a 2-worker heterogeneous fleet, same
        # run — TTFT p50 and inter-token p99 on both sides + the hand-off
        # migration count (collapse guards in bench_floor.json)
        "disagg_ttft_p50_ms": jx.get("disagg_ttft_p50_ms", 0.0),
        "colocated_ttft_p50_ms": jx.get("colocated_ttft_p50_ms", 0.0),
        "disagg_ttft_gain": jx.get("disagg_ttft_gain", 0.0),
        "disagg_inter_token_p99_ms": jx.get("disagg_inter_token_p99_ms", 0.0),
        "colocated_inter_token_p99_ms": jx.get(
            "colocated_inter_token_p99_ms", 0.0),
        "disagg_inter_token_gain": jx.get("disagg_inter_token_gain", 0.0),
        "disagg_long_job_p50_ms": jx.get("disagg_long_job_p50_ms", 0.0),
        "colocated_long_job_p50_ms": jx.get("colocated_long_job_p50_ms", 0.0),
        "disagg_migrations_done": jx.get("disagg_migrations_done", 0),
        "disagg_decode_tokens_per_sec": jx.get(
            "disagg_decode_tokens_per_sec", 0.0),
        "colocated_decode_tokens_per_sec": jx.get(
            "colocated_decode_tokens_per_sec", 0.0),
        "disagg_error": jx.get("disagg_error", ""),
        # prefix cache + session tiering (ISSUE 18): multi-turn chat over a
        # shared system prompt — prefix-hit TTFT vs cold (same-run ratio,
        # token-identical), resident conversations held above the device
        # arena via hibernation, and the cold→warm restore pause (speedup/
        # residency floors + restore-pause ceiling in bench_floor.json)
        "chat_ttft_cold_p50_ms": jx.get("chat_ttft_cold_p50_ms", 0.0),
        "chat_ttft_hit_p50_ms": jx.get("chat_ttft_hit_p50_ms", 0.0),
        "chat_prefix_ttft_speedup": jx.get("chat_prefix_ttft_speedup", 0.0),
        "chat_prefix_hit_rate": jx.get("chat_prefix_hit_rate", 0.0),
        "chat_token_identical": jx.get("chat_token_identical", 0),
        "chat_sessions": jx.get("chat_sessions", 0),
        "chat_resident_sessions": jx.get("chat_resident_sessions", 0),
        "chat_device_session_capacity": jx.get(
            "chat_device_session_capacity", 0),
        "chat_resident_over_capacity": jx.get(
            "chat_resident_over_capacity", 0.0),
        "chat_hibernated_pages": jx.get("chat_hibernated_pages", 0),
        "chat_restored_pages": jx.get("chat_restored_pages", 0),
        "chat_restore_pause_p50_ms": jx.get("chat_restore_pause_p50_ms", 0.0),
        "chat_error": jx.get("chat_error", ""),
        # self-speculative decoding (ISSUE 19): n-gram drafts verified as
        # k+1-token rows inside the ONE ragged program — wall speedup on
        # the templated workload vs the same prompts speculation-off,
        # token-identity gated (speedup + identity floors and the
        # compile-count ceiling live in bench_floor.json)
        "spec_decode_speedup": jx.get("spec_decode_speedup", 0.0),
        "spec_token_identity": jx.get("spec_token_identity", 0),
        "spec_accept_rate": jx.get("spec_accept_rate", 0.0),
        "spec_decode_tokens_per_s": jx.get("spec_decode_tokens_per_s", 0.0),
        "spec_base_tokens_per_s": jx.get("spec_base_tokens_per_s", 0.0),
        "spec_steps": jx.get("spec_steps", 0),
        "spec_base_steps": jx.get("spec_base_steps", 0),
        "spec_drafted_tokens": jx.get("spec_drafted_tokens", 0),
        "spec_accepted_tokens": jx.get("spec_accepted_tokens", 0),
        "spec_rolled_back_tokens": jx.get("spec_rolled_back_tokens", 0),
        "spec_compile_count": jx.get("spec_compile_count", 0),
        "spec_sessions": jx.get("spec_sessions", 0),
        "spec_error": jx.get("spec_error", ""),
        **affinity,
        # overload resilience (ISSUE 13): the multi-tenant storm at ~2×
        # measured capacity — interactive p99 holds, interactive shed ≈ 0,
        # batch absorbs the shedding, and the admission-disabled control
        # run degrades (floors/ceilings in bench_floor.json)
        **storm,
        # agentic workflow serving (ISSUE 17): the agent-loop storm —
        # session-carrying DAG steps through admission, session-affinity
        # serving, pool-executed context embeds, and workflow resume
        # (steps/s + hit-rate floors, step-p99 + re-prefill ceilings in
        # bench_floor.json)
        **agents,
        # gang scheduling (ISSUE 15): barrier-only gang rate + the three
        # MULTICHIP flows as scheduled gang jobs (gang_jobs_per_sec /
        # gang_flows_ok floors + the gang_partial_reservations == 0
        # all-or-nothing invariant ceiling live in bench_floor.json)
        **gang,
        # sharded serving gangs (ISSUE 20): the TP=2 gang vs single-rank
        # same-run comparison — token identity + one-program-per-rank are
        # exact contracts, tp_speedup is a 1-core-host collapse guard
        # (floors/ceiling in bench_floor.json)
        **tp,
    }
    if smoke:
        out["smoke"] = True
    if prof is not None:
        # per-layer µs/op breakdown: routing / codec / selection / commit
        out["profile"] = prof
    degraded = bool(out["embed_error"] or out["model_error"]
                    or out["batched_error"] or out["serving_error"]
                    or out["disagg_error"] or out["chat_error"]
                    or out["spec_error"] or out.get("gang_error")
                    or out.get("tp_error"))
    out["degraded"] = degraded
    if degraded:
        out["child_traceback"] = jx.get("child_traceback", "")
        sys.stderr.write(
            "\n*** BENCH DEGRADED: the JAX compute child failed — the control-"
            "plane numbers above are healthy but embed/model metrics are "
            "partial or missing. Child errors:\n"
            f"    embed_error: {out['embed_error'] or '-'}\n"
            f"    model_error: {out['model_error'] or '-'}\n"
            f"    batched_error: {out['batched_error'] or '-'}\n"
            f"    serving_error: {out['serving_error'] or '-'}\n"
        )
        if out["child_traceback"]:
            sys.stderr.write("--- child traceback (tail) ---\n")
            sys.stderr.write(out["child_traceback"][-2000:] + "\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
