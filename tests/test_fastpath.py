"""1×1 hot-path specialization (ISSUE 6): identity-dispatch collapse,
batched-tick == per-job equivalence (states, event logs, trace spans),
msgpack↔legacy-JSON stored-record compatibility, the CI perf-floor
checker, and the bench backend-probe watchdog contract."""
from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
from cordum_tpu.controlplane.scheduler.engine import Engine
from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
from cordum_tpu.infra.bus import LoopbackBus
from cordum_tpu.infra.codec import pack_record, unpack_record
from cordum_tpu.infra.config import parse_pool_config
from cordum_tpu.infra.jobstore import JobStore, SafetyDecisionRecord, events_key
from cordum_tpu.infra.kv import MemoryKV
from cordum_tpu.infra.statebus import PartitionedBus, PartitionedKV
from cordum_tpu.protocol import subjects as subj
from cordum_tpu.protocol.types import (
    BusPacket,
    Heartbeat,
    JobRequest,
    JobResult,
    LABEL_PARTITION,
)

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# identity-dispatch collapse (routing chosen at construction, not per op)
# ---------------------------------------------------------------------------


def test_partitioned_kv_single_part_collapses_to_backend():
    """An unsharded store IS its single backend: no routing wrapper object,
    so the 1×1 hot path pays zero per-op partition dispatch."""
    kv = MemoryKV()
    assert PartitionedKV([kv]) is kv
    multi = PartitionedKV([MemoryKV(), MemoryKV()])
    assert type(multi) is PartitionedKV and multi.n == 2


def test_partitioned_bus_single_collapses_to_backend():
    bus = LoopbackBus()
    assert PartitionedBus([bus]) is bus
    multi = PartitionedBus([LoopbackBus(), LoopbackBus()])
    assert type(multi) is PartitionedBus and multi.n == 2


def test_unsharded_engine_identity_ownership_and_no_stamp():
    """shard_count == 1 binds identity ownership and a no-op partition
    stamp at construction — no crc32, no label mutation on dispatch."""
    eng = _mk_engine(LoopbackBus(), MemoryKV(), batch_ticks=False)
    assert eng.owns("any-job-id") and eng.owns("another")
    req = JobRequest(job_id="j1", topic="job.bench")
    eng._stamp_partition(req)
    assert not (req.labels or {}).get(LABEL_PARTITION)
    sharded = _mk_engine(LoopbackBus(), MemoryKV(), batch_ticks=False,
                         shard_index=1, shard_count=2)
    req2 = JobRequest(job_id="j1", topic="job.bench")
    sharded._stamp_partition(req2)
    assert req2.labels[LABEL_PARTITION] == "1"


# ---------------------------------------------------------------------------
# batched tick path == per-job path (states, event logs, trace spans)
# ---------------------------------------------------------------------------


def _mk_engine(bus, kv, *, batch_ticks: bool, shard_index: int = 0,
               shard_count: int = 1) -> Engine:
    kernel = SafetyKernel(
        policy_doc={"tenants": {"default": {"allow_topics": ["job.*", "job.>"]}}}
    )
    from cordum_tpu.infra.registry import WorkerRegistry

    reg = WorkerRegistry()
    pc = parse_pool_config(
        {"topics": {"job.bench": "bench"}, "pools": {"bench": {"requires": []}}}
    )
    eng = Engine(
        bus=bus, job_store=JobStore(kv), safety=SafetyClient(kernel.check),
        strategy=LeastLoadedStrategy(reg, pc), registry=reg,
        instance_id=f"eng-{shard_index}", shard_index=shard_index,
        shard_count=shard_count, batch_ticks=batch_ticks,
    )
    reg.update(Heartbeat(worker_id="w1", pool="bench", max_parallel_jobs=1 << 30))
    return eng


async def _run_burst(job_ids: list[str], *, batch_ticks: bool):
    """Submit a burst, run to completion, return per-job
    (state, [event names], {span name: count}, schedule-parented names)."""
    kv = MemoryKV()
    bus = LoopbackBus()
    spans: list = []

    async def collect_span(subject, pkt):
        spans.append(pkt.payload)

    await bus.subscribe(subj.TRACE_SPAN, collect_span)
    eng = _mk_engine(bus, kv, batch_ticks=batch_ticks)
    await eng.start()

    async def worker_handler(subject, pkt):
        req = pkt.job_request
        await bus.publish(
            subj.RESULT,
            BusPacket.wrap(
                JobResult(job_id=req.job_id, status="SUCCEEDED", worker_id="w1"),
                sender_id="w1",
            ),
        )

    await bus.subscribe(subj.direct_subject("w1"), worker_handler, queue="w")
    for jid in job_ids:
        await bus.publish(
            subj.SUBMIT,
            BusPacket.wrap(
                JobRequest(job_id=jid, topic="job.bench", tenant_id="default"),
                sender_id="t",
            ),
        )
    js = JobStore(kv)
    for _ in range(2000):
        await bus.drain()
        states = [await js.get_state(j) for j in job_ids]
        if all(s == "SUCCEEDED" for s in states):
            break
        await asyncio.sleep(0.005)
    # let the trailing result spans flush
    for _ in range(10):
        await bus.drain()
        await asyncio.sleep(0.002)
    out = {}
    by_job: dict[str, list] = {}
    for sp in spans:
        jid = (sp.attrs or {}).get("job_id", "")
        if jid:
            by_job.setdefault(jid, []).append(sp)
    for jid in job_ids:
        ev = [e["event"] for e in await js.events(jid)]
        job_spans = by_job.get(jid, [])
        names: dict[str, int] = {}
        for sp in job_spans:
            names[sp.name] = names.get(sp.name, 0) + 1
        sched_ids = {sp.span_id for sp in job_spans if sp.name == "schedule"}
        under_schedule = sorted(
            sp.name for sp in job_spans if sp.parent_span_id in sched_ids
        )
        out[jid] = (await js.get_state(jid), ev, names, under_schedule)
    await eng.stop()
    await bus.close()
    return out


async def test_batched_tick_path_matches_per_job_path():
    """Tentpole equivalence: an identical job burst through the batched
    tick fast path lands the same final states, the same event logs, and
    the same trace-span structure as the per-job path."""
    jobs = [f"fp-{i}" for i in range(24)]
    batched = await _run_burst(jobs, batch_ticks=True)
    per_job = await _run_burst(jobs, batch_ticks=False)
    for jid in jobs:
        b_state, b_events, b_spans, b_under = batched[jid]
        p_state, p_events, p_spans, p_under = per_job[jid]
        assert b_state == p_state == "SUCCEEDED"
        assert b_events == p_events, f"{jid}: {b_events} != {p_events}"
        assert b_spans == p_spans, f"{jid}: {b_spans} != {p_spans}"
        # policy-check/strategy/dispatch parent under the schedule span in
        # both paths (the batched path takes explicit parents, not ambient
        # context — structure must not drift)
        assert b_under == p_under == ["dispatch", "policy-check", "strategy"]


async def test_batched_engine_observes_tick_metrics():
    """The fast path reports its batch sizes (cordum_sched_tick_batch_size)."""
    jobs = [f"tm-{i}" for i in range(8)]
    kv = MemoryKV()
    bus = LoopbackBus()
    eng = _mk_engine(bus, kv, batch_ticks=True)
    await eng.start()

    async def worker_handler(subject, pkt):
        req = pkt.job_request
        await bus.publish(
            subj.RESULT,
            BusPacket.wrap(
                JobResult(job_id=req.job_id, status="SUCCEEDED", worker_id="w1"),
                sender_id="w1",
            ),
        )

    await bus.subscribe(subj.direct_subject("w1"), worker_handler, queue="w")
    for jid in jobs:
        await bus.publish(
            subj.SUBMIT,
            BusPacket.wrap(
                JobRequest(job_id=jid, topic="job.bench", tenant_id="default"),
                sender_id="t",
            ),
        )
    js = JobStore(kv)
    for _ in range(2000):
        await bus.drain()
        states = [await js.get_state(j) for j in jobs]
        if all(s == "SUCCEEDED" for s in states):
            break
        await asyncio.sleep(0.005)
    assert all(s == "SUCCEEDED" for s in states)
    rendered = eng.metrics.render()
    assert "cordum_sched_tick_batch_size" in rendered
    count_lines = [ln for ln in rendered.splitlines()
                   if ln.startswith("cordum_sched_tick_batch_size_count")]
    assert count_lines and float(count_lines[0].rsplit(" ", 1)[1]) > 0
    await eng.stop()
    await bus.close()


# ---------------------------------------------------------------------------
# msgpack ↔ legacy-JSON stored-record compatibility
# ---------------------------------------------------------------------------


def test_unpack_record_reads_both_encodings():
    rec = {"ts_us": 7, "event": "submit", "n": 3}
    assert unpack_record(pack_record(rec)) == rec
    assert unpack_record(json.dumps(rec).encode()) == rec
    # tolerate the pretty-printed / whitespace-prefixed JSON some legacy
    # tooling wrote
    assert unpack_record(b"  \n" + json.dumps(rec, indent=1).encode()) == rec
    assert unpack_record(json.dumps([1, "a"]).encode()) == [1, "a"]


async def test_event_log_mixes_legacy_json_and_msgpack():
    """Old AOF/KV data keeps loading: an event log with pre-ISSUE-6 JSON
    entries still reads after this build appends msgpack entries."""
    kv = MemoryKV()
    js = JobStore(kv)
    legacy = {"ts_us": 1, "state": "PENDING", "prev": "", "event": "submit"}
    await kv.rpush(events_key("old-job"), json.dumps(legacy).encode())
    await js.append_event("old-job", "redelivered", attempt=2)
    ev = await js.events("old-job")
    assert ev[0] == legacy
    assert ev[1]["event"] == "redelivered" and ev[1]["attempt"] == 2


async def test_safety_decision_reads_legacy_json_record():
    kv = MemoryKV()
    js = JobStore(kv)
    rec = SafetyDecisionRecord(
        job_id="old-job", decision="ALLOW", policy_snapshot="h", decided_at_us=5
    )
    await kv.set("job:safety:old-job", json.dumps(rec.__dict__).encode())
    got = await js.get_safety_decision("old-job")
    assert got is not None and got.decision == "ALLOW" and got.decided_at_us == 5
    # and the msgpack write path round-trips through the same reader
    await js.put_safety_decision(
        SafetyDecisionRecord(job_id="new-job", decision="DENY", policy_snapshot="h2")
    )
    got2 = await js.get_safety_decision("new-job")
    assert got2 is not None and got2.decision == "DENY"


# ---------------------------------------------------------------------------
# CI perf floor checker (tools/check_bench_floor.py + bench_floor.json)
# ---------------------------------------------------------------------------


def _floor_mod():
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_bench_floor
    finally:
        sys.path.pop(0)
    return check_bench_floor


_HEALTHY_STORM = {
    "storm_interactive_p99_ms": 900.0, "storm_interactive_shed_rate": 0.0,
    "storm_batch_goodput": 35.0, "storm_control_vs_admitted_p99": 5.0,
}

# disaggregated serving keys (ISSUE 14): migrations happened, the
# steady-state decode-worker stream p99 held, prefill rate attributable
_HEALTHY_DISAGG = {
    "prefill_tokens_per_sec": 850.0, "disagg_migrations_done": 9,
    "disagg_inter_token_p99_ms": 23.0,
}

# gang scheduling keys (ISSUE 15): the control-plane gang pipeline ran,
# the three MULTICHIP flows completed, and the all-or-nothing invariant
# counter stayed at exactly zero
_HEALTHY_GANG = {
    "gang_jobs_per_sec": 4.0, "gang_flows_ok": 1.0,
    "gang_partial_reservations": 0.0,
}

# the agent-loop storm: multi-turn DAG runs rode session affinity end-to-end
# (hit rate 1.0, zero re-prefills) with context embeds batched on the pool
_HEALTHY_AGENTS = {
    "agents_workflow_steps_per_sec": 170.0, "agents_affinity_hit_rate": 1.0,
    "agents_context_embeds_per_sec": 80.0,
    "agents_reprefills": 0.0, "agents_step_p99_ms": 20.0,
}

# prefix cache + session tiering (ISSUE 18): the hit pass beat the cold
# pass token-identically, hibernation held residency above the device
# arena, and the cold->warm restore actually ran (fast)
_HEALTHY_CHAT = {
    "chat_prefix_ttft_speedup": 2.4, "chat_token_identical": 1,
    "chat_prefix_hit_rate": 0.857, "chat_resident_over_capacity": 1.6,
    "chat_restored_pages": 8, "chat_restore_pause_p50_ms": 1.0,
}

_HEALTHY_SPEC = {
    "spec_decode_speedup": 1.96, "spec_token_identity": 1,
    "spec_compile_count": 1,
}

# sharded serving gang (TP=2 over the in-process gang group): identity is
# binary, the compile ceiling is exactly one program per rank, and the
# speedup floor is a collapse guard only (both ranks time-share the core
# on 1-2 core CI hosts — see the bench_floor.json commentary)
_HEALTHY_TP = {
    "tp_token_identity": 1, "tp_speedup": 0.51,
    "tp_tokens_per_sec": 15.5, "tp_compile_per_rank": 1,
}


def test_floor_checker_passes_healthy_doc():
    mod = _floor_mod()
    doc = {"value": 2600.0, "selections_per_sec": 90000.0,
           "kv_roundtrips_per_job": 3.0, "statebus_kv_roundtrips_per_job": 8.0,
           "statebus_pipeline_speedup": 1.9,
           "sharded_jobs_per_sec": 300.0, "sharded_single_jobs_per_sec": 320.0,
           "serving_speedup": 4.5, "serving_affinity_hit_rate": 1.0,
           "decode_tokens_per_sec": 2900.0, "serving_compile_count": 1,
           "inter_token_p99_ms": 4.0, "migration_pause_p50_ms": 10.0,
           "statebus_replication_overhead_pct": 8.0,
           "fleet_snapshot_ok": 1.0, "telemetry_overhead_pct": 0.5,
           "capacity_matrix_ok": 1.0, "profiling_overhead_pct": 0.4,
           **_HEALTHY_STORM, **_HEALTHY_DISAGG, **_HEALTHY_GANG,
           **_HEALTHY_AGENTS, **_HEALTHY_CHAT, **_HEALTHY_SPEC,
           **_HEALTHY_TP}
    floors = json.loads((REPO / "bench_floor.json").read_text())
    assert mod.check(doc, floors) == []


def test_floor_checker_fails_regressed_metric(tmp_path):
    """The gate actually gates: a metric below its floor exits 1 (the
    deliberately-regressed-value demonstration from the acceptance bar)."""
    mod = _floor_mod()
    floors = json.loads((REPO / "bench_floor.json").read_text())
    doc = {"value": 100.0, "selections_per_sec": 90000.0,
           "kv_roundtrips_per_job": 3.0, "statebus_kv_roundtrips_per_job": 8.0,
           "statebus_pipeline_speedup": 1.9,
           "sharded_jobs_per_sec": 300.0, "sharded_single_jobs_per_sec": 320.0,
           "serving_speedup": 4.5, "serving_affinity_hit_rate": 1.0,
           "decode_tokens_per_sec": 2900.0, "serving_compile_count": 1,
           "inter_token_p99_ms": 4.0, "migration_pause_p50_ms": 10.0,
           "statebus_replication_overhead_pct": 8.0,
           "fleet_snapshot_ok": 1.0, "telemetry_overhead_pct": 0.5,
           "capacity_matrix_ok": 1.0, "profiling_overhead_pct": 0.4,
           **_HEALTHY_STORM, **_HEALTHY_DISAGG, **_HEALTHY_GANG,
           **_HEALTHY_AGENTS, **_HEALTHY_CHAT, **_HEALTHY_SPEC,
           **_HEALTHY_TP}
    violations = mod.check(doc, floors)
    assert violations and "value" in violations[0]
    # ceilings guard the other direction (round-trip budget regression)
    doc["value"] = 2600.0
    doc["kv_roundtrips_per_job"] = 49.0
    assert any("kv_roundtrips_per_job" in v for v in mod.check(doc, floors))
    # ... and the bucket-recompile cliff coming back is a gated failure
    doc["kv_roundtrips_per_job"] = 3.0
    doc["serving_compile_count"] = 6  # the old bucketed backend's count
    assert any("serving_compile_count" in v for v in mod.check(doc, floors))
    doc["serving_compile_count"] = 1
    # storm overload gates (ISSUE 13): interactive collapse, interactive
    # shed creep, shed-everything batch starvation, and a controller that
    # stopped doing anything (control run no longer degrades) all fail
    doc["storm_interactive_p99_ms"] = 9000.0
    assert any("storm_interactive_p99_ms" in v for v in mod.check(doc, floors))
    doc["storm_interactive_p99_ms"] = 900.0
    doc["storm_interactive_shed_rate"] = 0.2
    assert any("storm_interactive_shed_rate" in v for v in mod.check(doc, floors))
    doc["storm_interactive_shed_rate"] = 0.0
    doc["storm_batch_goodput"] = 0.0
    assert any("storm_batch_goodput" in v for v in mod.check(doc, floors))
    doc["storm_batch_goodput"] = 35.0
    doc["storm_control_vs_admitted_p99"] = 1.0
    assert any("storm_control_vs_admitted_p99" in v
               for v in mod.check(doc, floors))
    doc["storm_control_vs_admitted_p99"] = 5.0
    # disaggregation gates (ISSUE 14): a hand-off policy that stopped
    # migrating, a decode-worker stream-tail collapse, and a vanished
    # prefill/decode capacity split all fail
    doc["disagg_migrations_done"] = 0
    assert any("disagg_migrations_done" in v for v in mod.check(doc, floors))
    doc["disagg_migrations_done"] = 9
    doc["disagg_inter_token_p99_ms"] = 900.0
    assert any("disagg_inter_token_p99_ms" in v
               for v in mod.check(doc, floors))
    doc["disagg_inter_token_p99_ms"] = 23.0
    doc["prefill_tokens_per_sec"] = 0.0
    assert any("prefill_tokens_per_sec" in v for v in mod.check(doc, floors))
    doc["prefill_tokens_per_sec"] = 850.0
    # prefix-cache + tiering gates (ISSUE 18): a vanished TTFT win, a
    # token-divergent hit pass, residency collapsing back to device HBM,
    # and a restore-pause blowup all fail
    doc["chat_prefix_ttft_speedup"] = 1.0
    assert any("chat_prefix_ttft_speedup" in v for v in mod.check(doc, floors))
    doc["chat_prefix_ttft_speedup"] = 2.4
    doc["chat_token_identical"] = 0
    assert any("chat_token_identical" in v for v in mod.check(doc, floors))
    doc["chat_token_identical"] = 1
    doc["chat_resident_over_capacity"] = 1.0
    assert any("chat_resident_over_capacity" in v
               for v in mod.check(doc, floors))
    doc["chat_resident_over_capacity"] = 1.6
    doc["chat_restore_pause_p50_ms"] = 900.0
    assert any("chat_restore_pause_p50_ms" in v for v in mod.check(doc, floors))
    doc["chat_restore_pause_p50_ms"] = 1.0
    # end-to-end: main() exits nonzero on a regressed artifact
    bench_json = tmp_path / "bench.json"
    doc["value"] = 100.0
    bench_json.write_text("warmup noise\n" + json.dumps(doc) + "\n")
    assert mod.main([str(bench_json), str(REPO / "bench_floor.json")]) == 1


def test_floor_checker_flags_missing_metric():
    mod = _floor_mod()
    assert mod.check({}, {"floors": {"value": 1.0}}) != []


# ---------------------------------------------------------------------------
# bench backend-probe watchdog (satellite: regression test, not just CI grep)
# ---------------------------------------------------------------------------


def test_tpu_bench_child_fails_without_a_chip():
    """On a host with no chip the tpu bench child exits non-zero with a
    one-line reason and prints no result: a CPU timing must never stand in
    for a device metric."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # discover the backend as on a bare host
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--jax-child", "tpu"],
        capture_output=True, text=True, timeout=240, cwd=str(REPO), env=env,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout[-500:]
    reason = [ln for ln in proc.stderr.splitlines() if ln.startswith("bench --jax-child tpu:")]
    assert len(reason) == 1 and "no chip" in reason[0], proc.stderr[-2000:]


@pytest.mark.slow
def test_bench_jax_smoke_output_has_no_error_keys():
    """Full bench_jax(smoke=True) merge logic on a CPU host: the output
    dict must carry metrics, not embed_error/model_error keys."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    results = bench.bench_jax(smoke=True)
    assert "embed_error" not in results and "model_error" not in results, results
    assert results.get("embeds_per_sec", 0) > 0, results
