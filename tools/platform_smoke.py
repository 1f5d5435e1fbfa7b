#!/usr/bin/env python
"""Platform smoke: the end-to-end acceptance flow against the REAL
multi-process stack (reference ``tools/scripts/platform_smoke.sh`` +
``demo_guardrails.sh``).

Spawns statebus, safety kernel, scheduler, workflow engine, gateway, and a
TPU worker as separate OS processes, then over plain HTTP:

  1. workflow create → run → succeeded (hello echo through the worker)
  2. install demo-guardrails pack (admin)
  3. destructive job → DENIED (+ DLQ entry + remediation available)
  4. full-slice (chips:8) job → APPROVAL_REQUIRED → approve → dispatched
  5. flight recorder: traced job → span waterfall (≥5 spans, ≥4 services),
     cordum_stage_seconds in /metrics, `cordum trace` CLI render
  6. approval-only workflow → approve step → run succeeded
  7. micro-batching: bulk fan-out of ≥32 embed jobs through
     POST /api/v1/jobs:batch coalesces on the worker — at least one flushed
     batch of size ≥8, asserted via the batch span attributes
  8. fleet telemetry: /api/v1/fleet health beacons for every process,
     fleet counters == beacon sums, SLO burn rate, `cordumctl top`
  9. capacity observatory: /api/v1/capacity has a fresh non-zero row for
     every op the run executed, the fleet exposition carries an e2e
     exemplar resolving to a stored trace, and `cordumctl capacity` +
     `cordum traces blame` render
 10. ragged serving: llm.generate sessions with different prompt lengths
     decode through the worker's single ragged mixed prefill+decode entry
     point — `cordum_serving_compile_total{entry="ragged"}` reports
     exactly 1 compiled program, and the capacity matrix's llm.generate
     row carries the warmup compile in its compile split so the
     steady-state tokens/s excludes it
 11. serving drain/failover: a second worker joins, live sessions are
     submitted to the first, and POST /workers/smoke-w1/drain drains it —
     every session completes SUCCEEDED with its full token count (zero
     CANCELLED/FAILED), at least one finishes on the peer (live migration
     or requeue failover), the drained worker beacons draining and exits,
     and the fleet keeps serving afterwards
 12. agentic workflow serving: a 3-turn agent loop over one session —
     each turn a generate → context.update/context.window → generate DAG
     with `cordum.session_key` on the run — keeps every llm.generate of
     the session on ONE worker (scheduler affinity hits observed in the
     fleet exposition), runs its context embeds as real pool jobs, rides
     the INTERACTIVE SLO class, and renders each run as one ≥3-stage
     trace under the run root span; `cordumctl runs` renders the table
 13. prefix cache + session tiering: two llm.generate sessions sharing a
     long system prompt — the second admission maps the cached full pages
     (prefix-hit + skipped-token counters move, outputs identical to the
     first session's); then an idle conversation hibernates to the
     host-RAM cold arena (WORKER_SERVING_HIBERNATE_AFTER=2 on smoke-w2)
     and its next turn restores the cold pages (hibernated/restored
     counters + the restore-pause histogram move) with the full token
     count served exactly once

Exit 0 = PASS.  Usage: python tools/platform_smoke.py [--keep]
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import httpx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATEBUS_PORT = int(os.environ.get("SMOKE_STATEBUS_PORT", "7421"))
KERNEL_PORT = int(os.environ.get("SMOKE_KERNEL_PORT", "7431"))
GATEWAY_PORT = int(os.environ.get("SMOKE_GATEWAY_PORT", "8082"))
API = f"http://127.0.0.1:{GATEWAY_PORT}"
H_USER = {"X-Api-Key": "smoke-key"}
# X-Principal-Role covers dev open mode (no keys configured); with keys the
# admin key itself grants the role and the header cannot escalate others
H_ADMIN = {"X-Api-Key": "smoke-admin", "X-Principal-Id": "smoke-admin",
           "X-Principal-Role": "admin"}


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def spawn_stack(
    logdir: str,
    *,
    statebus_port: int = STATEBUS_PORT,
    kernel_port: int = KERNEL_PORT,
    gateway_port: int = GATEWAY_PORT,
    force_cpu: bool = True,
    pool_stanza: str = "",
    worker_env: dict | None = None,
) -> list[subprocess.Popen]:
    """Start the service binaries as child processes; the worker is the
    LAST entry of the returned list.  ``statebus_port`` needs the next port
    free too (two keyspace partitions).  With ``force_cpu=False`` the one
    worker takes whatever platform JAX finds (``chip_smoke.py`` runs it on
    the chip); ``pool_stanza`` is extra YAML for the ``tpu`` pool and
    ``worker_env`` overrides the worker's environment."""
    base_env = dict(os.environ)
    base_env.update({
        # sharded control plane: 2 statebus keyspace partitions (one process,
        # consecutive ports) × 2 scheduler shards — the ISSUE 5 smoke topology
        "CORDUM_STATEBUS_URL": (
            f"statebus://127.0.0.1:{statebus_port},"
            f"statebus://127.0.0.1:{statebus_port + 1}"
        ),
        "CORDUM_SCHEDULER_SHARDS": "2",
        "PYTHONPATH": REPO + os.pathsep + base_env.get("PYTHONPATH", ""),
        # hermetic placement: don't let the harness's own CPU burn flip
        # workers to overloaded (the smoke asserts exact worker identities)
        "CORDUM_HOST_LOAD": "0",
    })
    if force_cpu:
        base_env.update({"CORDUM_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu"})
    sched_env = {
        "SAFETY_KERNEL_ADDR": f"http://127.0.0.1:{kernel_port}",
        "POOL_CONFIG_PATH": os.path.join(logdir, "pools.yaml"),
        "TIMEOUT_CONFIG_PATH": os.path.join(logdir, "timeouts.yaml"),
        "SCHEDULER_SHARD_COUNT": "2",
    }
    services = [
        ("statebus", "cordum_tpu.cmd.statebus",
         {"STATEBUS_PORT": str(statebus_port),
          "STATEBUS_PARTITIONS": "2",
          "STATEBUS_AOF": os.path.join(logdir, "state.aof")}),
        ("kernel", "cordum_tpu.cmd.safety_kernel",
         {"SAFETY_KERNEL_PORT": str(kernel_port),
          "SAFETY_POLICY_PATH": os.path.join(logdir, "safety.yaml")}),
        ("scheduler-0", "cordum_tpu.cmd.scheduler",
         {**sched_env, "SCHEDULER_SHARD_INDEX": "0"}),
        ("scheduler-1", "cordum_tpu.cmd.scheduler",
         {**sched_env, "SCHEDULER_SHARD_INDEX": "1"}),
        ("wfengine", "cordum_tpu.cmd.workflow_engine", {}),
        ("gateway", "cordum_tpu.cmd.gateway",
         {"GATEWAY_HTTP_ADDR": f"127.0.0.1:{gateway_port}",
          "CORDUM_API_KEYS": "smoke-key",
          "CORDUM_ADMIN_KEYS": "smoke-admin",
          # the gateway reads the slo: stanza for the fleet SLO tracker
          "POOL_CONFIG_PATH": os.path.join(logdir, "pools.yaml"),
          "SAFETY_POLICY_PATH": os.path.join(logdir, "safety.yaml")}),
        ("worker", "cordum_tpu.cmd.worker",
         {"WORKER_ID": "smoke-w1", "WORKER_POOL": "tpu",
          "WORKER_TOPICS": "job.tpu.>,job.default,job.hello-pack.echo",
          "WORKER_CAPABILITIES": "tpu,echo",
          "WORKER_HEARTBEAT_INTERVAL": "1",
          # wide micro-batch window: the smoke fan-out arrives spread over
          # the dispatch pipeline's per-job latency, and step 7 asserts a
          # flushed batch of >= 8 (docs/BATCHING.md tuning knobs)
          "WORKER_MAX_BATCH_SIZE": "32",
          "WORKER_BATCH_WAIT_MS": "900",
          **(worker_env or {})}),
    ]
    # config files used by scheduler + kernel
    with open(os.path.join(logdir, "pools.yaml"), "w") as f:
        f.write(
            "topics:\n  job.default: tpu\n  job.hello-pack.echo: tpu\n  job.tpu.>: tpu\n"
            "pools:\n  tpu:\n    requires: []\n" + pool_stanza +
            # SLO objective for the fleet telemetry step: every smoke job
            # submits at the default BATCH class
            "slo:\n  batch:\n    job_class: BATCH\n    latency_ms: 5000\n"
            "    latency_target: 0.95\n"
        )
    with open(os.path.join(logdir, "timeouts.yaml"), "w") as f:
        f.write("reconciler:\n  dispatch_timeout_seconds: 60\n"
                "  running_timeout_seconds: 120\n  scan_interval_seconds: 2\n"
                "  pending_replay_seconds: 4\n")
    with open(os.path.join(logdir, "safety.yaml"), "w") as f:
        f.write("default_tenant: default\ntenants:\n  default:\n"
                "    allow_topics: [\"job.*\", \"job.>\"]\nrules: []\n")
    procs = []
    for name, module, extra in services:
        env = dict(base_env)
        env.update(extra)
        logf = open(os.path.join(logdir, f"{name}.log"), "ab")
        p = subprocess.Popen([sys.executable, "-m", module], env=env,
                             stdout=logf, stderr=logf, cwd=REPO)
        procs.append(p)
        log(f"started {name} (pid {p.pid})")
        if name == "statebus":
            time.sleep(0.8)
    return procs


def wait_http(url: str, timeout_s: float = 60.0) -> None:
    t0 = time.time()
    while time.time() - t0 < timeout_s:
        try:
            r = httpx.get(url, timeout=2.0)
            if r.status_code < 500:
                return
        except Exception:
            pass
        time.sleep(0.5)
    raise RuntimeError(f"timed out waiting for {url}")


def wait_job(c: httpx.Client, job_id: str, want: str, timeout_s: float = 60.0) -> dict:
    t0 = time.time()
    doc = {}
    while time.time() - t0 < timeout_s:
        # a transient gateway stall (1-core host: migration/compile churn
        # starves the event loop) must not kill the whole smoke — the
        # deadline above still bounds the wait
        try:
            doc = c.get(f"/api/v1/jobs/{job_id}?result=true").json()
        except httpx.TransportError:
            time.sleep(1.0)
            continue
        if doc.get("state") == want:
            return doc
        if doc.get("state") in ("FAILED", "DENIED", "TIMEOUT", "CANCELLED") and doc.get("state") != want:
            raise RuntimeError(f"job {job_id} reached {doc.get('state')}, wanted {want}: {doc}")
        time.sleep(0.4)
    raise RuntimeError(f"job {job_id} stuck (last: {doc.get('state')}), wanted {want}")


def wait_run(c: httpx.Client, run_id: str, want: str, timeout_s: float = 90.0) -> dict:
    t0 = time.time()
    doc = {}
    while time.time() - t0 < timeout_s:
        try:
            doc = c.get(f"/api/v1/runs/{run_id}").json()
        except httpx.TransportError:  # transient gateway stall; see wait_job
            time.sleep(1.0)
            continue
        if doc.get("status") == want:
            return doc
        if doc.get("status") in ("FAILED", "CANCELLED") and doc.get("status") != want:
            raise RuntimeError(f"run {run_id} reached {doc['status']}, wanted {want}: {doc.get('error')}")
        time.sleep(0.4)
    raise RuntimeError(f"run {run_id} stuck at {doc.get('status')}, wanted {want}")


def main() -> int:
    keep = "--keep" in sys.argv
    # SMOKE_BASE / BASE: target an already-running deployment (compose, k8s)
    # instead of spawning the process stack — the deploy-parity mode used by
    # docs/DEPLOY.md. Key overrides: SMOKE_API_KEY / SMOKE_ADMIN_KEY.
    global API
    external = os.environ.get("SMOKE_BASE") or os.environ.get("BASE")
    if external:
        API = external.rstrip("/")
        H_USER["X-Api-Key"] = os.environ.get("SMOKE_API_KEY", H_USER["X-Api-Key"])
        H_ADMIN["X-Api-Key"] = os.environ.get("SMOKE_ADMIN_KEY", H_ADMIN["X-Api-Key"])
        procs, logdir = [], "(external)"
        log(f"targeting external deployment {API}")
    else:
        logdir = tempfile.mkdtemp(prefix="cordum-smoke-")
        log(f"logs: {logdir}")
        procs = spawn_stack(logdir)
    try:
        wait_http(f"{API}/healthz")
        log("gateway is up")
        with httpx.Client(base_url=API, headers=H_USER, timeout=30.0) as c, \
             httpx.Client(base_url=API, headers=H_ADMIN, timeout=30.0) as admin:
            # worker registered?
            want_worker = "smoke-w1" if not external else ""
            t0 = time.time()
            workers = {}
            while time.time() - t0 < 60:
                workers = c.get("/api/v1/workers").json().get("workers", {})
                if (want_worker in workers) if want_worker else workers:
                    break
                time.sleep(0.5)
            if want_worker:
                assert want_worker in workers, f"worker never registered: {workers}"
            else:
                assert workers, "no workers heartbeating in external deployment"
            log("worker registered with heartbeats")

            # 1. hello workflow end-to-end through the real worker
            wf = {"id": "smoke-hello", "name": "hello",
                  "steps": {"echo": {"topic": "job.hello-pack.echo",
                                     "input": {"op": "echo", "message": "hi ${input.name}"}}}}
            r = c.post("/api/v1/workflows", json=wf)
            assert r.status_code == 201, r.text
            r = c.post("/api/v1/workflows/smoke-hello/runs", json={"input": {"name": "smoke"}})
            run_id = r.json()["run_id"]
            doc = wait_run(c, run_id, "SUCCEEDED")
            echoed = doc["context"]["steps"]["echo"]
            assert "hi smoke" in json.dumps(echoed), echoed
            log(f"1. hello workflow SUCCEEDED (run {run_id[:8]})")

            # 2. install demo-guardrails
            sys.path.insert(0, REPO)
            from cordum_tpu.packs import load_pack_dir

            m = load_pack_dir(os.path.join(REPO, "examples/demo-guardrails"))
            doc = {"id": m.id, "version": m.version,
                   "resources": {"workflows": m.workflows, "schemas": m.schemas},
                   "overlays": {"config": m.config_overlays, "policy": m.policy_overlays},
                   "simulations": m.simulations}
            r = admin.post("/api/v1/packs", json=doc)
            assert r.status_code == 201, r.text
            log("2. demo-guardrails pack installed (simulations passed)")

            # 3. destructive job denied (kernel hot-reloads fragments ≤2s)
            deadline = time.time() + 30
            while True:
                r = c.post("/api/v1/jobs", json={
                    "topic": "job.default", "payload": {"op": "echo"},
                    "metadata": {"risk_tags": ["destructive"]}})
                jid = r.json()["job_id"]
                time.sleep(1.0)
                state = c.get(f"/api/v1/jobs/{jid}").json().get("state")
                if state == "DENIED":
                    break
                if time.time() > deadline:
                    raise RuntimeError(f"destructive job not denied (state={state})")
                time.sleep(1.0)
            dlq = c.get("/api/v1/dlq").json()
            assert any(e["job_id"] == jid for e in dlq["entries"]), dlq
            log("3. destructive job DENIED + dead-lettered")

            # 4. full-slice job → approval → approve → dispatched
            r = c.post("/api/v1/jobs", json={
                "topic": "job.tpu.ops", "payload": {"op": "echo"},
                "metadata": {"capability": "tpu", "requires": ["tpu", "chips:8"]}})
            jid = r.json()["job_id"]
            t0 = time.time()
            while time.time() - t0 < 30:
                state = c.get(f"/api/v1/jobs/{jid}").json().get("state")
                if state == "APPROVAL_REQUIRED":
                    break
                time.sleep(0.4)
            assert state == "APPROVAL_REQUIRED", state
            approvals = c.get("/api/v1/approvals").json()["approvals"]
            assert any(a["job_id"] == jid for a in approvals)
            r = admin.post(f"/api/v1/approvals/{jid}/approve")
            assert r.status_code == 200, r.text
            doc = wait_job(c, jid, "SUCCEEDED")
            log("4. full-slice job approved and executed "
                f"(worker={doc.get('worker_id')})")

            # 5. flight recorder: an end-to-end job yields a queryable span
            # waterfall across >=4 services, stage histograms hit /metrics,
            # and the CLI renders it
            r = c.post("/api/v1/jobs", json={
                "topic": "job.default", "payload": {"op": "echo", "message": "traced"}})
            jid, trace_id = r.json()["job_id"], r.json()["trace_id"]
            wait_job(c, jid, "SUCCEEDED")
            trace = {}
            t0 = time.time()
            while time.time() - t0 < 30:
                trace = c.get(f"/api/v1/traces/{trace_id}").json()
                if trace.get("span_count", 0) >= 5 and len(trace.get("services") or []) >= 4:
                    break
                time.sleep(0.5)
            assert trace.get("span_count", 0) >= 5, trace
            services = set(trace.get("services") or [])
            assert {"gateway", "scheduler", "safety-kernel", "worker"} <= services, services
            assert trace.get("critical_path"), trace
            metrics_text = httpx.get(f"{API}/metrics", timeout=10.0).text
            stage_counts = [
                ln for ln in metrics_text.splitlines()
                if ln.startswith("cordum_stage_seconds_count") and not ln.rstrip().endswith(" 0")
            ]
            assert stage_counts, "no non-zero cordum_stage_seconds in /metrics"
            # retention caps must not have silently truncated any trace
            # (cordum_spans_dropped_total stays 0 through the whole run)
            dropped = [
                ln for ln in metrics_text.splitlines()
                if ln.startswith("cordum_spans_dropped_total")
                and not ln.rstrip().endswith(" 0") and not ln.rstrip().endswith(" 0.0")
            ]
            assert not dropped, f"spans dropped during smoke: {dropped}"
            cli = subprocess.run(
                [sys.executable, "-m", "cordum_tpu.cli", "trace", trace_id],
                capture_output=True, text=True, timeout=30, cwd=REPO,
                env={**os.environ, "CORDUM_API_URL": API,
                     "CORDUM_API_KEY": H_USER["X-Api-Key"],
                     "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")},
            )
            assert cli.returncode == 0 and f"trace {trace_id}" in cli.stdout, cli.stderr
            log(f"5. trace {trace_id[:8]} has {trace['span_count']} spans over "
                f"{len(services)} services; stage histograms live; CLI waterfall OK")

            # 6. approval workflow (guarded-inference from the pack)
            r = c.post("/api/v1/workflows/guarded-inference/runs",
                       json={"input": {"tokens": [[1, 2, 3]]}})
            run_id = r.json()["run_id"]
            t0 = time.time()
            while time.time() - t0 < 30:
                st = c.get(f"/api/v1/runs/{run_id}").json()["status"]
                if st == "WAITING_APPROVAL":
                    break
                time.sleep(0.4)
            assert st == "WAITING_APPROVAL", st
            r = admin.post(f"/api/v1/runs/{run_id}/steps/gate/approve", json={"approve": True})
            assert r.status_code == 200, r.text
            wait_run(c, run_id, "SUCCEEDED")
            log("6. guarded-inference run approved → SUCCEEDED")

            # 7. micro-batching: a bulk fan-out of 32 single-text embed jobs
            # must coalesce on the worker — at least one flushed batch of
            # size >= 8, proven by the batch attributes the flush writes
            # onto the execute spans
            n_fan = 32
            r = c.post("/api/v1/jobs:batch", json={"jobs": [
                {"topic": "job.tpu.ops",
                 "payload": {"op": "embed",
                             "texts": [f"microbatch smoke document {i}"]}}
                for i in range(n_fan)]})
            assert r.status_code == 202, r.text
            docs = r.json()["jobs"]
            assert len(docs) == n_fan and all(d.get("job_id") for d in docs), docs
            for d in docs:
                wait_job(c, d["job_id"], "SUCCEEDED")
            best = 0
            t0 = time.time()
            while time.time() - t0 < 30 and best < 8:
                best = 0
                for d in docs:
                    trace = c.get(f"/api/v1/traces/{d['trace_id']}").json()
                    for sp in trace.get("spans") or []:
                        size = (sp.get("attrs") or {}).get("batch_size", "")
                        if size.isdigit():
                            best = max(best, int(size))
                if best < 8:
                    time.sleep(0.5)
            assert best >= 8, f"largest flushed batch was {best}, wanted >= 8"
            log(f"7. bulk fan-out of {n_fan} embed jobs coalesced "
                f"(largest flushed batch {best})")

            # 8. fleet telemetry plane: /api/v1/fleet must show every
            # process's health beacon (gateway, 2 scheduler shards, statebus
            # partitions, worker, kernel, wf-engine), a fleet-wide scheduled
            # counter matching the per-shard beacon sum, a non-zero job rate
            # over the run, and an SLO burn rate for the configured class —
            # and `cordumctl top` must render it
            want_services = {"gateway", "scheduler", "statebus", "worker"}
            fleet = {}
            t0 = time.time()
            while time.time() - t0 < 45:
                fleet = c.get("/api/v1/fleet").json()
                healthy = {s["service"] for s in fleet.get("services", [])
                           if s.get("healthy")}
                if (want_services <= healthy
                        and fleet.get("healthy_services", 0) >= 4
                        and fleet["fleet"].get("jobs_dispatched_total", 0) > 0):
                    break
                time.sleep(1.0)
            healthy = {s["service"] for s in fleet["services"] if s["healthy"]}
            assert want_services <= healthy, f"missing beacons: {healthy}"
            assert fleet["healthy_services"] >= 4, fleet["counts"]
            shards = [s for s in fleet["services"]
                      if s["service"] == "scheduler" and s["healthy"]]
            assert len(shards) == 2, f"expected 2 scheduler shards: {shards}"
            assert {s.get("shard_index") for s in shards} == {0, 1}, shards
            parts = [s for s in fleet["services"]
                     if s["service"] == "statebus" and s["healthy"]]
            assert {p.get("partition") for p in parts} == {0, 1}, parts
            # fleet-wide scheduled counter == sum of the per-shard beacons
            beacon_sum = sum(s.get("jobs_scheduled", 0) for s in shards)
            assert fleet["fleet"]["jobs_dispatched_total"] == beacon_sum > 0, (
                fleet["fleet"], shards)
            # every earlier step ran jobs: the run-window rate is non-zero
            assert fleet["fleet"]["completed_5m"] > 0, fleet["fleet"]
            # the SLO tracker reports a burn rate for the configured class
            slo = {s["name"]: s for s in fleet.get("slo", [])}
            assert "batch" in slo, fleet.get("slo")
            w5 = slo["batch"]["windows"]["5m"]
            assert w5["total"] > 0 and w5["burn_rate"] >= 0.0, w5
            assert slo["batch"]["state"] in ("ok", "warn", "page"), slo
            assert fleet["fleet"]["spans_dropped_total"] == 0, fleet["fleet"]
            top = subprocess.run(
                [sys.executable, "-m", "cordum_tpu.cli", "top", "--once"],
                capture_output=True, text=True, timeout=30, cwd=REPO,
                env={**os.environ, "CORDUM_API_URL": API,
                     "CORDUM_API_KEY": H_USER["X-Api-Key"],
                     "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")},
            )
            assert top.returncode == 0, top.stderr
            for needle in ("scheduler", "statebus", "worker", "slo batch"):
                assert needle in top.stdout, (needle, top.stdout)
            log(f"8. fleet telemetry: {fleet['healthy_services']} healthy beacons "
                f"({sorted(healthy)}), fleet scheduled={beacon_sum}, slo "
                f"burn5m={w5['burn_rate']} ({slo['batch']['state']}); "
                "cordumctl top renders")

            # 9. capacity observatory: GET /api/v1/capacity must report a
            # fresh non-zero throughput row for every op this run executed
            # (echo via the workflow/approval jobs, embed via the batch
            # fan-out), the fleet exposition must carry the matrix gauges
            # plus an e2e exemplar that resolves to a stored trace, and the
            # critical-path blame surfaces must render
            import re

            want_ops = {"echo", "embed"}
            cap, fresh_ops = {}, set()
            t0 = time.time()
            while time.time() - t0 < 45:
                cap = c.get("/api/v1/capacity").json()
                fresh_ops = {r["op"] for r in cap.get("matrix", [])
                             if not r["stale"] and r["items_per_s"] > 0}
                if want_ops <= fresh_ops:
                    break
                time.sleep(1.0)
            assert want_ops <= fresh_ops, (
                f"capacity matrix missing fresh ops: {fresh_ops} from "
                f"{cap.get('matrix')}")
            ages = [r["age_s"] for r in cap["matrix"] if r["op"] in want_ops]
            assert ages and min(ages) < 30, f"stale capacity rows: {ages}"
            assert cap["workers"], cap
            assert all(cap["ops"].get(op, 0) > 0 for op in want_ops), cap["ops"]
            fleet_text = httpx.get(f"{API}/metrics?scope=fleet",
                                   timeout=10.0).text
            assert "cordum_capacity_items_per_sec" in fleet_text
            # the acceptance link: an e2e histogram exemplar's trace id must
            # resolve to a stored trace with spans
            m = re.search(
                r'cordum_job_e2e_seconds_bucket\{[^}]*\} [0-9.]+ '
                r'# \{trace_id="([^"]+)"\}', fleet_text)
            assert m, "no exemplar on cordum_job_e2e_seconds in fleet scope"
            ex_trace = c.get(f"/api/v1/traces/{m.group(1)}").json()
            assert ex_trace.get("span_count", 0) >= 1, ex_trace
            blame = c.get("/api/v1/traces/analysis").json()
            assert blame["traces"] > 0, blame
            assert "execute" in blame["stages"], blame["stages"]
            share_sum = sum(s["blame_share"] for s in blame["stages"].values())
            assert 0.98 <= share_sum <= 1.02, (share_sum, blame["stages"])
            for cmd, needles in (
                (["capacity"], ("echo", "embed", "items/s")),
                (["traces", "blame", "--last", "50"],
                 ("critical-path blame", "execute")),
            ):
                cp = subprocess.run(
                    [sys.executable, "-m", "cordum_tpu.cli", *cmd],
                    capture_output=True, text=True, timeout=30, cwd=REPO,
                    env={**os.environ, "CORDUM_API_URL": API,
                         "CORDUM_API_KEY": H_USER["X-Api-Key"],
                         "PYTHONPATH": REPO + os.pathsep
                         + os.environ.get("PYTHONPATH", "")},
                )
                assert cp.returncode == 0, (cmd, cp.stderr)
                for needle in needles:
                    assert needle in cp.stdout, (cmd, needle, cp.stdout)
            log(f"9. capacity observatory: fresh rows for {sorted(fresh_ops)}, "
                f"e2e exemplar {m.group(1)[:8]} resolves "
                f"({ex_trace['span_count']} spans), blame shares sum to "
                f"{share_sum:.3f}; cordumctl capacity + traces blame render")

            # 10. ragged serving: mixed-length llm.generate sessions through
            # the single ragged entry point — one compiled XLA program for
            # the whole mix (no prompt-length/batch buckets), and the
            # capacity matrix's steady-state decode rate excludes the
            # warmup compile via the compile split
            gen_docs = []
            for i, plen in enumerate((3, 7, 12)):  # different "buckets"
                r = c.post("/api/v1/jobs", json={
                    "topic": "job.tpu.generate",
                    "payload": {"op": "llm.generate",
                                "tokens": list(range(1, plen + 1)),
                                "max_new_tokens": 8,
                                "session_id": f"smoke-conv-{i}"}})
                assert r.status_code == 202, r.text
                gen_docs.append(r.json())
            results = [wait_job(c, d["job_id"], "SUCCEEDED") for d in gen_docs]
            for d in results:
                assert len(d["result"]["tokens"]) == 8, d["result"]
            # the whole mixed run compiled exactly ONE serving program
            compile_lines = {}
            srv_row = {}
            t0 = time.time()
            while time.time() - t0 < 45:
                fleet_text = httpx.get(f"{API}/metrics?scope=fleet",
                                       timeout=10.0).text
                compile_lines = {
                    ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
                    for ln in fleet_text.splitlines()
                    if ln.startswith("cordum_serving_compile_total{")
                }
                cap = c.get("/api/v1/capacity").json()
                srv_row = next((r for r in cap.get("matrix", [])
                                if r["op"] == "llm.generate"), {})
                if compile_lines and srv_row.get("tokens_per_s", 0) > 0:
                    break
                time.sleep(1.0)
            ragged = [v for k, v in compile_lines.items()
                      if 'entry="ragged"' in k]
            assert ragged == [1.0], (
                f"expected exactly one ragged compile: {compile_lines}")
            # the warmup compile rides the capacity compile split of
            # whichever phase row the first step served — the mixed step's
            # device time now splits into llm.prefill + llm.generate rows
            # (docs/SERVING.md §Disaggregation) — and the steady-state rate
            # the matrix reports excludes it either way
            pre_row = next((r for r in cap.get("matrix", [])
                            if r["op"] == "llm.prefill"), {})
            assert (srv_row.get("compile_n", 0)
                    + pre_row.get("compile_n", 0)) >= 1, (srv_row, pre_row)
            assert srv_row.get("n", 0) > srv_row.get("compile_n", 0), srv_row
            assert srv_row.get("tokens_per_s", 0) > 0, srv_row
            assert pre_row.get("tokens_per_s", 0) > 0, pre_row
            log(f"10. ragged serving: 3 mixed-length sessions decoded, "
                f"1 compiled program, capacity row steady tokens/s="
                f"{srv_row['tokens_per_s']} (compile_n={srv_row['compile_n']} "
                f"of n={srv_row['n']} excluded)")

            # 11. serving drain/failover: a second worker joins; live
            # sessions pinned to smoke-w1 are drained off it mid-decode —
            # live KV-page migration to the peer, with scheduler requeue
            # (failover) as the fallback for a dispatch that raced the
            # draining beacon.  Zero CANCELLED/FAILED sessions either way.
            if not external:
                w2_env = dict(os.environ)
                w2_env.update({
                    "CORDUM_STATEBUS_URL": (
                        f"statebus://127.0.0.1:{STATEBUS_PORT},"
                        f"statebus://127.0.0.1:{STATEBUS_PORT + 1}"),
                    "CORDUM_SCHEDULER_SHARDS": "2",
                    "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
                    "CORDUM_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
                    # hermetic like the boot-time workers: without this the
                    # only post-drain worker senses the harness's own CPU
                    # burn, reads overloaded (cpu_load>=90), and every
                    # affinity election silently fails onto topic fan-in —
                    # step 12's session-affinity hits become impossible
                    "CORDUM_HOST_LOAD": "0",
                    "WORKER_ID": "smoke-w2", "WORKER_POOL": "tpu",
                    "WORKER_TOPICS": "job.tpu.>,job.default,job.hello-pack.echo",
                    "WORKER_CAPABILITIES": "tpu,echo",
                    "WORKER_HEARTBEAT_INTERVAL": "1",
                    # step 13 rides this worker: idle conversations
                    # hibernate to the host cold arena after 2s
                    # (docs/SERVING.md §Prefix cache and tiering)
                    "WORKER_SERVING_HIBERNATE_AFTER": "2",
                })
                w2_log = open(os.path.join(logdir, "worker2.log"), "ab")
                w2 = subprocess.Popen(
                    [sys.executable, "-m", "cordum_tpu.cmd.worker"],
                    env=w2_env, stdout=w2_log, stderr=w2_log, cwd=REPO)
                procs.append(w2)
                t0 = time.time()
                while time.time() - t0 < 60:
                    if "smoke-w2" in c.get("/api/v1/workers").json().get("workers", {}):
                        break
                    time.sleep(0.5)
                assert "smoke-w2" in c.get("/api/v1/workers").json()["workers"]
                drain_docs = []
                for i in range(3):
                    r = c.post("/api/v1/jobs", json={
                        "topic": "job.tpu.generate",
                        "payload": {"op": "llm.generate",
                                    "tokens": list(range(2, 10)),
                                    "max_new_tokens": 48,
                                    "session_id": f"drain-conv-{i}"},
                        "labels": {"preferred_worker_id": "smoke-w1"}})
                    assert r.status_code == 202, r.text
                    drain_docs.append(r.json())
                # drain while the sessions are in flight
                r = admin.post("/api/v1/workers/smoke-w1/drain",
                               json={"reason": "smoke step 11"})
                assert r.status_code == 202, r.text
                finals = [wait_job(c, d["job_id"], "SUCCEEDED", 90)
                          for d in drain_docs]
                peer_finishes = 0
                for d, doc in zip(drain_docs, finals):
                    assert len(doc["result"]["tokens"]) == 48, doc["result"]
                    events = [e.get("event") for e in
                              c.get(f"/api/v1/jobs/{d['job_id']}?events=true")
                              .json().get("events", [])]
                    assert "cancelled" not in events, (d["job_id"], events)
                    if doc.get("worker_id") == "smoke-w2":
                        peer_finishes += 1
                assert peer_finishes >= 1, (
                    f"no session finished on the peer: {[f.get('worker_id') for f in finals]}")
                # the drained worker beacons draining (then deregisters) and
                # its process exits on its own
                t0 = time.time()
                w1_gone = False
                while time.time() - t0 < 60:
                    ws = c.get("/api/v1/workers").json().get("workers", {})
                    hb = ws.get("smoke-w1")
                    if hb is None or hb.get("draining"):
                        w1_gone = True
                        break
                    time.sleep(0.5)
                assert w1_gone, "smoke-w1 never beaconed draining"
                # the fleet keeps serving: a fresh session completes on w2
                r = c.post("/api/v1/jobs", json={
                    "topic": "job.tpu.generate",
                    "payload": {"op": "llm.generate", "tokens": [3, 1, 4],
                                "max_new_tokens": 8,
                                "session_id": "post-drain-conv"}})
                doc = wait_job(c, r.json()["job_id"], "SUCCEEDED", 60)
                assert doc.get("worker_id") == "smoke-w2", doc.get("worker_id")
                log(f"11. drain/failover: 3 sessions survived the drain "
                    f"({peer_finishes} finished on smoke-w2), zero CANCELLED, "
                    "post-drain traffic serves on the peer")
            else:
                log("11. drain/failover: skipped (external deployment)")

            # 12. agentic workflow serving (docs/WORKFLOWS.md): a 3-turn
            # agent loop on ONE session.  Every run carries the same
            # cordum.session_key, so the engine stamps session_id into each
            # llm.generate payload and the scheduler's affinity cache keeps
            # the whole session on one worker; context.update/window run
            # in-engine with their embeds riding the pool as real embed
            # jobs; the workflow's INTERACTIVE slo_class lands on the run
            # labels; each run renders as one trace under the run root span.
            def _affinity_hits(text: str) -> float:
                return sum(
                    float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                    if ln.startswith("cordum_session_affinity_total{")
                    and 'outcome="hit"' in ln)

            hits_before = _affinity_hits(
                httpx.get(f"{API}/metrics?scope=fleet", timeout=10.0).text)
            wf = {"id": "smoke-agent", "name": "agent loop",
                  "slo_class": "INTERACTIVE",
                  "steps": {
                      "plan": {"topic": "job.tpu.generate",
                               "input": {"op": "llm.generate",
                                         "tokens": [2, 7, 1],
                                         "max_new_tokens": 6}},
                      "remember": {"topic": "job.tpu.context",
                                   "depends_on": ["plan"],
                                   "input": {"op": "context.update",
                                             "user_payload": "${input.goal}",
                                             "model_response":
                                                 "plan ${steps.plan.tokens}",
                                             "chunks": [{
                                                 "file_path": "notes",
                                                 "content": "agent planned "
                                                            "${steps.plan.tokens}"}]}},
                      "window": {"topic": "job.tpu.context",
                                 "depends_on": ["remember"],
                                 "input": {"op": "context.window",
                                           "mode": "RAG",
                                           "query": "${input.goal}"}},
                      "act": {"topic": "job.tpu.generate",
                              "depends_on": ["window"],
                              "input": {"op": "llm.generate",
                                        "tokens": [4, 4, 8],
                                        "max_new_tokens": 6}},
                  }}
            r = c.post("/api/v1/workflows", json=wf)
            assert r.status_code == 201, r.text
            turn_workers = []
            last_run = {}
            for turn in range(3):
                r = c.post("/api/v1/workflows/smoke-agent/runs",
                           json={"input": {"goal": f"agent smoke turn {turn}"},
                                 "labels": {"cordum.session_key": "agent-smoke"}})
                assert r.status_code == 202, r.text
                run_id = r.json()["run_id"]
                last_run = wait_run(c, run_id, "SUCCEEDED")
                steps_ctx = last_run["context"]["steps"]
                # the RAG window saw the memory this (and earlier) turns wrote
                assert steps_ctx["window"]["message_count"] >= 1, steps_ctx["window"]
                assert len(steps_ctx["act"]["tokens"]) == 6, steps_ctx["act"]
                workers = {}
                for sid in ("plan", "act"):
                    jd = c.get(f"/api/v1/jobs/{run_id}:{sid}@1").json()
                    assert jd.get("state") == "SUCCEEDED", jd
                    workers[sid] = jd.get("worker_id", "")
                turn_workers.append(workers)
            assert last_run.get("labels", {}).get("cordum.slo_class") == "INTERACTIVE", \
                last_run.get("labels")
            if not external:
                # every llm.generate of the session stayed on the one live
                # worker — the no-re-prefill contract
                owners = {w for tw in turn_workers for w in tw.values()}
                assert owners == {"smoke-w2"}, f"session hopped workers: {turn_workers}"
                # and the affinity cache produced real hits (6 session jobs
                # over <=2 shards: some shard routed a repeat)
                hits_after, aff_lines = hits_before, []
                t0 = time.time()
                while time.time() - t0 < 30 and hits_after <= hits_before:
                    fleet_text = httpx.get(f"{API}/metrics?scope=fleet",
                                           timeout=10.0).text
                    hits_after = _affinity_hits(fleet_text)
                    aff_lines = [
                        ln for ln in fleet_text.splitlines()
                        if ln.startswith("cordum_session_affinity_total")]
                    if hits_after <= hits_before:
                        time.sleep(1.0)
                # failure triage: no lines at all = the serving placement
                # path never engaged (scheduler's capacity view had no
                # fresh prefill rate — beacon starvation under load);
                # new/miss lines without hit = no shard saw a repeat
                assert hits_after > hits_before, (
                    hits_before, hits_after, aff_lines)
            # one trace per run: the run root span plus >=3 distinct DAG
            # stages parented under it
            trace_id = last_run.get("trace_id", "")
            assert trace_id, last_run
            trace, stages, names = {}, set(), set()
            t0 = time.time()
            while time.time() - t0 < 30:
                trace = c.get(f"/api/v1/traces/{trace_id}").json()
                spans = trace.get("spans") or []
                stages = {(sp.get("attrs") or {}).get("step")
                          for sp in spans} - {None}
                names = {sp.get("name") for sp in spans}
                if len(stages) >= 3 and "workflow-run" in names:
                    break
                time.sleep(0.5)
            assert len(stages) >= 3, (stages, trace.get("span_count"))
            assert "workflow-run" in names, names
            runs_out = subprocess.run(
                [sys.executable, "-m", "cordum_tpu.cli", "runs",
                 "--workflow-id", "smoke-agent"],
                capture_output=True, text=True, timeout=30, cwd=REPO,
                env={**os.environ, "CORDUM_API_URL": API,
                     "CORDUM_API_KEY": H_USER["X-Api-Key"],
                     "PYTHONPATH": REPO + os.pathsep
                     + os.environ.get("PYTHONPATH", "")},
            )
            assert runs_out.returncode == 0, runs_out.stderr
            assert "smoke-agent" in runs_out.stdout, runs_out.stdout
            assert "INTERACTIVE" in runs_out.stdout, runs_out.stdout
            log(f"12. agent loop: 3 turns on one session, workers={turn_workers[-1]}, "
                f"window={last_run['context']['steps']['window']['message_count']} msgs, "
                f"trace stages={sorted(stages)}; cordumctl runs renders")

            # 13. prefix cache + session tiering (docs/SERVING.md §Prefix
            # cache and tiering): two sessions share a long system prompt —
            # the second admission maps the cached full pages and skips
            # their prefill (hit + skipped-token counters move, outputs
            # stay identical: sharing is a placement change, not a math
            # change).  Then an idle conversation hibernates to the
            # host-RAM cold arena (smoke-w2 runs with
            # WORKER_SERVING_HIBERNATE_AFTER=2) and its next turn restores
            # the cold pages — hibernated/restored counters and the
            # restore-pause histogram move, and the terminal result carries
            # the full token count exactly once.
            if not external:
                def _ctr(text: str, name: str, match: str = "") -> float:
                    return sum(
                        float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                        if ln.startswith(name) and match in ln)

                def _fleet() -> str:
                    return httpx.get(f"{API}/metrics?scope=fleet",
                                     timeout=10.0).text

                before = _fleet()
                hits0 = _ctr(before, "cordum_serving_prefix_total{",
                             'outcome="hit"')
                skip0 = _ctr(before, "cordum_serving_prefix_tokens_total")
                hib0 = _ctr(before, "cordum_serving_hibernate_total{",
                            'event="hibernated"')
                res0 = _ctr(before, "cordum_serving_hibernate_total{",
                            'event="restored"')
                pause0 = _ctr(before,
                              "cordum_serving_hibernate_pause_seconds_count")
                # 40 shared tokens = 2 cacheable full 16-slot pages
                system = [((7 * i) % 250) + 2 for i in range(40)]
                docs = []
                for sid in ("pfx-a", "pfx-b"):
                    r = c.post("/api/v1/jobs", json={
                        "topic": "job.tpu.generate",
                        "payload": {"op": "llm.generate", "tokens": system,
                                    "max_new_tokens": 8, "session_id": sid}})
                    assert r.status_code == 202, r.text
                    docs.append(wait_job(c, r.json()["job_id"],
                                         "SUCCEEDED", 60))
                assert docs[0]["result"]["tokens"] == docs[1]["result"]["tokens"], \
                    "prefix sharing changed the generated tokens"
                # the fleet scope is fed by 2s worker beacons — poll until
                # the hit/skipped counters propagate instead of racing them
                after, t0 = _fleet(), time.time()
                while time.time() - t0 < 20 and (
                        _ctr(after, "cordum_serving_prefix_total{",
                             'outcome="hit"') < hits0 + 1
                        or _ctr(after, "cordum_serving_prefix_tokens_total")
                        < skip0 + 32):
                    time.sleep(1.0)
                    after = _fleet()
                assert _ctr(after, "cordum_serving_prefix_total{",
                            'outcome="hit"') >= hits0 + 1, "no prefix hit"
                skipped = _ctr(after,
                               "cordum_serving_prefix_tokens_total") - skip0
                assert skipped >= 32, (
                    f"second session's prefill skipped only {skipped} of the "
                    "32 shared full-page tokens")
                # hibernate: one turn, go idle past the 2s threshold, then
                # the next turn restores the conversation's cold pages
                hib_p = [((13 * i) % 250) + 3 for i in range(20)]
                r = c.post("/api/v1/jobs", json={
                    "topic": "job.tpu.generate",
                    "payload": {"op": "llm.generate", "tokens": hib_p,
                                "max_new_tokens": 8,
                                "session_id": "hib-conv"}})
                turn1 = wait_job(c, r.json()["job_id"], "SUCCEEDED", 60)
                # other idle conversations (pfx-a/b, the agent loop) also
                # hibernate, so a bare counter bump can't prove hib-conv
                # went cold — and the fleet scope sums BOTH workers'
                # resident gauges (drained smoke-w1 never sweeps), so
                # "zero warm anywhere" is unreachable.  Instead wait for
                # the sweeps to QUIESCE: hib-conv's lone full page has
                # refcount 1 after its clean retire, so once the
                # hibernated counter has moved and then stayed flat for
                # 5 consecutive 1s polls (>> the 2s idle threshold +
                # 0.5s sweep interval), every demotable page — hib-conv's
                # included — is in the cold arena
                t0 = time.time()
                hibernated, cold, stable, prev = hib0, 0.0, 0, -1.0
                while time.time() - t0 < 60 and stable < 5:
                    time.sleep(1.0)
                    txt = _fleet()
                    hibernated = _ctr(txt, "cordum_serving_hibernate_total{",
                                      'event="hibernated"')
                    cold = _ctr(txt, "cordum_serving_resident_sessions{",
                                'tier="cold"')
                    stable = (stable + 1
                              if hibernated > hib0 and hibernated == prev
                              else 0)
                    prev = hibernated
                assert hibernated > hib0, "idle conversation never hibernated"
                assert stable >= 5, "hibernate sweep never quiesced"
                assert cold >= 1, f"no conversation went cold: cold={cold}"
                turn2_prompt = hib_p + turn1["result"]["tokens"] + [5]
                r = c.post("/api/v1/jobs", json={
                    "topic": "job.tpu.generate",
                    "payload": {"op": "llm.generate", "tokens": turn2_prompt,
                                "max_new_tokens": 8,
                                "session_id": "hib-conv"}})
                turn2 = wait_job(c, r.json()["job_id"], "SUCCEEDED", 60)
                # exactly-once: the terminal result is the full generation
                assert len(turn2["result"]["tokens"]) == 8, turn2["result"]
                final, t0 = _fleet(), time.time()
                while time.time() - t0 < 20 and (
                        _ctr(final, "cordum_serving_hibernate_total{",
                             'event="restored"') <= res0
                        or _ctr(final,
                                "cordum_serving_hibernate_pause_seconds_count")
                        <= pause0):
                    time.sleep(1.0)
                    final = _fleet()
                assert _ctr(final, "cordum_serving_hibernate_total{",
                            'event="restored"') > res0, "no cold-page restore"
                assert _ctr(final,
                            "cordum_serving_hibernate_pause_seconds_count") \
                    > pause0, "restore pause never observed"
                log(f"13. prefix+tiering: shared-prefix hit skipped "
                    f"{skipped:.0f} prompt tokens (outputs identical), "
                    f"idle conversation hibernated and restored on turn 2 "
                    f"({len(turn2['result']['tokens'])} tokens exactly once)")
            else:
                log("13. prefix+tiering: skipped (external deployment)")

            # 14. speculative decoding (docs/SERVING.md §Speculative
            # decoding): a templated (motif-heavy) llm.generate session on
            # the live stack engages the prompt-lookup drafter — non-zero
            # drafts verified and ACCEPTED through the ragged step — while
            # a control worker started with WORKER_SERVING_SPECULATIVE=0
            # generates the identical token sequence for the same prompt
            # (speculation is a schedule change, not a math change).  The
            # accept EWMA rides only the spec worker's occupancy beacon,
            # and no worker ever compiled a second ragged program: draft
            # verification rows are prefill-shaped, so they reuse the one
            # static-shape serving executable.
            if not external:
                def _spec_fleet() -> str:
                    return httpx.get(f"{API}/metrics?scope=fleet",
                                     timeout=10.0).text

                def _spec_ctr(text: str, name: str) -> float:
                    return sum(
                        float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                        if ln.startswith(name))

                before = _spec_fleet()
                drafted0 = _spec_ctr(before,
                                     "cordum_serving_spec_drafted_total")
                acc0 = _spec_ctr(before,
                                 "cordum_serving_spec_accepted_total")

                def _spec_ragged(text: str) -> float:
                    return sum(
                        float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                        if ln.startswith("cordum_serving_compile_total{")
                        and 'entry="ragged"' in ln)

                ragged0 = _spec_ragged(before)
                # the spec-disabled control worker: same model, same pool,
                # speculation forced off
                w3_env = dict(os.environ)
                w3_env.update({
                    "CORDUM_STATEBUS_URL": (
                        f"statebus://127.0.0.1:{STATEBUS_PORT},"
                        f"statebus://127.0.0.1:{STATEBUS_PORT + 1}"),
                    "CORDUM_SCHEDULER_SHARDS": "2",
                    "PYTHONPATH": REPO + os.pathsep
                    + os.environ.get("PYTHONPATH", ""),
                    "CORDUM_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
                    "CORDUM_HOST_LOAD": "0",
                    "WORKER_ID": "smoke-w3", "WORKER_POOL": "tpu",
                    "WORKER_TOPICS": "job.tpu.>,job.default",
                    "WORKER_CAPABILITIES": "tpu",
                    "WORKER_HEARTBEAT_INTERVAL": "1",
                    "WORKER_SERVING_SPECULATIVE": "0",
                })
                w3_log = open(os.path.join(logdir, "worker3.log"), "ab")
                w3 = subprocess.Popen(
                    [sys.executable, "-m", "cordum_tpu.cmd.worker"],
                    env=w3_env, stdout=w3_log, stderr=w3_log, cwd=REPO)
                procs.append(w3)
                t0 = time.time()
                while time.time() - t0 < 60:
                    if "smoke-w3" in c.get("/api/v1/workers").json().get(
                            "workers", {}):
                        break
                    time.sleep(0.5)
                assert "smoke-w3" in c.get("/api/v1/workers").json()["workers"]
                # templated prompt: a repeated motif the n-gram drafter can
                # look up (agent-loop prompts share this shape)
                motif = [5, 9, 14, 23, 7, 11, 3, 19]
                tpl = motif * 4 + [2]

                def _spec_gen(sid: str, wid: str) -> dict:
                    r = c.post("/api/v1/jobs", json={
                        "topic": "job.tpu.generate",
                        "payload": {"op": "llm.generate",
                                    "tokens": list(tpl),
                                    "max_new_tokens": 48,
                                    "session_id": sid},
                        "labels": {"preferred_worker_id": wid}})
                    assert r.status_code == 202, r.text
                    return wait_job(c, r.json()["job_id"], "SUCCEEDED", 90)

                spec_doc = _spec_gen("spec-conv", "smoke-w2")
                ctrl_doc = _spec_gen("spec-ctrl-conv", "smoke-w3")
                assert spec_doc.get("worker_id") == "smoke-w2", spec_doc
                assert ctrl_doc.get("worker_id") == "smoke-w3", ctrl_doc
                assert len(spec_doc["result"]["tokens"]) == 48, spec_doc
                assert spec_doc["result"]["tokens"] == \
                    ctrl_doc["result"]["tokens"], (
                        "speculation changed the generated tokens")
                # the spec worker verified and accepted real drafts
                after, t0 = _spec_fleet(), time.time()
                while time.time() - t0 < 30 and (
                        _spec_ctr(after, "cordum_serving_spec_accepted_total")
                        <= acc0):
                    time.sleep(1.0)
                    after = _spec_fleet()
                drafted = _spec_ctr(
                    after, "cordum_serving_spec_drafted_total") - drafted0
                accepted = _spec_ctr(
                    after, "cordum_serving_spec_accepted_total") - acc0
                assert drafted > 0, "no tokens were ever drafted"
                assert accepted > 0, "no drafted token was ever accepted"
                # the acceptance EWMA beacons from the spec worker only;
                # the control worker's occupancy never carries the key
                occ2, occ3, t0 = {}, {}, time.time()
                while time.time() - t0 < 30:
                    cap_workers = c.get("/api/v1/capacity").json().get(
                        "workers", {})
                    occ2 = (cap_workers.get("smoke-w2") or {}).get(
                        "occupancy") or {}
                    occ3 = (cap_workers.get("smoke-w3") or {}).get(
                        "occupancy") or {}
                    if "spec_accept_rate" in occ2 and occ3:
                        break
                    time.sleep(1.0)
                assert "spec_accept_rate" in occ2, occ2
                assert "spec_accept_rate" not in occ3, occ3
                # draft rows never grew the compile ladder: the fleet
                # counter sums one warmup compile per worker, so the spec
                # session on the already-warm smoke-w2 must add ZERO and
                # the fresh control worker exactly its one warmup
                ragged_added = _spec_ragged(after) - ragged0
                assert ragged_added == 1.0, (
                    f"draft rows recompiled the serving program: "
                    f"{ragged_added} new ragged compiles (expected only "
                    "the control worker's warmup)")
                log(f"14. speculative decoding: templated session accepted "
                    f"{accepted:.0f} of {drafted:.0f} drafted tokens on "
                    f"smoke-w2, tokens identical to the spec-disabled "
                    f"control (smoke-w3), accept EWMA beacons from the spec "
                    f"worker only, zero new ragged compiles on the warm "
                    f"worker")
            else:
                log("14. speculative decoding: skipped (external deployment)")

            # 15. sharded serving gang (docs/SERVING.md §Sharded serving):
            # one llm.generate job carrying a gang stanza of kind=serving
            # reserves TWO co-located workers all-or-nothing, rendezvouses
            # them into a TP=2 gang, and serves the session set tensor-
            # parallel — rank 0 alone samples and streams, the follower
            # replays the broadcast ragged entries with lm_head DCE'd.
            # While the gang lingers post-job, /api/v1/capacity must show
            # ONE fused row for it (aggregate tokens/s, min-of-ranks page
            # headroom) instead of two independent worker rows, and the
            # fleet metrics must show stream tokens from rank 0 ONLY.
            if not external:
                def _fleet_txt() -> str:
                    return httpx.get(f"{API}/metrics?scope=fleet",
                                     timeout=10.0).text

                motif = [5, 9, 14, 23, 7, 11, 3, 19]
                r = c.post("/api/v1/jobs", json={
                    "topic": "job.tpu.generate",
                    "payload": {"op": "llm.generate",
                                "gang": {"kind": "serving", "workers": 2},
                                "prompts": [motif * 2 + [2]],
                                "max_new_tokens": 12,
                                "cache_pages": 32, "page_size": 8,
                                "linger_s": 20.0}})
                assert r.status_code == 202, r.text
                gang_job = r.json()["job_id"]
                # the fused capacity row appears while the gang is live
                # (the linger window keeps it up past the job result)
                fused, t0 = [], time.time()
                while time.time() - t0 < 90:
                    fused = c.get("/api/v1/capacity").json().get(
                        "serving_gangs", [])
                    if fused:
                        break
                    time.sleep(0.5)
                assert len(fused) == 1, fused
                row = fused[0]
                assert row["size"] == 2 and len(row["members"]) == 2, row
                assert sorted(row["members"].values()) == [0, 1], row
                assert row["leader"] in row["members"], row
                assert row["pages_total_min"] > 0, row
                doc = wait_job(c, gang_job, "SUCCEEDED", 120)
                res = doc["result"]
                assert res["kind"] == "serving", res
                lead = res["per_rank"]["0"]
                follow = res["per_rank"]["1"]
                assert len(lead["results"][0]["tokens"]) == 12, lead
                # one ragged program per rank; the follower replayed every
                # broadcast step and sampled nothing
                assert lead["compiled"] == 1 and follow["compiled"] == 1, res
                assert follow["steps_replayed"] == lead["steps"] > 0, res
                # the gangs table knows the kind (cordumctl gangs)
                gdoc = c.get("/api/v1/gangs").json()
                assert any(g.get("kind") == "serving"
                           for g in gdoc.get("gangs", [])), gdoc
                # rank 0 alone streamed: the stream-token counter carries
                # exactly the rank="0" series
                ranks = set()
                for ln in _fleet_txt().splitlines():
                    if ln.startswith(
                            "cordum_serving_gang_stream_tokens_total{"):
                        ranks.add(ln.split('rank="')[1].split('"')[0])
                assert ranks == {"0"}, ranks
                log(f"15. sharded serving gang: TP=2 gang "
                    f"({'+'.join(sorted(row['members']))}) served the "
                    f"session with 1 ragged program per rank, one fused "
                    f"capacity row ({row['pages_free_min']}/"
                    f"{row['pages_total_min']} min pages free), stream "
                    f"packets from rank 0 only")
            else:
                log("15. sharded serving gang: skipped (external deployment)")

        log("PASS")
        return 0
    finally:
        for p in reversed(procs):
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if not keep:
            log(f"logs kept at {logdir}")


if __name__ == "__main__":
    sys.exit(main())
