#!/usr/bin/env python
"""chip_smoke: the main path on the chip, through the entry points a user calls.

    python chip_smoke.py              one TPU chip (fails at once without one)
    python chip_smoke.py --chips 4    the tensor-parallel path, one process,
                                      four chips; no other phase
    python chip_smoke.py --rehearse   the same code at tiny sizes on whatever
                                      platform JAX finds (what tier-1 runs)

One chip, two phases, never two processes on the chip at once:

  A. the service binaries (statebus, safety kernel, scheduler, gateway,
     worker) as child processes, this parent off JAX; only the worker loads
     it.  Over HTTP: the worker's heartbeat names its device, then a matmul,
     an embed fan-out and a streamed llm.generate run to SUCCEEDED.  The
     worker drains on SIGTERM and every child exits before phase B.
  B. this process takes the chip and composes the stack in-process (gateway
     app on 127.0.0.1, scheduler engine, one Worker) around a Llama at the
     published Llama-3-8B widths with only depth and context cut, drives
     serving traffic over HTTP, and compares tokens with a plain reference.

Every phase prints one JSON object; the LAST line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failure makes
the exit code non-zero and the last line ``"ok": false``.  The wall times
printed are smoke timings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import math
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback

# Phase-B sizes.  Widths are LlamaConfig.llama3_8b()'s and are never touched;
# depth and context are the only cuts.  N=16 is the deepest depth whose
# compiled ragged step (tests/test_chip_compile.py: 11.2 GB of arguments,
# 1.0 GB of temporaries) plus one extra arena copy (the non-donating page
# scatter/copy, 1.07 GB) leaves headroom on a 16 GB v5e.
REAL = {
    "n_layers": 16, "max_seq_len": 4096, "pages": 2048, "page_size": 16,
    "max_sessions": 8, "prefill_budget": 16, "new_tokens": 64,
    # 8 concurrent sessions, 256-1024 prompt tokens; index 0 repeats the
    # warm-up prompt (whole-prompt prefix hit -> copy-on-write), index 1 is
    # the repetitive one (drafted rows), index 2 takes the second turn,
    # index 3 is hibernated and restored
    "prompt_lens": (256, 1024, 320, 384, 896, 512, 768, 640),
    "turn_tokens": 32, "ref_len": 1152,
    "embed_texts": 256, "matmul_n": 4096,
    # phase A (the binary serves LlamaConfig.tiny(): context 128)
    "a_prompt_len": 48, "a_new_tokens": 32, "a_embed_jobs": 32,
    # --chips 4: full 32-layer depth, context cut
    "tp_max_seq_len": 2048, "tp_pages": 1024, "tp_prompt_lens": (96, 200, 48, 130),
    "tp_new_tokens": 12, "tp_ref_len": 256,
}
TINY = {
    **REAL,
    "n_layers": 2, "max_seq_len": 128, "pages": 96, "new_tokens": 8,
    "prompt_lens": (32, 64, 40, 48, 56, 24, 72, 36), "turn_tokens": 8,
    "ref_len": 128, "embed_texts": 64, "matmul_n": 64,
    "a_prompt_len": 12, "a_new_tokens": 8,
    "tp_max_seq_len": 128, "tp_pages": 64, "tp_prompt_lens": (24, 50, 12, 33),
    "tp_new_tokens": 6, "tp_ref_len": 64,
}

# A token mismatch against the reference is excused only where the
# reference's own margin between its top logit and the system's token is at
# most this many bf16 ulps of the top logit (a near-tie that two correct
# bf16 computations may break differently).  The count is printed.
NEAR_TIE_BF16_ULPS = 4

#: the serving programs and how often each may compile over all traffic —
#: the same on the CPU rehearsal and on the chip
SERVING_COMPILES = {
    "jit(ragged_program)": 1, "jit(_gather_page)": 1,
    "jit(_scatter_page)": 1, "jit(_copy_page)": 1,
}

API_KEY = "smoke-key"  # the user key tools/platform_smoke.spawn_stack gives the gateway


def emit(**doc) -> None:
    print(json.dumps(doc), flush=True)


def require(cond, msg) -> None:
    """A check that fails the smoke (``assert`` would vanish under -O)."""
    if not cond:
        raise AssertionError(msg if isinstance(msg, str) else f"check failed: {msg!r}")


def free_port(*, pair: bool = False) -> int:
    """A free TCP port on 127.0.0.1 (with ``pair``: the next one free too)."""
    for _ in range(64):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            if not pair:
                return port
            with socket.socket() as s2:
                try:
                    s2.bind(("127.0.0.1", port + 1))
                except OSError:
                    continue
                return port
    raise RuntimeError("no free port pair")


def seeded_tokens(seed: int, n: int, vocab: int) -> list[int]:
    """``n`` token ids in [1, vocab) from a seed — no jax, so phase A's
    parent can make prompts too."""
    import random

    rng = random.Random(seed)
    return [rng.randrange(1, vocab) for _ in range(n)]


# ---------------------------------------------------------------------------
# client side: the SDK over HTTP, plus the WS token stream
# ---------------------------------------------------------------------------


async def stream_generate(client, base_url: str, tokens: list[int], *,
                          session_id: str, max_new_tokens: int,
                          timeout_s: float) -> list[int]:
    """One streamed ``llm.generate`` through the gateway: open the WS tap,
    submit, assemble the streamed packets by offset, then read the terminal
    result back and hold the stream to it."""
    import aiohttp

    from cordum_tpu.sdk.client import merge_stream_packet

    async with aiohttp.ClientSession() as http:
        ws = await http.ws_connect(
            base_url + "/api/v1/stream", headers={"X-Api-Key": API_KEY})
        doc = await client.submit_job("job.tpu.generate", {
            "op": "llm.generate", "tokens": tokens, "session_id": session_id,
            "max_new_tokens": max_new_tokens, "stream": True,
        })
        job_id = doc["job_id"]
        streamed: list[int] = []
        n_seen = packets = 0
        deadline = time.monotonic() + timeout_s
        while True:
            msg = await ws.receive(timeout=max(0.1, deadline - time.monotonic()))
            if msg.type not in (aiohttp.WSMsgType.TEXT, aiohttp.WSMsgType.BINARY):
                raise RuntimeError(f"stream tap closed under job {job_id}: {msg.type}")
            pkt = json.loads(msg.data).get("packet") or {}
            body = pkt.get("payload") or {}
            if body.get("job_id") != job_id:
                continue
            if pkt.get("kind") == "job_progress" and body.get("status_hint") == "stream":
                fresh, n_seen = merge_stream_packet(
                    n_seen, body.get("offset"), body.get("tokens") or [])
                streamed.extend(fresh)
                packets += 1
            elif pkt.get("kind") == "job_result":
                break
        await ws.close()
    final = await client.wait_job(job_id, timeout_s=30.0)
    if final.get("state") != "SUCCEEDED":
        raise RuntimeError(f"llm.generate {job_id} ended {final.get('state')}: {final}")
    result = [int(t) for t in final["result"]["tokens"]]
    require(len(result) == max_new_tokens, (len(result), max_new_tokens))
    require(packets >= 1, "no token was streamed before the terminal result")
    require(streamed == result[:len(streamed)], "streamed tokens differ from the result")
    return result


async def embed_fanout(client, n_jobs: int, texts_per_job: int, *, timeout_s: float) -> dict:
    """Bulk-submit embed jobs; every job must come back SUCCEEDED with
    unit-norm finite vectors, and at least one through a micro-batch."""
    jobs = [{"topic": "job.tpu.ops",
             "payload": {"op": "embed", "texts": [
                 f"chip smoke document {j}-{t} about control plane scheduling"
                 for t in range(texts_per_job)]}}
            for j in range(n_jobs)]
    docs = (await client.submit_jobs(jobs))["jobs"]
    require(len(docs) == n_jobs and all(d.get("job_id") for d in docs), docs)
    batched = vectors = 0
    dim = 0
    for d in docs:
        final = await client.wait_job(d["job_id"], timeout_s=timeout_s)
        if final.get("state") != "SUCCEEDED":
            raise RuntimeError(f"embed job ended {final.get('state')}: {final}")
        res = final["result"]
        dim = int(res["dim"])
        batched += bool(res.get("batched"))
        for vec in res["embeddings"]:
            norm = math.sqrt(sum(x * x for x in vec))
            require(len(vec) == dim and math.isfinite(norm), (len(vec), norm))
            require(abs(norm - 1.0) < 2e-2, f"embedding norm {norm}")
            vectors += 1
    require(vectors == n_jobs * texts_per_job, vectors)
    require(batched >= 1, "no embed job rode a micro-batch")
    return {"jobs": n_jobs, "vectors": vectors, "dim": dim, "batched_jobs": batched}


async def run_matmul(client, n: int, *, timeout_s: float) -> dict:
    doc = await client.submit_job("job.tpu.ops", {
        "op": "matmul", "b": 2, "n": n, "k": n, "m": n, "dtype": "bfloat16"})
    final = await client.wait_job(doc["job_id"], timeout_s=timeout_s)
    if final.get("state") != "SUCCEEDED":
        raise RuntimeError(f"matmul job ended {final.get('state')}: {final}")
    res = final["result"]
    require(res["shape"] == [2, n, n], res["shape"])
    require(math.isfinite(res["checksum"]), res["checksum"])
    return {"n": n, "dtype": "bfloat16", "shape": res["shape"], "flops": res["flops"]}


# ---------------------------------------------------------------------------
# phase A: the service binaries, parent off JAX
# ---------------------------------------------------------------------------


def phase_a(args, sz: dict) -> dict:
    require("jax" not in sys.modules, "phase A's parent must stay off jax")
    from tools.platform_smoke import spawn_stack

    t0 = time.monotonic()
    logdir = tempfile.mkdtemp(prefix="cordum-chip-smoke-")
    gw_port = free_port()
    api = f"http://127.0.0.1:{gw_port}"
    pool_pages = 96  # read back through the heartbeat: proves the pool file was read
    procs = spawn_stack(
        logdir, statebus_port=free_port(pair=True), kernel_port=free_port(),
        gateway_port=gw_port, force_cpu=False,
        pool_stanza=f"    serving_cache_pages: {pool_pages}\n"
                    "    serving_max_new_tokens: 64\n",
        worker_env={"WORKER_ID": "chip-w1",
                    "POOL_CONFIG_PATH": os.path.join(logdir, "pools.yaml")},
    )
    worker = procs[-1]
    try:
        out = asyncio.run(_phase_a_traffic(args, sz, api, worker, pool_pages))
        # graceful drain: SIGTERM, and the worker must exit 0 by itself
        worker.send_signal(signal.SIGTERM)
        rc = worker.wait(timeout=90)
        require(rc == 0, f"worker exited {rc} on SIGTERM; see {logdir}/worker.log")
        out["worker_drain_exit"] = rc
    finally:
        for p in reversed(procs):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=20)
    require(all(p.poll() is not None for p in procs), "a child outlived phase A")
    require("jax" not in sys.modules, "phase A's parent imported jax")
    out.update(children=len(procs), logdir=logdir,
               wall_s=round(time.monotonic() - t0, 1))
    return out


async def _phase_a_traffic(args, sz, api, worker, pool_pages) -> dict:
    from cordum_tpu.sdk.client import Client
    from tools.platform_smoke import wait_http

    out: dict = {}
    t0 = time.monotonic()
    await asyncio.to_thread(wait_http, f"{api}/healthz", 120.0)
    async with Client(api, api_key=API_KEY, timeout_s=60.0) as client:
        # first heartbeat: the first import on the chip is slow, so wait long
        hb = None
        while hb is None:
            if worker.poll() is not None:
                raise RuntimeError(f"worker exited {worker.returncode} before its first heartbeat")
            if time.monotonic() - t0 > 420:
                raise RuntimeError("no worker heartbeat within 420 s")
            hb = (await client.workers()).get("workers", {}).get("chip-w1")
            if hb is None:
                await asyncio.sleep(0.5)
        out["first_heartbeat_s"] = round(time.monotonic() - t0, 1)
        out["heartbeat"] = {k: hb.get(k) for k in (
            "type", "device_kind", "chip_count", "slice_topology",
            "hbm_used_gb", "hbm_total_gb", "devices_healthy")}
        if not args.rehearse:
            require(hb["type"] == "tpu" and "tpu" in hb["device_kind"].lower(),
                    f"the worker's device is {hb['type']!r} ({hb['device_kind']!r}), not a TPU")
            require(hb["chip_count"] == 1, hb["chip_count"])
            require(hb["hbm_total_gb"] > 0, hb["hbm_total_gb"])
        require(hb["device_kind"], "heartbeat names no device kind")
        require(hb["devices_healthy"] is True, "the worker reports unhealthy devices")
        pages_free = int(hb["labels"]["cordum.kv_pages_free"])
        require(pages_free == pool_pages - 1,
                f"worker did not read its pool file: {pages_free} free pages")
        out["pool_file_pages_free"] = pages_free

        out["matmul"] = await run_matmul(client, sz["matmul_n"], timeout_s=300)
        out["embed"] = await embed_fanout(client, sz["a_embed_jobs"], 1, timeout_s=300)
        toks = await stream_generate(
            client, api, seeded_tokens(args.seed, sz["a_prompt_len"], 256),
            session_id="chip-a", max_new_tokens=sz["a_new_tokens"], timeout_s=300)
        out["generate"] = {"model": "LlamaConfig.tiny()", "prompt": sz["a_prompt_len"],
                           "streamed_tokens": len(toks)}
        # the worker must still be registered after the compiles (no expiry)
        hb2 = (await client.workers())["workers"].get("chip-w1")
        require(hb2 is not None, "the scheduler expired the worker during phase A")
    return out


# ---------------------------------------------------------------------------
# compile accounting + the plain reference (shared by phase B and --chips 4)
# ---------------------------------------------------------------------------


class CompileLog:
    """Every backend compile request JAX makes, by function name, with the
    seconds it took (a persistent-cache hit still counts as a request, with
    its shorter time) — via jax.monitoring."""

    def __init__(self) -> None:
        from jax import monitoring

        self.events: list[tuple[str, float]] = []
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), float(secs)))

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for name, _ in self.events:
            out[name] = out.get(name, 0) + 1
        return out

    def seconds(self) -> float:
        return round(sum(s for _, s in self.events), 2)

    def doc(self) -> dict:
        """Per program: [compile requests, seconds]; programs under 0.5 s
        that are not serving programs are folded into ``other``."""
        agg: dict[str, list] = {}
        for name, secs in self.events:
            key = name if (name in SERVING_COMPILES or secs >= 0.5) else "other"
            row = agg.setdefault(key, [0, 0.0])
            row[0] += 1
            row[1] = round(row[1] + secs, 2)
        return agg


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 (8 significand bits) at magnitude ``x``."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -126))) - 7)


def make_reference(cfg, ref_len: int, mesh=None):
    """The plain reference: jitted ``llama.forward`` over the whole
    (prompt + generated) sequence, right-padded to ``ref_len`` (causal
    attention makes the padding inert).  It shares no code with the paged
    path.  Precision: the model's own — bf16 weights and activations, f32
    accumulation at the TPU's DEFAULT matmul precision — so it is a second
    bf16 computation of the same function, not a float32 oracle; hence the
    near-tie rule.  Returns per position the argmax, its logit, and the
    logit of the token the system chose (teacher forcing: position p is
    held against the system's token p+1, so one mismatch cannot cascade)."""
    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import llama

    @jax.jit
    def reference_forward(params, tokens, chosen):
        logits = llama.forward(params, tokens, cfg, mesh=mesh).astype(jnp.float32)
        top = jnp.max(logits, axis=-1)
        arg = jnp.argmax(logits, axis=-1)
        got = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
        return arg, top, got

    def compare(params, prompt: list[int], generated: list[int]) -> dict:
        import numpy as np

        seq = list(prompt) + list(generated)
        require(len(seq) <= ref_len, (len(seq), ref_len))
        toks = np.zeros((1, ref_len), np.int32)
        toks[0, :len(seq)] = seq
        chosen = np.zeros((1, ref_len), np.int32)
        chosen[0, :len(seq) - 1] = seq[1:]
        arg, top, got = (np.asarray(a)[0] for a in reference_forward(
            params, jnp.asarray(toks), jnp.asarray(chosen)))
        require(np.isfinite(top[:len(seq)]).all(), "reference logits not finite")
        match = excused = 0
        worst = 0.0
        for i in range(len(generated)):
            p = len(prompt) - 1 + i
            if int(arg[p]) == generated[i]:
                match += 1
                continue
            ulps = float(top[p] - got[p]) / bf16_ulp(float(top[p]))
            worst = max(worst, ulps)
            if ulps > NEAR_TIE_BF16_ULPS:
                raise AssertionError(
                    f"token {i} disagrees with the reference beyond a near-tie: "
                    f"system {generated[i]}, reference {int(arg[p])}, margin "
                    f"{ulps:.1f} bf16 ulps (bound {NEAR_TIE_BF16_ULPS})")
            excused += 1
        return {"compared": len(generated), "match": match, "excused": excused,
                "worst_margin_ulps": round(worst, 2)}

    return compare


def fold(total: dict, one: dict) -> None:
    for k in ("compared", "match", "excused"):
        total[k] = total.get(k, 0) + one[k]
    total["worst_margin_ulps"] = max(total.get("worst_margin_ulps", 0.0),
                                     one["worst_margin_ulps"])


def take_device(args) -> tuple[dict, str, int, CompileLog]:
    """First touch of jax in this process: place the compile cache, start
    counting compiles, and name the device as jax reports it."""
    import jax

    from cordum_tpu.parallel.mesh import configure_compile_cache

    cache_dir = configure_compile_cache()
    d = jax.devices()[0]
    dev = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
    if not args.rehearse:
        require(dev["platform"] == "tpu", f"no chip: jax found {dev}")
    return dev, cache_dir, cache_entries(cache_dir), CompileLog()


# ---------------------------------------------------------------------------
# phase B: one process, real width
# ---------------------------------------------------------------------------


async def phase_b(args, sz: dict) -> dict:
    import jax

    t0 = time.monotonic()
    dev, cache_dir, entries_before, compiles = take_device(args)

    from cordum_tpu.controlplane.gateway.app import Gateway
    from cordum_tpu.controlplane.gateway.auth import BasicAuthProvider
    from cordum_tpu.controlplane.safetykernel.kernel import SafetyKernel
    from cordum_tpu.controlplane.scheduler.engine import Engine
    from cordum_tpu.controlplane.scheduler.safety_client import SafetyClient
    from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.jobstore import JobStore
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.memstore import MemoryStore
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.infra.schemareg import SchemaRegistry
    from cordum_tpu.models.llama import LlamaConfig
    from cordum_tpu.sdk.client import Client
    from cordum_tpu.worker.handlers import attach_default_tpu_worker
    from cordum_tpu.worker.runtime import Worker
    from cordum_tpu.workflow.engine import Engine as WorkflowEngine
    from cordum_tpu.workflow.store import WorkflowStore

    base = LlamaConfig.tiny() if args.rehearse else LlamaConfig.llama3_8b()
    cfg = dataclasses.replace(base, n_layers=sz["n_layers"], max_seq_len=sz["max_seq_len"])

    kv, bus = MemoryKV(), LoopbackBus()
    job_store, mem, wf_store = JobStore(kv), MemoryStore(kv), WorkflowStore(kv)
    kernel = SafetyKernel(policy_doc={
        "default_tenant": "default",
        "tenants": {"default": {"allow_topics": ["job.*", "job.>"]}}, "rules": []})
    await kernel.reload()
    registry = WorkerRegistry()
    pools = parse_pool_config({"topics": {"job.tpu.>": "tpu"},
                               "pools": {"tpu": {"requires": []}}})
    engine = Engine(bus=bus, job_store=job_store, safety=SafetyClient(kernel.check),
                    strategy=LeastLoadedStrategy(registry, pools), registry=registry)
    schemas = SchemaRegistry(kv)
    gateway = Gateway(
        kv=kv, bus=bus, job_store=job_store, mem=mem, kernel=kernel,
        wf_store=wf_store, schemas=schemas, registry=WorkerRegistry(),
        wf_engine=WorkflowEngine(store=wf_store, bus=bus, mem=mem, schemas=schemas),
        auth=BasicAuthProvider([API_KEY]), telemetry=False)
    worker = Worker(bus=bus, store=mem, worker_id="chip-b1", pool="tpu",
                    topics=["job.tpu.>"], capabilities=["tpu"],
                    heartbeat_interval_s=1.0)
    metrics = Metrics()
    attach_default_tpu_worker(
        worker, llama_cfg=cfg, seed=args.seed, metrics=metrics,
        serving_cache_pages=sz["pages"], serving_page_size=sz["page_size"],
        serving_max_sessions=sz["max_sessions"],
        serving_prefill_budget=sz["prefill_budget"],
        serving_max_new_tokens=sz["new_tokens"],
        # on, but never by the clock: the smoke fires the sweep itself
        serving_hibernate_after_s=3600.0)
    eng = worker.serving
    be = eng.backend
    port = free_port()
    api = f"http://127.0.0.1:{port}"
    await engine.start()
    await gateway.start("127.0.0.1", port)
    await worker.start()
    await worker.send_heartbeat()
    out: dict = {"device": dev, "cache_dir": cache_dir,
                 "cache_entries_before": entries_before}
    n_new, turn = sz["new_tokens"], sz["turn_tokens"]
    timeout = 900.0
    try:
        async with Client(api, api_key=API_KEY, timeout_s=120.0) as client:
            while "chip-b1" not in (await client.workers())["workers"]:
                require(time.monotonic() - t0 < 60, "worker never registered")
                await asyncio.sleep(0.05)

            def gen(tokens, session_id):
                return stream_generate(client, api, tokens, session_id=session_id,
                                       max_new_tokens=n_new, timeout_s=timeout)

            lens = sz["prompt_lens"]
            prompts = [seeded_tokens(args.seed + 1 + i, n, cfg.vocab_size)
                       for i, n in enumerate(lens)]
            motif = seeded_tokens(args.seed + 99, 6, cfg.vocab_size)
            prompts[1] = (motif * (lens[1] // len(motif) + 1))[:lens[1]]
            require(lens[0] % sz["page_size"] == 0, "the warm-up prompt must end on a page")

            # warm-up: one session alone compiles the one ragged program and
            # leaves its whole page-aligned prompt in the prefix cache
            t_w = time.monotonic()
            warm = await gen(prompts[0], "warm")
            out["warmup"] = {"wall_s": round(time.monotonic() - t_w, 1),
                             "compile_s": compiles.seconds()}

            # 8 concurrent sessions: chunked prefill and decode rows share
            # steps; session 0 repeats the warm-up prompt under another key
            # (whole-prompt prefix hit, then copy-on-write of its last page)
            t_c = time.monotonic()
            outs = await asyncio.gather(*(
                gen(p, f"conv-{i}") for i, p in enumerate(prompts)))
            out["concurrent"] = {"sessions": len(prompts), "prompt_lens": list(lens),
                                 "new_tokens": n_new,
                                 "wall_s": round(time.monotonic() - t_c, 1)}
            # a second turn on a resident session (affinity + its own pages)
            turn2 = prompts[2] + outs[2] + seeded_tokens(
                args.seed + 200, turn, cfg.vocab_size)
            hits_before = eng.stats.prefix_hits
            out2 = await gen(turn2, "conv-2")
            require(eng.stats.prefix_hits > hits_before, "second turn missed its pages")

            # hibernate every idle cached page, then a follow-up turn on one
            # session restores its pages: export_kv -> import_kv -> scatter
            demoted = await eng.tiering.sweep(now=time.monotonic() + 7200.0)
            require(demoted >= 1 and eng.prefix.warm_pages == 0, (
                demoted, eng.prefix.warm_pages))
            turn3 = prompts[3] + outs[3] + seeded_tokens(
                args.seed + 300, turn, cfg.vocab_size)
            out3 = await gen(turn3, "conv-3")
            out["hibernate"] = {"demoted_pages": demoted,
                                "restored_pages": eng.prefix.stats.restored_pages}

            n_jobs = 32
            out["embed"] = await embed_fanout(
                client, n_jobs, sz["embed_texts"] // n_jobs, timeout_s=timeout)
            out["matmul"] = await run_matmul(client, sz["matmul_n"], timeout_s=timeout)

            # steady window: every program has compiled once by now, so a
            # fresh session, then a restore and a prefix hit with its
            # copy-on-write, must compile nothing
            mark = len(compiles.events)
            # a fresh random prompt shares no page, so only steps replace the
            # arenas while it runs: the buffer from before must be donated
            arena_before = be._k_pages
            await gen(seeded_tokens(args.seed + 400, lens[2], cfg.vocab_size), "steady-new")
            donated = arena_before.is_deleted()
            del arena_before
            require(donated == (jax.default_backend() != "cpu"),
                    f"arena donated={donated} on {jax.default_backend()}: the "
                    "backend donates on every platform but the CPU")
            out["arena_donated"] = donated
            await eng.tiering.sweep(now=time.monotonic() + 7200.0)
            steady_hit = await gen(prompts[0], "steady-hit")
            late = compiles.events[mark:]
            require(not late, f"compiled after warm-up: {late}")

        # ---- what phase B must establish --------------------------------
        require(be.compiled_programs() == 1, be.compiled_programs())
        counts = compiles.counts()
        for name, want in SERVING_COMPILES.items():
            require(counts.get(name, 0) == want, (name, counts.get(name, 0), want))
        eng.allocator.check_consistency()  # raises on violation
        st = eng.stats
        require(st.prefix_hits >= 1 and st.cow_copies >= 1, (st.prefix_hits, st.cow_copies))
        require(eng.prefix.stats.restored_pages >= 1, "no hibernated page was restored")
        require(st.drafted_tokens > 0, "no speculative row was drafted")
        require(st.failed == 0 and st.cancelled == 0, (st.failed, st.cancelled))

        platform = jax.devices()[0].platform
        leaves = jax.tree.leaves(be._params) + [be._k_pages, be._v_pages]
        require(all({d.platform for d in x.devices()} == {platform} for x in leaves),
                f"a parameter or arena leaf is not on the {platform}")
        weight_bytes = sum(x.nbytes for x in jax.tree.leaves(be._params))
        n_params = sum(x.size for x in jax.tree.leaves(be._params))

        # tokens against the plain reference, every session
        compare = make_reference(cfg, sz["ref_len"])
        agreement: dict = {}
        checked = [(prompts[0], warm), (turn2, out2), (turn3, out3),
                   (prompts[0], steady_hit)]
        checked += list(zip(prompts, outs))
        for prompt, generated in checked:
            fold(agreement, compare(be._params, prompt, generated))
        require(agreement["match"] * 2 >= agreement["compared"], agreement)
        agreement.update(sessions=len(checked), bound_bf16_ulps=NEAR_TIE_BF16_ULPS,
                         reference="jitted llama.forward, model dtype "
                                   f"{jax.numpy.dtype(cfg.dtype).name}, default matmul precision")

        hb = worker.build_heartbeat()
        if platform == "tpu":
            require(hb.type == "tpu" and "tpu" in hb.device_kind.lower(), hb)
            require(hb.chip_count == 1, hb.chip_count)
            # one chip: every extent of its real coords is 1
            require(hb.slice_topology == "x".join("1" * len(jax.devices()[0].coords)),
                    f"topology {hb.slice_topology!r} vs coords {jax.devices()[0].coords}")
            require(hb.hbm_used_gb * 1e9 >= weight_bytes, (hb.hbm_used_gb, weight_bytes))
        require(hb.devices_healthy, "the worker reports unhealthy devices")
        mem_stats = jax.devices()[0].memory_stats() or {}
    finally:
        await worker.stop()
        await gateway.stop()
        await engine.stop()
        await bus.close()

    out.update(
        reduced={"n_layers": cfg.n_layers, "max_seq_len": cfg.max_seq_len,
                 "pages": sz["pages"], "page_size": sz["page_size"],
                 "params": int(n_params), "of_layers": base.n_layers,
                 "of_max_seq_len": base.max_seq_len},
        widths={"d_model": cfg.d_model, "n_heads": cfg.n_heads,
                "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
                "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
                "dtype": jax.numpy.dtype(cfg.dtype).name},
        serving={"steps": st.steps, "prefix_hits": st.prefix_hits,
                 "prefix_hit_tokens": st.prefix_hit_tokens,
                 "cow_copies": st.cow_copies, "drafted_tokens": st.drafted_tokens,
                 "accepted_tokens": st.accepted_tokens,
                 "prefill_tokens": st.prefill_tokens,
                 "decoded_tokens": st.decoded_tokens,
                 "max_occupancy": st.max_occupancy,
                 "compiled_programs": be.compiled_programs()},
        reference=agreement,
        heartbeat={"type": hb.type, "device_kind": hb.device_kind,
                   "chip_count": hb.chip_count, "slice_topology": hb.slice_topology,
                   "hbm_used_gb": round(hb.hbm_used_gb, 3),
                   "hbm_total_gb": round(hb.hbm_total_gb, 3)},
        weight_bytes=int(weight_bytes),
        peak_bytes_in_use=mem_stats.get("peak_bytes_in_use"),
        bytes_limit=mem_stats.get("bytes_limit"),
        compiles=compiles.doc(), compile_s=compiles.seconds(),
        compile_cache_hits=compiles.cache_hits,
        cache_entries_after=cache_entries(cache_dir),
        wall_s=round(time.monotonic() - t0, 1),
    )
    return out


# ---------------------------------------------------------------------------
# --chips 4: the tensor-parallel serving path, full depth
# ---------------------------------------------------------------------------


async def phase_tp4(args, sz: dict) -> dict:
    import jax
    import numpy as np

    t0 = time.monotonic()
    dev, cache_dir, entries_before, compiles = take_device(args)
    require(dev["count"] == 4,
            f"--chips 4 needs exactly four devices, jax found {dev['count']} (rehearse "
            "with XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu)")

    from cordum_tpu.models.llama import LlamaConfig
    from cordum_tpu.serving.engine import GenRequest, ServingEngine
    from cordum_tpu.serving.shard import ShardedServingBackend

    # the rehearsal's toy needs kv heads that four ranks divide
    base = (dataclasses.replace(LlamaConfig.tiny(), n_heads=8, n_kv_heads=4)
            if args.rehearse else LlamaConfig.llama3_8b())
    cfg = dataclasses.replace(base, max_seq_len=sz["tp_max_seq_len"])  # full depth
    be = ShardedServingBackend(
        cfg, rank=0, tp=4, num_pages=sz["tp_pages"], page_size=sz["page_size"],
        max_seqs=sz["max_sessions"],
        max_batch_tokens=sz["max_sessions"] + sz["prefill_budget"], seed=args.seed)

    async def run_blocking(fn, *a):
        return await asyncio.get_running_loop().run_in_executor(None, fn, *a)

    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=sz["max_sessions"],
                        max_new_tokens_cap=sz["tp_new_tokens"])
    prompts = [seeded_tokens(args.seed + 1 + i, n, cfg.vocab_size)
               for i, n in enumerate(sz["tp_prompt_lens"])]
    try:
        # sessions of different lengths join at once: prefill chunks of the
        # long ones share steps with the decode rows of the short ones
        results = await asyncio.wait_for(asyncio.gather(*(
            eng.submit(GenRequest(prompt=p, max_new_tokens=sz["tp_new_tokens"],
                                  stream=False, session_key=f"tp-{i}"),
                       job_id=f"tp-job-{i}")
            for i, p in enumerate(prompts))), timeout=1500)
        outs = [[int(t) for t in r["tokens"]] for r in results]
        require(all(len(o) == sz["tp_new_tokens"] for o in outs), [len(o) for o in outs])
        require(be.compiled_programs() == 1, be.compiled_programs())
        require(compiles.counts().get("jit(ragged_program)") == 1, compiles.counts())
        eng.allocator.check_consistency()
        steps = eng.stats.steps
    finally:
        await eng.stop()

    # each device holds about a quarter of the weights and of each arena
    devices = list(be.mesh.devices.flat)
    require(len(devices) == 4 and be.mesh.shape["tp"] == 4, be.mesh)

    def per_device(tree) -> list[int]:
        held = {d.id: 0 for d in devices}
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                held[sh.device.id] += sh.data.nbytes
        return [held[d.id] for d in devices]

    weight_total = sum(x.nbytes for x in jax.tree.leaves(be._params))
    shares = {}
    for name, tree, total in (("weights", be._params, weight_total),
                              ("k_arena", be._k_pages, be._k_pages.nbytes),
                              ("v_arena", be._v_pages, be._v_pages.nbytes)):
        held = per_device(tree)
        shares[name] = {"total_bytes": int(total), "per_device_bytes": held,
                        "per_device_share": [round(h / total, 4) for h in held]}
        # a quarter, plus the small replicated norm vectors for the weights
        require(all(0.24 <= h / total <= 0.27 for h in held), (name, held, total))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if dev["platform"] == "tpu":
        state = weight_total + 2 * be._k_pages.nbytes
        require(all(b is not None and 0.2 * state <= b <= 0.6 * state for b in in_use), (
            in_use, state))

    # the collectives a Megatron layout needs are in the compiled program
    text = be._ragged_jit.lower(
        be._params, be._k_pages, be._v_pages,
        np.zeros((be.feed_layout.size,), np.int32)).compile().as_text()
    collectives = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                   for op in ("all-reduce", "all-gather", "reduce-scatter",
                              "collective-permute", "all-to-all")}
    # row-parallel wo and w_down each end in a sum over tp, in every layer
    require(collectives["all-reduce"] >= 2 * cfg.n_layers, collectives)

    compare = make_reference(cfg, sz["tp_ref_len"], mesh=be.mesh)
    agreement: dict = {}
    for prompt, generated in zip(prompts, outs):
        fold(agreement, compare(be._params, prompt, generated))
    require(agreement["match"] * 2 >= agreement["compared"], agreement)
    agreement.update(sessions=len(prompts), bound_bf16_ulps=NEAR_TIE_BF16_ULPS,
                     reference="jitted llama.forward(mesh=mesh) on the same sharded params")

    return {
        "device": dev, "tp": 4, "mesh": dict(be.mesh.shape),
        "reduced": {"n_layers": cfg.n_layers, "max_seq_len": cfg.max_seq_len,
                    "pages": sz["tp_pages"], "of_max_seq_len": base.max_seq_len,
                    "params": int(sum(x.size for x in jax.tree.leaves(be._params)))},
        "sessions": len(prompts), "prompt_lens": list(sz["tp_prompt_lens"]),
        "new_tokens": sz["tp_new_tokens"], "steps": steps,
        "shares": shares, "bytes_in_use_per_device": in_use,
        "collectives": collectives, "reference": agreement,
        "compiles": compiles.doc(), "compile_s": compiles.seconds(),
        "cache_dir": cache_dir, "cache_entries_before": entries_before,
        "cache_entries_after": cache_entries(cache_dir),
        "wall_s": round(time.monotonic() - t0, 1),
    }


# ---------------------------------------------------------------------------


def run(args) -> dict:
    sz = TINY if args.rehearse else REAL
    import platform as _platform

    from cordum_tpu.native import load_strategy_scan

    emit(phase="start", mode="rehearse" if args.rehearse else "chip",
         chips=args.chips, seed=args.seed, python=_platform.python_version(),
         native_scan="loaded" if load_strategy_scan() is not None else "python fallback")
    if args.chips == 4:
        doc = asyncio.run(phase_tp4(args, sz))
        emit(phase="tp4", **doc)
        return doc["device"]
    emit(phase="A", **phase_a(args, sz))
    doc = asyncio.run(phase_b(args, sz))
    import jax
    import jaxlib

    emit(phase="B", jax=jax.__version__, jaxlib=jaxlib.__version__, **doc)
    return doc["device"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, on whatever platform jax finds")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    t0 = time.monotonic()
    try:
        device = run(args)
    except Exception as e:  # noqa: BLE001 - the one catch: report, then exit non-zero
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:400]}),
              flush=True)
        return 1
    emit(phase="done", wall_s=round(time.monotonic() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
