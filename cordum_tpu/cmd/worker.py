"""TPU worker binary: the in-tree worker that owns a slice and executes jobs
as JAX computations (the north star's ``sdk/runtime`` TPU worker).

Env: WORKER_ID, WORKER_POOL, WORKER_TOPICS (comma), WORKER_CAPABILITIES,
WORKER_MAX_PARALLEL, WORKER_TP (tensor-parallel width for the local mesh).

Micro-batching (cordum_tpu/batching) is on by default; limits come from the
worker's pool stanza in pools.yaml (``max_batch_size`` /
``max_batch_wait_ms``), overridable via WORKER_MAX_BATCH_SIZE /
WORKER_BATCH_WAIT_MS, and WORKER_BATCHING=0 disables it.

Serving (cordum_tpu/serving, ``llm.generate``) is on by default too; the
pool stanza's ``serving_cache_pages`` / ``serving_page_size`` /
``serving_max_sessions`` / ``serving_max_new_tokens`` /
``serving_prefill_budget`` size the paged KV cache, admission control, and
the ragged step's chunked-prefill token budget, overridable via
WORKER_SERVING_CACHE_PAGES / WORKER_SERVING_PAGE_SIZE /
WORKER_SERVING_MAX_SESSIONS / WORKER_SERVING_MAX_NEW_TOKENS /
WORKER_SERVING_PREFILL_BUDGET, and WORKER_SERVING=0 disables the engine.
Disaggregation (docs/SERVING.md §Disaggregation): WORKER_SERVING_ROLE
(prefill | decode | mixed, or the pool's ``serving_role``) sets the
placement role — a "prefill" worker live-migrates each session to the
best decode peer once its prompt finishes prefilling, or earlier once
prefill crosses WORKER_SERVING_HANDOFF_TOKENS (``serving_handoff_tokens``).
Prefix cache + tiering (docs/SERVING.md §Prefix cache and tiering):
WORKER_SERVING_PREFIX_CACHE=0 (``serving_prefix_cache``) disables
copy-on-write shared-prefix KV pages; WORKER_SERVING_HIBERNATE_AFTER
(``serving_hibernate_after_s``, seconds) > 0 tiers cached prefixes idle
past the threshold into the host-RAM cold arena and pins the session's
scheduler affinity until the next turn restores them.
WORKER_SERVING_COLD_TIER=statebus (``serving_cold_tier``) journals the
cold arena through the statebus KV so hibernated sessions survive a
worker restart (restored on boot, re-admitted on the next turn).
Speculative decoding (docs/SERVING.md §Speculative decoding):
WORKER_SERVING_SPECULATIVE=0 (``serving_speculative``) disables the
zero-extra-weights n-gram drafter inside the ragged step;
WORKER_SERVING_DRAFT_K (``serving_draft_k``) caps tokens drafted per
session per step (0 = engine default).

Graceful drain (docs/SERVING.md §Migration, drain, and failover): SIGTERM
(unless WORKER_DRAIN_ON_TERM=0) and ``cordumctl drain <worker>`` both put
the worker in drain mode — stop admitting, live-migrate serving sessions
to peers, finish per-job work (WORKER_DRAIN_TIMEOUT, default 30s), then
exit with zero CANCELLED sessions.  WORKER_LLAMA_DTYPE (float32|bfloat16)
overrides the tiny model's dtype — the chaos suite pins float32 so resumed
token streams compare exactly against the fp32 sequential oracle.
"""
from __future__ import annotations

import asyncio
import os
import signal

if os.environ.get("CORDUM_FORCE_CPU") == "1":
    # pin this worker to the CPU backend BEFORE any jax backend
    # initializes: a chip belongs to one process at a time, so harnesses
    # that start several workers on one host (CI, smoke, chaos) keep them
    # all off it
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

from ..infra.memstore import MemoryStore
from ..infra.metrics import Metrics
from ..obs.profiler import RuntimeProfiler
from ..obs.telemetry import TelemetryExporter
from ..serving.tiering import StatebusColdTier
from ..worker.handlers import attach_default_tpu_worker
from ..worker.runtime import Worker
from . import _boot


def _pool_limits(cfg, pool_name: str):
    """This worker's pool stanza from pools.yaml (None = defaults).
    A missing or invalid pool file must not stop a worker from booting."""
    try:
        from ..infra.config import load_pool_config

        return load_pool_config(cfg.pool_config_path).pools.get(pool_name)
    except Exception as e:  # noqa: BLE001 - batching/serving config is best-effort
        from ..infra import logging as logx

        logx.warn("pool config unreadable; using built-in worker defaults",
                  path=cfg.pool_config_path, err=str(e))
        return None


async def main() -> None:
    cfg = _boot.setup()
    kv, bus, conn = await _boot.connect_statebus(cfg)
    env = os.environ
    pool_name = env.get("WORKER_POOL", "tpu-default")
    pool = _pool_limits(cfg, pool_name)
    worker = Worker(
        bus=bus,
        store=MemoryStore(kv),
        worker_id=env.get("WORKER_ID", f"tpu-worker-{os.getpid()}"),
        pool=pool_name,
        topics=[t for t in env.get("WORKER_TOPICS", "job.tpu.>").split(",") if t],
        capabilities=[c for c in env.get("WORKER_CAPABILITIES", "tpu,echo").split(",") if c],
        max_parallel_jobs=_boot.env_int("WORKER_MAX_PARALLEL", 4),
        heartbeat_interval_s=_boot.env_float("WORKER_HEARTBEAT_INTERVAL", 10.0),
        region=env.get("WORKER_REGION", ""),
        # prefill/decode disaggregation (docs/SERVING.md §Disaggregation):
        # "prefill" workers hand sessions to decode peers post-prefill
        serving_role=env.get("WORKER_SERVING_ROLE", "")
        or (pool.serving_role if pool else "") or "mixed",
    )
    # before the first compile: compiled programs persist across restarts
    from ..parallel.mesh import configure_compile_cache

    configure_compile_cache()
    # one registry shared by the batcher, the serving engine and the fleet
    # telemetry exporter, so worker-side metrics reach the aggregator
    metrics = Metrics()
    extra_kw = {}
    dtype_name = env.get("WORKER_LLAMA_DTYPE", "")
    if dtype_name in ("float32", "bfloat16"):
        import dataclasses

        import jax.numpy as jnp

        from ..models import llama

        extra_kw["llama_cfg"] = dataclasses.replace(
            llama.LlamaConfig.tiny(),
            dtype=jnp.float32 if dtype_name == "float32" else jnp.bfloat16,
        )
    attach_default_tpu_worker(
        worker,
        metrics=metrics,
        **extra_kw,
        tp=_boot.env_int("WORKER_TP", 1),
        batching=env.get("WORKER_BATCHING", "1") != "0",
        max_batch_rows=_boot.env_int("WORKER_MAX_BATCH_SIZE", 0)
        or (pool.max_batch_size if pool else 0) or 32,
        max_batch_wait_ms=_boot.env_float("WORKER_BATCH_WAIT_MS", 0.0)
        or (pool.max_batch_wait_ms if pool else 0.0) or 25.0,
        serving=env.get("WORKER_SERVING", "1") != "0",
        serving_cache_pages=_boot.env_int("WORKER_SERVING_CACHE_PAGES", 0)
        or (pool.serving_cache_pages if pool else 0) or 128,
        serving_page_size=_boot.env_int("WORKER_SERVING_PAGE_SIZE", 0)
        or (pool.serving_page_size if pool else 0) or 16,
        serving_max_sessions=_boot.env_int("WORKER_SERVING_MAX_SESSIONS", 0)
        or (pool.serving_max_sessions if pool else 0) or 8,
        serving_max_new_tokens=_boot.env_int("WORKER_SERVING_MAX_NEW_TOKENS", 0)
        or (pool.serving_max_new_tokens if pool else 0) or 64,
        serving_prefill_budget=_boot.env_int("WORKER_SERVING_PREFILL_BUDGET", 0)
        or (pool.serving_prefill_budget if pool else 0) or 16,
        serving_handoff_tokens=_boot.env_int("WORKER_SERVING_HANDOFF_TOKENS", 0)
        or (pool.serving_handoff_tokens if pool else 0),
        # prefix cache + tiering (docs/SERVING.md §Prefix cache and tiering)
        serving_prefix_cache=(
            env["WORKER_SERVING_PREFIX_CACHE"] != "0"
            if "WORKER_SERVING_PREFIX_CACHE" in env
            else (pool.serving_prefix_cache if pool else True)
        ),
        serving_hibernate_after_s=_boot.env_float(
            "WORKER_SERVING_HIBERNATE_AFTER", 0.0)
        or (pool.serving_hibernate_after_s if pool else 0.0),
        # self-speculative decoding (docs/SERVING.md §Speculative decoding)
        serving_speculative=(
            env["WORKER_SERVING_SPECULATIVE"] != "0"
            if "WORKER_SERVING_SPECULATIVE" in env
            else (pool.serving_speculative if pool else True)
        ),
        serving_draft_k=_boot.env_int("WORKER_SERVING_DRAFT_K", 0)
        or (pool.serving_draft_k if pool else 0),
        serving_cold_tier=env.get("WORKER_SERVING_COLD_TIER", "")
        or (pool.serving_cold_tier if pool else ""),
        # gang scheduling (docs/GANG.md): member jobs rendezvous + run the
        # SPMD/MPMD step program; WORKER_GANG=0 opts the worker out
        gang=env.get("WORKER_GANG", "1") != "0",
        gang_rendezvous_timeout_s=_boot.env_float(
            "WORKER_GANG_RENDEZVOUS_TIMEOUT", 10.0),
        gang_peer_timeout_s=_boot.env_float("WORKER_GANG_PEER_TIMEOUT", 30.0),
    )
    profiler = RuntimeProfiler(metrics, service="worker")
    telemetry = TelemetryExporter(
        "worker", bus, metrics, instance_id=worker.worker_id,
        health_fn=lambda: {**worker.telemetry_health(), **profiler.health()},
    )
    await worker.start()
    # statebus-backed cold tier: re-populate the mirror from the journal
    # so sessions hibernated before a restart are restorable here
    tiering = worker.serving.tiering if worker.serving is not None else None
    if tiering is not None and isinstance(tiering.arena, StatebusColdTier):
        await tiering.arena.load()
    await telemetry.start()
    await profiler.start()
    # SIGTERM drains by default (live-migrate sessions, finish jobs, exit);
    # SIGINT stays the immediate-stop path.  A `cordumctl drain` arriving
    # over the bus completes the same drained event.
    stop = asyncio.Event()
    drain_timeout = _boot.env_float("WORKER_DRAIN_TIMEOUT", 30.0)

    def _on_term() -> None:
        if env.get("WORKER_DRAIN_ON_TERM", "1") != "0":
            asyncio.ensure_future(worker.drain(timeout_s=drain_timeout))
        else:
            stop.set()

    loop = asyncio.get_running_loop()
    try:
        loop.add_signal_handler(signal.SIGTERM, _on_term)
        loop.add_signal_handler(signal.SIGINT, stop.set)
    except NotImplementedError:  # pragma: no cover - non-unix
        pass
    try:
        stop_w = asyncio.ensure_future(stop.wait())
        drained_w = asyncio.ensure_future(worker.wait_drained())
        done, pending = await asyncio.wait(
            {stop_w, drained_w}, return_when=asyncio.FIRST_COMPLETED
        )
        for t in pending:
            t.cancel()
    finally:
        await profiler.stop()
        await telemetry.stop()
        await worker.stop()
        await conn.close()


if __name__ == "__main__":
    asyncio.run(main())
