"""The system under test, composed in this process: the wiring PR 21 proved
on the chip (``chip_smoke.phase_b``), copied so that a later change to that
script cannot move the benchmark.  MemoryKV, LoopbackBus, scheduler Engine,
Gateway on 127.0.0.1, and the workers the configuration's family module
builds (``families/<f>.py`` ``make_workers``), so that a family whose model
spans a gang of chips, or several replicas, brings its own and this file
does not change."""
from __future__ import annotations

import asyncio
import socket
import time
from typing import Any

API_KEY = "bench-key"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class CompileLog:
    """Every backend compile request JAX makes, by function name, with its
    seconds (a persistent-cache hit still counts as a request) - via
    ``jax.monitoring``.  Copied from ``chip_smoke.CompileLog``."""

    def __init__(self) -> None:
        from jax import monitoring

        self.events: list[tuple[str, float, float]] = []  # (name, seconds, monotonic)
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), float(secs), time.monotonic()))

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0: float, t1: float) -> list[tuple[str, float]]:
        return [(n, s) for n, s, at in self.events if t0 <= at < t1]

    def seconds(self) -> float:
        return sum(s for _, s, _ in self.events)


class Stack:
    def __init__(self, family: Any, cfg: Any, params: Any, pool: dict, seed: int) -> None:
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
        from cordum_tpu.infra.registry import WorkerRegistry
        from cordum_tpu.infra.schemareg import SchemaRegistry
        from cordum_tpu.workflow.engine import Engine as WorkflowEngine
        from cordum_tpu.workflow.store import WorkflowStore

        kv = MemoryKV()
        self.bus = LoopbackBus()
        job_store, mem, wf_store = JobStore(kv), MemoryStore(kv), WorkflowStore(kv)
        self.kernel = SafetyKernel(policy_doc={
            "default_tenant": "default",
            "tenants": {"default": {"allow_topics": ["job.*", "job.>"]}}, "rules": []})
        registry = WorkerRegistry()
        pools = parse_pool_config({"topics": {"job.tpu.>": "tpu"},
                                   "pools": {"tpu": {"requires": []}}})
        self.engine = Engine(bus=self.bus, job_store=job_store,
                             safety=SafetyClient(self.kernel.check),
                             strategy=LeastLoadedStrategy(registry, pools), registry=registry)
        schemas = SchemaRegistry(kv)
        self.gateway = Gateway(
            kv=kv, bus=self.bus, job_store=job_store, mem=mem, kernel=self.kernel,
            wf_store=wf_store, schemas=schemas, registry=WorkerRegistry(),
            wf_engine=WorkflowEngine(store=wf_store, bus=self.bus, mem=mem, schemas=schemas),
            auth=BasicAuthProvider([API_KEY]), telemetry=False)
        self.family = family
        self.workers = family.make_workers(bus=self.bus, store=mem, cfg=cfg, params=params,
                                           pool=pool, seed=seed)
        # the counters and hooks the per-layer readers use are the first worker's
        self.serving = self.workers[0].serving
        self.backend = self.serving.backend
        self.port = free_port()
        self.api = f"http://127.0.0.1:{self.port}"

    async def start(self) -> None:
        await self.kernel.reload()
        await self.engine.start()
        await self.gateway.start("127.0.0.1", self.port)
        for w in self.workers:
            await w.start()
            await w.send_heartbeat()

    async def wait_registered(self, timeout_s: float = 60.0) -> None:
        from cordum_tpu.sdk.client import Client

        t0 = time.monotonic()
        async with Client(self.api, api_key=API_KEY, timeout_s=30.0) as client:
            want = {w.worker_id for w in self.workers}
            while not want <= set((await client.workers())["workers"]):
                if time.monotonic() - t0 > timeout_s:
                    raise RuntimeError("a worker never registered with the scheduler")
                await asyncio.sleep(0.05)

    async def stop(self) -> None:
        for w in self.workers:
            await w.stop()
        await self.gateway.stop()
        await self.engine.stop()
        await self.bus.close()

    def free_device_state(self) -> None:
        """Drop the program's device state before the check (the weights
        stay: they are the benchmark's)."""
        self.family.free_device_state(self.workers)
