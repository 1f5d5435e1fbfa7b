"""Built-in JAX job handlers for the TPU worker pool.

Job payloads arrive via context pointers as JSON: ``{"op": ..., ...}``.
Each handler maps a control-plane job onto an XLA computation:

  * ``echo``        — the hello-pack contract (reference
                      ``examples/hello-worker-go/main.go:44-90``): return the
                      context payload
  * ``matmul``      — batched bf16 matmul benchmark op (MXU saturation)
  * ``embed``       — batch text embedding (context-engine compute path)
  * ``infer``       — Llama-family forward step (greedy next-token scoring)
  * ``train_step``  — one SPMD training step over the worker's mesh

Handlers are pure-async wrappers that push the actual XLA work onto the
worker's executor thread so heartbeats/cancel keep flowing while the chip
crunches.  jitted callables are cached per (op, shape-bucket).
"""
from __future__ import annotations

import functools
import time
from typing import Any, Optional

import numpy as np

from ..infra import logging as logx
from ..obs import startup
from .runtime import JobContext, Worker


class HandlerError(Exception):
    pass


def _maybe_timer(timer, **attrs: str):
    """``ctx.device_timer`` when the caller passed one, else a no-op CM —
    TPUCompute stays usable outside a traced JobContext (bench, tests)."""
    if timer is not None:
        return timer("device", **attrs)
    import contextlib

    return contextlib.nullcontext()


async def echo_handler(ctx: JobContext) -> Any:
    """Return the job context payload (plus a marker, like the hello worker)."""
    return {"echo": ctx.payload, "worker": ctx.worker.worker_id}


# ---------------------------------------------------------------------------


def make_matmul_program(iters: int):
    """The jitted ``matmul`` op: ``iters`` rounds of k→m→k through two
    matmuls, then the final projection to ``(b, n, m)``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def matmul_program(x, y, y_back):
        # carry shape must stay (b, n, k) across iterations, so each
        # step goes k→m→k through two matmuls
        def body(i, acc):
            return jnp.tanh((acc @ y) @ y_back)

        acc = jax.lax.fori_loop(0, iters, body, x)
        return acc @ y  # final projection to (b, n, m)

    return matmul_program


class TPUCompute:
    """Lazily-initialized JAX compute state shared by the TPU handlers.

    Holds the device mesh, the embedder, an optional Llama model, and jit
    caches.  Created once per worker process (the slice owner).
    """

    def __init__(self, *, tp: int = 1, embedder_cfg=None, llama_cfg=None, seed: int = 0):
        with startup.phase("startup.compute"):
            import jax

            from ..models.embedder import Embedder, EmbedderConfig
            from ..models import llama as llama_mod
            from ..parallel.mesh import simple_mesh

            self.jax = jax
            n_dev = len(jax.devices())
            self.mesh = simple_mesh(min(tp, n_dev) if n_dev % min(tp, n_dev) == 0 else 1)
            with startup.phase("startup.embedder"):
                self.embedder = Embedder(
                    embedder_cfg or EmbedderConfig(), seed=seed, mesh=self.mesh)
        self.llama_cfg = llama_cfg or llama_mod.LlamaConfig.tiny()
        self._llama_params = None
        self._llama_fwd = None
        self._matmul_cache: dict[tuple, Any] = {}
        self._batch_shapes: set[tuple] = set()  # compile_cached span attr
        self._seed = seed

    # -- matmul -----------------------------------------------------------
    def matmul(self, b: int, n: int, k: int, m: int, iters: int = 1, dtype: str = "bfloat16",
               timer=None):
        import jax
        import jax.numpy as jnp

        key = (b, n, k, m, iters, dtype)
        fn = self._matmul_cache.get(key)
        compiled = fn is not None  # device span attr: compile vs cached split
        if fn is None:
            dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
            fn = (make_matmul_program(iters), dt)
            self._matmul_cache[key] = fn
        run, dt = fn
        kx, ky, kb = jax.random.split(jax.random.PRNGKey(self._seed), 3)
        x = jax.random.normal(kx, (b, n, k), dt)
        y = jax.random.normal(ky, (k, m), dt)
        y_back = jax.random.normal(kb, (m, k), dt)
        with _maybe_timer(timer, op="matmul", compile_cached=str(compiled).lower(),
                          items=str(b), bucket=f"{n}x{k}x{m}"):
            out = jax.block_until_ready(run(x, y, y_back))
        return {
            "shape": list(out.shape),
            "checksum": float(jnp.sum(out.astype(jnp.float32))),
            "flops": 2.0 * b * n * k * m * (2 * iters + 1),
        }

    # -- llama ------------------------------------------------------------
    def _ensure_llama(self):
        if self._llama_params is None:
            import jax

            from ..models import llama as llama_mod

            self._llama_params = llama_mod.init_params(
                jax.random.PRNGKey(self._seed), self.llama_cfg
            )
            cfg = self.llama_cfg

            @jax.jit
            def fwd(params, tokens):
                return llama_mod.forward(params, tokens, cfg)

            self._llama_fwd = fwd

    def infer(self, tokens: list[list[int]], max_len: Optional[int] = None, timer=None):
        import jax.numpy as jnp
        import numpy as np

        compiled = self._llama_params is not None
        self._ensure_llama()
        cfg = self.llama_cfg
        t = max(len(r) for r in tokens)
        t = min(max_len or cfg.max_seq_len, max(t, 1))
        batch = np.zeros((len(tokens), t), np.int32)
        lens = []
        for i, row in enumerate(tokens):
            row = [min(x, cfg.vocab_size - 1) for x in row[:t]]
            batch[i, : len(row)] = row
            lens.append(max(1, len(row)))
        with _maybe_timer(timer, op="infer", compile_cached=str(compiled).lower(),
                          items=str(len(tokens)), bucket=str(t)):
            logits = self._llama_fwd(self._llama_params, jnp.asarray(batch))
            # score each row at ITS last real token (causal attention makes
            # this invariant to right-padding, so per-job and micro-batched
            # inference agree bit-for-bit in exact arithmetic)
            last = logits[jnp.arange(len(tokens)), jnp.asarray(lens) - 1]
            next_tokens = np.asarray(jnp.argmax(last, axis=-1)).tolist()
        return {"next_tokens": next_tokens, "seq_len": t}

    # -- micro-batch entry points -----------------------------------------
    def embed_batch(self, texts: list[str], *, seq_len: int = 0,
                    batch_buckets=None, timer=None):
        """One padded XLA call embedding many jobs' texts: sequence dim
        trimmed to the queue's length bucket, batch dim padded up to a
        power-of-two bucket so XLA keeps one program per (batch, seq)
        bucket pair."""
        import numpy as np

        from ..batching.buckets import bucket_for, pow2_buckets
        from ..models.embedder import batch_tokenize

        cfg = self.embedder.cfg
        ids, mask = batch_tokenize(texts, cfg, max_len=seq_len or cfg.max_len)
        b = len(texts)
        bpad = bucket_for(b, batch_buckets or pow2_buckets(1, 256))
        if bpad > b:
            ids = np.pad(ids, ((0, bpad - b), (0, 0)))
            mask = np.pad(mask, ((0, bpad - b), (0, 0)))
        shape = ("embed", bpad, ids.shape[1])
        compiled = shape in self._batch_shapes
        self._batch_shapes.add(shape)
        with _maybe_timer(timer, op="embed_batch", compile_cached=str(compiled).lower(),
                          items=str(b), bucket=str(ids.shape[1])):
            out = self.embedder.embed_tokens(ids, mask)
        return np.asarray(out)[:b]

    def infer_batch(self, rows: list[list[int]], *, seq_len: int = 0,
                    batch_buckets=None, timer=None):
        """One padded XLA call scoring many jobs' rows; each row's next
        token is gathered at its own last real position (causal attention
        makes the right-padding inert).  Returns (next_tokens, seq_len)."""
        import jax.numpy as jnp
        import numpy as np

        from ..batching.buckets import bucket_for, pow2_buckets

        self._ensure_llama()
        cfg = self.llama_cfg
        t = min(max(1, seq_len or max((len(r) for r in rows), default=1)), cfg.max_seq_len)
        b = len(rows)
        bpad = bucket_for(b, batch_buckets or pow2_buckets(1, 256))
        batch = np.zeros((bpad, t), np.int32)
        lens = np.ones((bpad,), np.int32)
        for i, row in enumerate(rows):
            row = [min(x, cfg.vocab_size - 1) for x in row[:t]]
            batch[i, : len(row)] = row
            lens[i] = max(1, len(row))
        shape = ("infer", bpad, t)
        compiled = shape in self._batch_shapes
        self._batch_shapes.add(shape)
        with _maybe_timer(timer, op="infer_batch", compile_cached=str(compiled).lower(),
                          items=str(b), bucket=str(t)):
            logits = self._llama_fwd(self._llama_params, jnp.asarray(batch))
            last = logits[jnp.arange(bpad), jnp.asarray(lens) - 1]
            next_tokens = np.asarray(jnp.argmax(last, axis=-1))[:b].tolist()
        return next_tokens, t


def make_tpu_handlers(compute: TPUCompute):
    """Build the op-dispatching default handler backed by `compute`."""

    async def handler(ctx: JobContext) -> Any:
        payload = ctx.payload or {}
        if not isinstance(payload, dict):
            raise HandlerError(f"payload must be a JSON object, got {type(payload).__name__}")
        op = payload.get("op", "echo")
        ctx.check_cancelled()
        if op == "echo":
            return {"echo": payload, "worker": ctx.worker.worker_id}
        if op == "matmul":
            return await ctx.worker.run_in_executor(
                functools.partial(
                    compute.matmul,
                    int(payload.get("b", 8)),
                    int(payload.get("n", 512)),
                    int(payload.get("k", 512)),
                    int(payload.get("m", 512)),
                    int(payload.get("iters", 1)),
                    str(payload.get("dtype", "bfloat16")),
                    timer=ctx.device_timer,
                )
            )
        if op == "embed":
            texts = payload.get("texts")
            if not isinstance(texts, list) or not all(isinstance(t, str) for t in texts):
                raise HandlerError("embed op requires texts: list[str]")

            def _embed():
                with ctx.device_timer("device", op="embed", items=str(len(texts))):
                    return compute.embedder.embed(texts)

            vecs = await ctx.worker.run_in_executor(_embed)
            return {"embeddings": np.asarray(vecs).tolist(), "dim": int(vecs.shape[1])}
        if op == "infer":
            tokens = payload.get("tokens")
            if not isinstance(tokens, list):
                raise HandlerError("infer op requires tokens: list[list[int]]")
            return await ctx.worker.run_in_executor(
                functools.partial(
                    compute.infer, tokens, payload.get("max_len"), timer=ctx.device_timer
                )
            )
        if op == "llm.generate":
            # serving jobs route through the worker's serving engine BEFORE
            # the handler path (runtime._on_job); landing here means the
            # engine is not attached or the payload shape is invalid
            serving = ctx.worker.serving
            if serving is None:
                raise HandlerError(
                    "llm.generate requires the serving engine (WORKER_SERVING=1)"
                )
            raise HandlerError(
                "llm.generate requires tokens: non-empty list[int] "
                "(plus optional session_id/max_new_tokens/eos_token/stream)"
            )
        if op == "train":
            import asyncio

            from .training import TrainRunner

            loop = asyncio.get_running_loop()

            def report(frac, msg):
                asyncio.run_coroutine_threadsafe(ctx.progress(100 * frac, msg), loop)

            runner = TrainRunner()
            return await ctx.worker.run_in_executor(
                functools.partial(
                    runner.train, payload,
                    cancelled=ctx.cancelled.is_set, progress=report,
                )
            )
        raise HandlerError(f"unknown op {op!r}")

    return handler


def make_micro_batcher(
    compute: TPUCompute,
    worker: Worker,
    *,
    max_batch_rows: int = 32,
    max_wait_ms: float = 25.0,
    metrics=None,
):
    """Build the worker's micro-batcher over ``compute``'s batch entry
    points: payload decomposition (``parts_fn``) + the padded-XLA flush.
    Invalid payload shapes decompose to None so they keep the per-job
    handler path and fail with the op's own pointed error."""
    import numpy as np

    from ..batching.buckets import pow2_buckets
    from ..batching.engine import BatchParts, MicroBatcher
    from ..models.embedder import token_count

    ecfg = compute.embedder.cfg
    lcfg = compute.llama_cfg

    def parts_fn(payload) -> "BatchParts | None":
        if not isinstance(payload, dict):
            return None
        op = payload.get("op")
        if op == "embed":
            texts = payload.get("texts")
            if isinstance(texts, list) and texts and all(isinstance(t, str) for t in texts):
                return BatchParts(
                    "embed", texts, len(texts),
                    max(token_count(t, ecfg) for t in texts),
                )
        elif op == "infer":
            tokens = payload.get("tokens")
            if payload.get("max_len"):
                return None  # explicit padding request: keep per-job semantics
            if (
                isinstance(tokens, list) and tokens
                and all(isinstance(r, list) and r
                        and all(isinstance(x, int) for x in r) for r in tokens)
            ):
                length = min(max(len(r) for r in tokens), lcfg.max_seq_len)
                return BatchParts("infer", tokens, len(tokens), length)
        return None

    async def flush_fn(op, bucket, items):
        if op == "embed":
            texts = [t for it in items for t in it.rows]

            def run_embed():
                return compute.embed_batch(texts, seq_len=bucket)

            t0 = time.perf_counter()
            vecs = await worker.run_in_executor(run_embed)
            # one flush = one coalesced XLA call delivering len(texts) items
            # at this length bucket — the capacity matrix's batched-embed row
            worker.capacity.observe(
                "embed", device_s=time.perf_counter() - t0,
                bucket=str(bucket), items=len(texts),
            )
            out, i = [], 0
            for it in items:
                out.append({
                    "embeddings": np.asarray(vecs[i:i + it.n_rows]).tolist(),
                    "dim": int(vecs.shape[1]),
                    "batched": True,
                })
                i += it.n_rows
            return out
        if op == "infer":
            rows = [r for it in items for r in it.rows]

            def run_infer():
                return compute.infer_batch(rows, seq_len=bucket)

            t0 = time.perf_counter()
            toks, t = await worker.run_in_executor(run_infer)
            worker.capacity.observe(
                "infer", device_s=time.perf_counter() - t0,
                bucket=str(bucket), items=len(rows),
            )
            out, i = [], 0
            for it in items:
                out.append({
                    "next_tokens": toks[i:i + it.n_rows],
                    "seq_len": t,
                    "batched": True,
                })
                i += it.n_rows
            return out
        raise HandlerError(f"unbatchable op {op!r}")

    seq_cap = max(ecfg.max_len, min(lcfg.max_seq_len, 512))
    return MicroBatcher(
        flush_fn,
        parts_fn=parts_fn,
        max_batch_rows=max_batch_rows,
        max_wait_ms=max_wait_ms,
        len_buckets=pow2_buckets(16, seq_cap),
        metrics=metrics,
        tracer=worker.tracer,
    )


def make_serving_engine(
    compute: TPUCompute,
    worker: Worker,
    *,
    cache_pages: int = 128,
    page_size: int = 16,
    max_sessions: int = 8,
    max_new_tokens: int = 64,
    max_concurrent_prefills: int = 2,
    prefill_budget: int = 16,
    handoff_tokens: int = 0,
    prefix_cache: Optional[bool] = None,
    hibernate_after_s: float = 0.0,
    speculative: Optional[bool] = None,
    draft_k: int = 0,
    cold_tier: str = "",
    model=None,
    params=None,
    metrics=None,
):
    """Build the worker's continuous-batching serving engine over a paged
    backend.  By default it serves ``compute``'s llama model and shares its
    params (one copy of the weights per worker process; the KV page arena is
    the serving addition).  ``model`` — a ``serving.modelspec.ModelSpec`` or
    a family's config object — serves another model instead, with ``params``
    as its weights (seeded random ones when None).

    The backend's static ragged-step shapes are sized here: ``max_sessions``
    sequence rows over a flat token buffer of ``max_sessions +
    prefill_budget`` slots, so a full decode set always fits and prefill
    chunks ride the remaining ``prefill_budget`` tokens per step.

    ``prefix_cache`` and ``speculative`` default to None, "on where the model
    allows", and are handed to the engine as given: it turns them off, with
    one log line that names the capability, for a model that keeps recurrent
    state in per-session slots (``kv_positional`` false), and refuses them
    for such a model when asked for by name (``UnsupportedForModel``).
    """
    from ..serving.backend import ServingBackend
    from ..serving.engine import ServingEngine

    def params_provider():
        compute._ensure_llama()
        return compute._llama_params

    with startup.phase("startup.backend"):
        backend = ServingBackend(
            model if model is not None else compute.llama_cfg,
            num_pages=cache_pages,
            page_size=page_size,
            max_seqs=max_sessions,
            max_batch_tokens=max_sessions + max(1, prefill_budget),
            params=params,
            # another model's weights are never the compute's llama ones
            params_provider=params_provider if model is None else None,
            metrics=metrics,
        )
        engine = ServingEngine(
            backend,
            run_blocking=worker.run_in_executor,
            max_sessions=max_sessions,
            max_new_tokens_cap=max_new_tokens,
            max_concurrent_prefills=max_concurrent_prefills,
            handoff_threshold_tokens=handoff_tokens,
            prefix_cache=prefix_cache,
            hibernate_after_s=hibernate_after_s,
            speculative=speculative,
            # draft_k == 0 means "engine default" so config files can omit it
            **({"draft_k": draft_k} if draft_k > 0 else {}),
            metrics=metrics,
            tracer=worker.tracer,
            capacity=worker.capacity,
        )
    if cold_tier == "statebus" and engine.tiering is not None:
        # journal hibernated sessions through the statebus KV so they
        # survive a restart; cmd.worker awaits arena.load() post-start
        from ..serving.tiering import StatebusColdTier

        engine.tiering.arena = StatebusColdTier(
            worker.store.kv, worker_id=worker.worker_id,
        )
    return engine


def attach_default_tpu_worker(
    worker: Worker,
    *,
    tp: int = 1,
    batching: bool = True,
    max_batch_rows: int = 32,
    max_batch_wait_ms: float = 25.0,
    serving: bool = True,
    serving_cache_pages: int = 128,
    serving_page_size: int = 16,
    serving_max_sessions: int = 8,
    serving_max_new_tokens: int = 64,
    serving_prefill_budget: int = 16,
    serving_handoff_tokens: int = 0,
    serving_prefix_cache: Optional[bool] = None,
    serving_hibernate_after_s: float = 0.0,
    serving_speculative: Optional[bool] = None,
    serving_draft_k: int = 0,
    serving_cold_tier: str = "",
    serving_model=None,
    serving_params=None,
    gang: bool = True,
    gang_rendezvous_timeout_s: float = 10.0,
    gang_peer_timeout_s: float = 30.0,
    metrics=None,
    **kw,
) -> TPUCompute:
    """Wire the standard TPU op handlers (and, by default, the micro-batcher
    over the batchable ops, the llm.generate serving engine, and the gang
    runner for multi-chip gang member jobs) onto a worker."""
    compute = TPUCompute(tp=tp, **kw)
    worker.register_default(make_tpu_handlers(compute))
    if batching:
        worker.attach_batcher(make_micro_batcher(
            compute, worker,
            max_batch_rows=max_batch_rows, max_wait_ms=max_batch_wait_ms,
            metrics=metrics,
        ))
    if serving:
        worker.attach_serving(make_serving_engine(
            compute, worker,
            cache_pages=serving_cache_pages, page_size=serving_page_size,
            max_sessions=serving_max_sessions,
            max_new_tokens=serving_max_new_tokens,
            prefill_budget=serving_prefill_budget,
            handoff_tokens=serving_handoff_tokens,
            prefix_cache=serving_prefix_cache,
            hibernate_after_s=serving_hibernate_after_s,
            speculative=serving_speculative,
            draft_k=serving_draft_k,
            cold_tier=serving_cold_tier,
            model=serving_model, params=serving_params,
            metrics=metrics,
        ))
    if gang:
        from .gang import GangRunner
        from .training import TrainRunner

        worker.attach_gang(GangRunner(
            worker,
            trainer=TrainRunner(),
            rendezvous_timeout_s=gang_rendezvous_timeout_s,
            peer_timeout_s=gang_peer_timeout_s,
        ), metrics=metrics)
    return compute
