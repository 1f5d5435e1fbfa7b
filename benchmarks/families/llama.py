"""The ``llama`` family: how a configuration file of this family becomes the
program's model, and where its plain reference is.

This is the one benchmark module that imports the program's model and worker
code: it maps a configuration's published keys onto ``cordum_tpu.models
.llama.LlamaConfig``, makes seeded weights in the pytree layout the program
reads, and builds the worker that serves them (one chip, one worker, through
``attach_default_tpu_worker``, as PR 21 proved it).  The weights are the BENCHMARK's (made here, from ``--seed``), handed
to the program and to the reference alike, so the reference takes nothing
the program has made.  A later family adds ``benchmarks/families/<f>.py``
with the same names.
"""
from __future__ import annotations

import math
from typing import Any

from . import llama_reference as reference  # noqa: F401 - the family's plain reference

#: keys every configuration file of this family states
REQUIRED_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "max_position_embeddings",
    "rope_theta", "rms_norm_eps", "tie_word_embeddings", "torch_dtype",
)


def validate(doc: dict) -> None:
    """Refuse a file the program's ``LlamaConfig`` cannot express exactly."""
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration {doc.get('name')!r} lacks {missing}")
    if doc["tie_word_embeddings"]:
        raise ValueError("LlamaConfig has separate embed and lm_head: tied embeddings cannot run")
    if doc["head_dim"] * doc["num_attention_heads"] != doc["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim = d_model // n_heads")
    if doc.get("sliding_window") or doc.get("bias") or doc.get("attention_bias"):
        raise ValueError("LlamaConfig has no window and no bias")
    if doc["torch_dtype"] != "bfloat16":
        raise ValueError("the serving path is measured in bfloat16")


def program_config(doc: dict) -> Any:
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp

    from cordum_tpu.models.llama import LlamaConfig

    validate(doc)
    return LlamaConfig(
        vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
        n_layers=doc["num_hidden_layers"], n_heads=doc["num_attention_heads"],
        n_kv_heads=doc["num_key_value_heads"], d_ff=doc["intermediate_size"],
        rope_theta=float(doc["rope_theta"]), norm_eps=float(doc["rms_norm_eps"]),
        max_seq_len=doc["max_position_embeddings"], dtype=jnp.bfloat16,
    )


def param_shapes(doc: dict) -> dict:
    """Leaf shapes in the layout the program reads (``models/llama.py``
    ``init_params``): ``{"embed", "layers": [{...}], "final_norm", "lm_head"}``."""
    d, f, v = doc["hidden_size"], doc["intermediate_size"], doc["vocab_size"]
    q = doc["num_attention_heads"] * doc["head_dim"]
    kv = doc["num_key_value_heads"] * doc["head_dim"]
    layer = {"attn_norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
             "mlp_norm": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return {"embed": (v, d), "layers": [dict(layer) for _ in range(doc["num_hidden_layers"])],
            "final_norm": (d,), "lm_head": (d, v)}


def n_params(doc: dict) -> int:
    import jax

    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple)))


def make_params(doc: dict, seed: int) -> dict:
    """Seeded weights on the default device, in bfloat16, from ONE jitted
    call: normal(0, 1/sqrt(fan_in)) matrices (the embedding scaled by
    1/sqrt(d) like the program's own init), norms at 1."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(doc)

    def draw(key, shape):
        if len(shape) == 1:
            return jnp.ones(shape, jnp.bfloat16)
        fan_in = doc["hidden_size"] if shape[0] == doc["vocab_size"] else shape[0]
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(jnp.bfloat16)

    @jax.jit
    def init(key):
        leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [draw(k, s) for k, s in zip(keys, leaves)])

    return init(jax.random.PRNGKey(int(seed)))


def make_workers(*, bus: Any, store: Any, cfg: Any, params: dict, pool: dict, seed: int) -> list:
    """The workers that serve this configuration: here one, on one chip.
    The prefix cache and hibernation stay at the worker's defaults;
    speculation is on unless the configuration's pool says otherwise."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.worker.handlers import attach_default_tpu_worker
    from cordum_tpu.worker.runtime import Worker

    worker = Worker(bus=bus, store=store, worker_id="bench-w1", pool="tpu",
                    topics=["job.tpu.>"], capabilities=["tpu"], heartbeat_interval_s=1.0)
    attach_default_tpu_worker(
        worker, llama_cfg=cfg, seed=seed % (2 ** 31), metrics=Metrics(),
        serving_cache_pages=pool["pages"], serving_page_size=pool["page_size"],
        serving_max_sessions=pool["max_sessions"],
        serving_prefill_budget=pool["prefill_budget"],
        serving_max_new_tokens=pool["max_new_tokens"],
        serving_speculative=pool.get("speculative", True))
    # the benchmark's own seeded weights: ``attach_default_tpu_worker`` takes
    # none, so the backend's provider is set here (PERF.md, Open questions: a
    # public argument in the program would end this reach into its state)
    worker.serving.backend._params_provider = lambda: params
    return [worker]


def free_device_state(workers: list) -> None:
    """Drop the page arenas, so the reference runs beside the weights alone."""
    for w in workers:
        be = w.serving.backend
        be._k_pages = be._v_pages = None
