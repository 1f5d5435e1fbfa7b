"""The ``axk1`` family (SKT A.X-K1: ``model_type: axk1``, latent attention and
a group-limited sigmoid router): how a configuration file of this family
becomes the program's model, and where its plain reference is.

Like ``families/afmoe.py`` this module maps the file's published keys onto
the program's config (``cordum_tpu.models.axk1.Axk1Config``), makes seeded
weights in the layout the program reads, and builds the one worker that
serves them through ``attach_default_tpu_worker``'s public arguments.  The
weights are the BENCHMARK's, handed to the program and to the reference
alike.

A file of this family states the chip's share of a deployment:
``n_routed_experts`` is the experts HELD here (``first_expert`` on),
``num_experts_routed`` the router's published width, ``vocab_size`` the slice
of the vocabulary.

It also keeps what the family's per-layer readers read.  The harness hands
readers ``stats_delta`` over ten counters of ``ServingStats``, none of them
the prefix cache's tokens, the walk's slots or the expert layer's, so
``make_workers`` wraps ``backend.step`` as ``families/afmoe.py`` does and
notes each step with its time: the rows, the program's expert counters, the
walk's computed and live query slots, and the engine's running prefix-cache
counters.  The records go into the afmoe family's ``STEPS`` (one list a
process, in that family's format with this family's keys beside it), so the
expert layer's four readers serve both families unedited; ``steps_in`` is
that module's.  Host work of this family's cells only: a list append a step.
"""
from __future__ import annotations

import math
import time
from typing import Any

from . import afmoe as _afmoe
from . import axk1_reference as reference  # noqa: F401 - the family's plain reference

REQUIRED_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "first_k_dense_replace", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "vocab_size",
    "max_position_embeddings", "n_routed_experts", "num_experts_routed", "first_expert",
    "num_experts_per_tok", "n_group", "topk_group", "n_shared_experts", "routed_scaling_factor",
    "norm_topk_prob", "scoring_func", "rope_theta", "rope_scaling", "rms_norm_eps",
    "tie_word_embeddings", "torch_dtype", "attention_bias",
)
#: what ``run.py --rehearse`` cannot know to shrink: the family's own widths
#: at the harness's tiny hidden size (64, 4 heads)
TINY_OWN = {"q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "moe_intermediate_size": 32}

#: one record per ``backend.step`` of this process (see the module docstring)
STEPS = _afmoe.STEPS
steps_in = _afmoe.steps_in


def settle(doc: dict) -> dict:
    """``run.py --rehearse`` overlays the llama family's tiny widths (2
    layers, hidden 64, 4 heads) on the file; bring this family's own ranks
    and head widths in line, IN PLACE (the run's copy of the file, which the
    reference reads too).  A file at its own sizes is left as it is."""
    if doc["hidden_size"] < doc["q_lora_rank"]:
        doc.update(TINY_OWN)
        doc["first_k_dense_replace"] = min(doc["first_k_dense_replace"],
                                           doc["num_hidden_layers"] - 1)
        doc["num_dense_layers"] = doc["first_k_dense_replace"]
    return doc


def validate(doc: dict) -> None:
    """Refuse a file the program's ``Axk1Config`` cannot express exactly."""
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration {doc.get('name')!r} lacks {missing}")
    if doc["tie_word_embeddings"] or doc["attention_bias"]:
        raise ValueError("Axk1Config has an untied head and no bias")
    if doc["scoring_func"] != "sigmoid" or (doc["rope_scaling"] or {}).get("type") != "yarn":
        raise ValueError("Axk1Config routes by sigmoid scores and rotates under YaRN")
    if doc.get("moe_layer_freq", 1) != 1:
        raise ValueError("every layer after the leading dense ones is an expert layer")
    if doc["first_expert"] + doc["n_routed_experts"] > doc["num_experts_routed"]:
        raise ValueError("the experts held lie outside the router's width")
    if doc["torch_dtype"] != "bfloat16":
        raise ValueError("the serving path is measured in bfloat16")


def program_config(doc: dict) -> Any:
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp

    from cordum_tpu.models.axk1 import Axk1Config

    validate(settle(doc))
    rs = doc["rope_scaling"]
    return Axk1Config(
        vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"], q_rank=doc["q_lora_rank"],
        kv_rank=doc["kv_lora_rank"], nope_dim=doc["qk_nope_head_dim"],
        rope_dim=doc["qk_rope_head_dim"], v_dim=doc["v_head_dim"],
        d_ff=doc["intermediate_size"], d_expert=doc["moe_intermediate_size"],
        n_layers=doc["num_hidden_layers"], n_dense_layers=doc["first_k_dense_replace"],
        n_experts=doc["num_experts_routed"], first_expert=doc["first_expert"],
        experts_held=doc["n_routed_experts"], top_k=doc["num_experts_per_tok"],
        n_group=doc["n_group"], topk_group=doc["topk_group"], n_shared=doc["n_shared_experts"],
        route_scale=float(doc["routed_scaling_factor"]), route_norm=bool(doc["norm_topk_prob"]),
        rope_theta=float(doc["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_len=int(rs["original_max_position_embeddings"]),
        rope_beta_fast=float(rs["beta_fast"]), rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]), rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=float(doc["rms_norm_eps"]), max_seq_len=doc["max_position_embeddings"],
        dtype=jnp.bfloat16,
    )


def layer_shapes(doc: dict, li: int) -> dict:
    d, h = doc["hidden_size"], doc["num_attention_heads"]
    qr, kr = doc["q_lora_rank"], doc["kv_lora_rank"]
    nope, rd, vd = doc["qk_nope_head_dim"], doc["qk_rope_head_dim"], doc["v_head_dim"]
    layer = {"norm_in": (d,), "norm_post": (d,), "q_norm": (qr,), "kv_norm": (kr,),
             "wqa": (d, qr), "wqb": (qr, h * (nope + rd)), "wkva": (d, kr + rd),
             "wkvb": (kr, h * (nope + vd)), "wo": (h * vd, d)}
    if li < doc["first_k_dense_replace"]:
        f = doc["intermediate_size"]
        layer.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        fe, held = doc["moe_intermediate_size"], doc["n_routed_experts"]
        fs = fe * doc["n_shared_experts"]
        layer.update(router=(d, doc["num_experts_routed"]),
                     e_gate=(held, d, fe), e_up=(held, d, fe), e_down=(held, fe, d),
                     s_gate=(d, fs), s_up=(d, fs), s_down=(fs, d))
    return layer


def param_shapes(doc: dict) -> dict:
    """Leaf shapes in the layout the program reads (``models/axk1.py``
    ``init_params``)."""
    settle(doc)
    d, v = doc["hidden_size"], doc["vocab_size"]
    return {"embed": (v, d),
            "layers": [layer_shapes(doc, li) for li in range(doc["num_hidden_layers"])],
            "final_norm": (d,), "lm_head": (d, v)}


def n_params(doc: dict) -> int:
    import jax

    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple)))


def make_params(doc: dict, seed: int) -> dict:
    """Seeded weights on the default device: normal(0, 1/sqrt(fan_in))
    matrices in bfloat16 (the embedding normal(0, 1): its rows enter the
    residual stream unscaled, as one of unit variance does after a norm),
    every norm's gain 1, no selection bias.  One jitted call a layer (the
    expert layers share one compile)."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(doc)

    def draw(key, name, shape):
        if len(shape) == 1:
            return jnp.ones(shape, jnp.bfloat16)
        fan_in = 1 if name == "embed" else shape[-2]
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(jnp.bfloat16)

    def init(key, tree):
        names = sorted(tree)
        keys = jax.random.split(key, len(names))
        return {n: draw(k, n, tree[n]) for n, k in zip(names, keys)}

    keys = jax.random.split(jax.random.PRNGKey(int(seed) % (2 ** 31)), len(shapes["layers"]) + 1)
    inits: dict = {}  # one jitted init per distinct set of shapes
    layers = []
    for key, tree in zip(keys, shapes["layers"]):
        sig = tuple(sorted(tree.items()))
        if sig not in inits:
            inits[sig] = jax.jit(lambda k, tree=tree: init(k, tree))
        layers.append(inits[sig](key))
    ends = {k: v for k, v in shapes.items() if k != "layers"}
    return {**jax.jit(lambda k: init(k, ends))(keys[-1]), "layers": layers}


def make_workers(*, bus: Any, store: Any, cfg: Any, params: dict, pool: dict, seed: int) -> list:
    """The workers that serve this configuration: here one, on one chip,
    with the prefix cache as the pool says (on: every layer's latent pages
    cover the whole row under one table).  Hibernation and migration cannot
    carry a latent page and stay off."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.worker.handlers import attach_default_tpu_worker
    from cordum_tpu.worker.runtime import Worker

    worker = Worker(bus=bus, store=store, worker_id="bench-w1", pool="tpu",
                    topics=["job.tpu.>"], capabilities=["tpu"], heartbeat_interval_s=1.0)
    attach_default_tpu_worker(
        worker, seed=seed % (2 ** 31), metrics=Metrics(),
        serving_model=cfg, serving_params=params,
        serving_cache_pages=pool["pages"],
        serving_page_size=pool["page_size"], serving_max_sessions=pool["max_sessions"],
        serving_prefill_budget=pool["prefill_budget"],
        serving_max_new_tokens=pool["max_new_tokens"],
        serving_speculative=pool.get("speculative", False),
        serving_prefix_cache=pool.get("prefix_cache", True))
    tap_steps(worker.serving)
    return [worker]


def tap_steps(engine: Any) -> None:
    """Note every step of ``engine``'s backend in ``STEPS``: the afmoe
    family's record (this family has no window kind: zeros there) and, under
    keys of this family's own, the walk's query slots and the engine's
    prefix-cache counters as they stood when the step returned."""
    be = engine.backend
    inner = be.step
    del STEPS[:]

    def tapped(entries):
        out = inner(entries)
        st = engine.stats
        STEPS.append({
            "at": time.monotonic(),
            "rows": [(len(e.tokens), e.start, e.draft + 1 if e.draft else int(e.sample))
                     for e in entries],
            "counters": dict(be.last_counters),
            "window_blocks": 0, "full_blocks": be.last_attn_blocks[0],
            "window_pages": 0, "full_pages": engine.allocator.used_pages,
            "slots_computed": be.last_attn_rows[1], "slots_live": be.last_attn_live,
            "prefix_hit_tokens": st.prefix_hit_tokens, "prefill_tokens": st.prefill_tokens,
            "prefix_hits": st.prefix_hits, "cow_copies": st.cow_copies,
        })
        return out
    be.step = tapped


def free_device_state(workers: list) -> None:
    """Drop the latent arena, so the reference runs beside the weights alone."""
    for w in workers:
        w.serving.backend.release_arenas()
