"""The ``longcat`` family (Meituan LongCat-Flash: a shortcut-connected block of
two latent-attention sublayers and two dense FFNs round one expert branch, a
softmax router over real and identity experts): how a configuration file of
this family becomes the program's model, and where its plain reference is.

Like ``families/axk1.py`` this module maps the file's published keys onto the
program's config (``cordum_tpu.models.longcat.LongcatConfig``) and makes
seeded weights in the layout the program reads; the weights are the
BENCHMARK's, handed to the program and to the reference alike.  The one
worker, its tap and the freeing of the arena are that family's, unchanged:
nothing in them knows a model (``make_workers`` hands the program's config
and the weights to ``attach_default_tpu_worker``; the tap notes each step's
rows, the program's counters, the walk's query slots and the prefix cache's
counters in the shared ``STEPS``, so the expert layer's and the walk's
readers serve this family unedited, and this family's own counters
(``moe_zero_assignments``, ``moe_real_picks_max`` / ``_min``) ride in the
same ``counters`` dict).

A file of this family states the chip's share of a deployment:
``n_routed_experts`` is the REAL experts HELD here (``first_expert`` on),
``num_experts_routed`` the router's whole width (the published real experts
and ``zero_expert_num`` identity experts behind them), ``vocab_size`` the
slice of the vocabulary.
"""
from __future__ import annotations

import math
from typing import Any

from . import axk1 as _axk1
from . import longcat_reference as reference  # noqa: F401 - the family's plain reference

REQUIRED_KEYS = (
    "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora", "vocab_size",
    "max_position_embeddings", "n_routed_experts", "num_experts_routed", "first_expert",
    "zero_expert_num", "zero_expert_type", "moe_topk", "routed_scaling_factor", "rope_theta",
    "rms_norm_eps", "attention_method", "attention_bias", "torch_dtype",
)
#: what ``run.py --rehearse`` cannot know to shrink: the family's own widths
#: at the harness's tiny hidden size (64, 4 heads)
TINY_OWN = {"q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "expert_ffn_hidden_size": 32, "moe_intermediate_size": 32}
#: a layer's two sublayers, as ``models/longcat.py`` has them
SUBLAYERS = 2

#: one record per ``backend.step`` of this process: the sparse families' one list
STEPS = _axk1.STEPS
steps_in = _axk1.steps_in
make_workers = _axk1.make_workers
free_device_state = _axk1.free_device_state


def settle(doc: dict) -> dict:
    """``run.py --rehearse`` overlays the llama family's tiny widths (2
    layers, hidden 64, 4 heads) on the file under ITS names; bring this
    family's own names and widths in line, IN PLACE (the run's copy of the
    file, which the reference reads too).  A file at its own sizes is left as
    it is."""
    if doc["hidden_size"] < doc["q_lora_rank"]:
        doc.update(TINY_OWN)
        doc["num_layers"] = doc["num_hidden_layers"]
        doc["ffn_hidden_size"] = doc["intermediate_size"]
    return doc


def validate(doc: dict) -> None:
    """Refuse a file the program's ``LongcatConfig`` cannot express exactly."""
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration {doc.get('name')!r} lacks {missing}")
    if doc["attention_bias"] or doc["attention_method"] != "MLA":
        raise ValueError("LongcatConfig has latent attention and no bias")
    if doc["zero_expert_type"] != "identity":
        raise ValueError("the router's zero-compute experts return their input")
    real = doc["num_experts_routed"] - doc["zero_expert_num"]
    if not 0 <= doc["first_expert"] <= doc["first_expert"] + doc["n_routed_experts"] <= real:
        raise ValueError("the experts held lie outside the router's real experts")
    if doc["torch_dtype"] != "bfloat16":
        raise ValueError("the serving path is measured in bfloat16")


def program_config(doc: dict) -> Any:
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp

    from cordum_tpu.models.longcat import LongcatConfig

    validate(settle(doc))
    return LongcatConfig(
        vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"], q_rank=doc["q_lora_rank"],
        kv_rank=doc["kv_lora_rank"], nope_dim=doc["qk_nope_head_dim"],
        rope_dim=doc["qk_rope_head_dim"], v_dim=doc["v_head_dim"],
        d_ff=doc["ffn_hidden_size"], d_expert=doc["expert_ffn_hidden_size"],
        n_layers=doc["num_layers"],
        n_experts=doc["num_experts_routed"] - doc["zero_expert_num"],
        n_identity=doc["zero_expert_num"], first_expert=doc["first_expert"],
        experts_held=doc["n_routed_experts"], top_k=doc["moe_topk"],
        route_scale=float(doc["routed_scaling_factor"]),
        scale_q=bool(doc["mla_scale_q_lora"]), scale_kv=bool(doc["mla_scale_kv_lora"]),
        rope_theta=float(doc["rope_theta"]), norm_eps=float(doc["rms_norm_eps"]),
        max_seq_len=doc["max_position_embeddings"], dtype=jnp.bfloat16,
    )


def layer_shapes(doc: dict) -> dict:
    d, h = doc["hidden_size"], doc["num_attention_heads"]
    qr, kr = doc["q_lora_rank"], doc["kv_lora_rank"]
    nope, rd, vd = doc["qk_nope_head_dim"], doc["qk_rope_head_dim"], doc["v_head_dim"]
    f, fe, held = doc["ffn_hidden_size"], doc["expert_ffn_hidden_size"], doc["n_routed_experts"]
    sub = {"norm_in": (d,), "norm_post": (d,), "q_norm": (qr,), "kv_norm": (kr,),
           "wqa": (d, qr), "wqb": (qr, h * (nope + rd)), "wkva": (d, kr + rd),
           "wkvb": (kr, h * (nope + vd)), "wo": (h * vd, d),
           "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return {"sub": [dict(sub) for _ in range(SUBLAYERS)],
            "router": (d, doc["num_experts_routed"]), "router_bias": (doc["num_experts_routed"],),
            "e_gate": (held, d, fe), "e_up": (held, d, fe), "e_down": (held, fe, d)}


def param_shapes(doc: dict) -> dict:
    """Leaf shapes in the layout the program reads (``models/longcat.py``
    ``init_params``)."""
    settle(doc)
    d, v = doc["hidden_size"], doc["vocab_size"]
    return {"embed": (v, d), "layers": [layer_shapes(doc) for _ in range(doc["num_layers"])],
            "final_norm": (d,), "lm_head": (d, v)}


def n_params(doc: dict) -> int:
    import jax

    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple)))


def make_params(doc: dict, seed: int) -> dict:
    """Seeded weights on the default device: normal(0, 1/sqrt(fan_in))
    matrices in bfloat16 (the embedding normal(0, 1): its rows enter the
    residual stream unscaled, as one of unit variance does after a norm),
    every norm's gain 1, the selection bias normal(0, 1 / router width) in
    float32; an up-projection behind a rank scaling (``wqb``, ``wkvb``) at
    1/sqrt(hidden_size), the spread the scaling is made for (``assumed`` in
    the file).  One jitted call a layer (the layers share one compile)."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(doc)
    scaled = {"wqb": doc["mla_scale_q_lora"], "wkvb": doc["mla_scale_kv_lora"]}

    def draw(key, name, shape):
        if name == "router_bias":
            return jax.random.normal(key, shape, jnp.float32) / shape[0]
        if len(shape) == 1:
            return jnp.ones(shape, jnp.bfloat16)
        fan_in = (1 if name == "embed" else doc["hidden_size"] if scaled.get(name)
                  else shape[-2])
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(jnp.bfloat16)

    def init(key, tree):
        names = sorted(tree)
        keys = jax.random.split(key, len(names))
        return {n: ([init(k, sub) for k, sub in zip(jax.random.split(k, len(tree[n])), tree[n])]
                    if isinstance(tree[n], list) else draw(k, n, tree[n]))
                for n, k in zip(names, keys)}

    keys = jax.random.split(jax.random.PRNGKey(int(seed) % (2 ** 31)), len(shapes["layers"]) + 1)
    init_layer = jax.jit(lambda k: init(k, shapes["layers"][0]))  # every layer alike
    ends = {k: v for k, v in shapes.items() if k != "layers"}
    return {**jax.jit(lambda k: init(k, ends))(keys[-1]),
            "layers": [init_layer(key) for key in keys[:-1]]}
