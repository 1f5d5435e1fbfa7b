"""The ``xing`` family (XingChen-AGI Xing4.0: ``model_type: xing4_0``, the
DeepSeek-V3 line of keys plus the five ``hc_*`` / ``mhc_*`` keys of
manifold-constrained hyper-connections): how a configuration file of this
family becomes the program's model, and where its plain reference is.

The file's keys are A.X-K1's and the five more, so this module IS
``families/axk1.py`` where the two agree: that module's ``settle`` (the
rehearsal's tiny ranks), its checks of the published keys, its shapes of a
layer's attention, dense and expert weights, its one worker with the tap that
notes every step for the shared readers (``STEPS``: rows, the program's
counters, among them this family's ``mhc_slots`` / ``mhc_live``, the walk's
slots, the prefix cache's tokens) and its freeing of the arena.  What is this
family's own: the program's config (``cordum_tpu.models.xing.XingConfig``),
each sublayer's maps (``hc_attn`` / ``hc_ffn``: ``phi``, ``alpha``, ``bias``)
and the router's selection bias among the shapes, and how they are seeded.

A file of this family holds the expert set WHOLE (``n_routed_experts`` =
``num_experts_routed``, ``first_expert`` 0) and the whole vocabulary; what is
cut is depth and context.
"""
from __future__ import annotations

import math
from typing import Any

from . import axk1 as _axk1
from . import xing_reference as reference  # noqa: F401 - the family's plain reference

REQUIRED_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                 "mhc_h_res_clamp_max", "topk_method")
#: a layer's two sets of maps, by the sublayer they bracket (``models/xing.py`` ``MAPS``)
MAPS = ("hc_attn", "hc_ffn")
#: float32 leaves (the rest is bfloat16): the maps' scalars and the selection bias
#: (``hyper.init_params`` and ``make_params`` make them so; a test that only
#: describes the weights' shapes reads the names here)
FLOAT32 = ("alpha", "bias", "router_bias")

#: one record per ``backend.step`` of this process: the sparse families' one list
STEPS = _axk1.STEPS
steps_in = _axk1.steps_in
settle = _axk1.settle
make_workers = _axk1.make_workers
free_device_state = _axk1.free_device_state


def validate(doc: dict) -> None:
    """Refuse a file the program's ``XingConfig`` cannot express exactly."""
    _axk1.validate(doc)
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration {doc.get('name')!r} lacks {missing}")
    if doc["topk_method"] != "noaux_tc" or doc["n_group"] != 1 or doc["topk_group"] != 1:
        raise ValueError("XingConfig routes over one group with a selection bias (noaux_tc)")
    if doc["hc_mult"] < 1 or doc["hc_sinkhorn_iters"] < 1:
        raise ValueError("a stream and a Sinkhorn iteration at least")


def program_config(doc: dict) -> Any:
    """The program's own config object at the file's sizes: A.X-K1's fields
    as ``families/axk1.py`` maps them, and the maps' five numbers."""
    import dataclasses

    from cordum_tpu.models.xing import XingConfig

    validate(settle(doc))
    base = dataclasses.asdict(_axk1.program_config(doc))
    return XingConfig(
        **base, hc_mult=int(doc["hc_mult"]), hc_sinkhorn_iters=int(doc["hc_sinkhorn_iters"]),
        hc_eps=float(doc["hc_eps"]), hc_clamp_min=float(doc["mhc_h_res_clamp_min"]),
        hc_clamp_max=float(doc["mhc_h_res_clamp_max"]))


def maps_shapes(doc: dict) -> dict:
    """One sublayer's maps: ``phi`` [n (n + 2), n C], ``alpha`` [3], ``bias``
    [n (n + 2)]."""
    n = doc["hc_mult"]
    return {"phi": (n * (n + 2), n * doc["hidden_size"]), "alpha": (3,), "bias": (n * (n + 2),)}


def layer_shapes(doc: dict, li: int) -> dict:
    layer = {**_axk1.layer_shapes(doc, li), **{name: maps_shapes(doc) for name in MAPS}}
    if li >= doc["first_k_dense_replace"]:
        layer["router_bias"] = (doc["num_experts_routed"],)
    return layer


def param_shapes(doc: dict) -> dict:
    """Leaf shapes in the layout the program reads (``models/xing.py``
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
    """Seeded weights on the default device.  What the two families share is
    ``families/axk1.py``'s draw, called: normal(0, 1/sqrt(fan_in)) matrices in
    bfloat16 (the embedding normal(0, 1)), every norm's gain 1.  Beside it,
    one jitted call a layer, the maps, which draw NOTHING a trained model
    would have fitted and leave the token-dependent part to decide them:
    ``phi`` normal(0, 1/sqrt(n C)) in bfloat16 (``u^ phi^T`` of unit spread),
    ``alpha`` 1, ``b_pre = b_post = 0`` (``H_post`` 1 on average: every
    branch at unit gain), ``B_res = 2 I`` (a mixing that mostly keeps a
    stream).  The selection bias is ZERO, not fitted as
    ``families/bailing.py`` fits Ling's: that fit answers a KDA sublayer's
    common part, which sends a tenth of a drawn router's experts ten times
    their share; a latent-attention sublayer has none, and a drawn sigmoid
    router over a normed input routes as evenly as A.X-K1's and Mellum's do
    unbiased (``moe_load_imbalance``: PERF.md section 6, PR 49)."""
    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import hyper

    params = _axk1.make_params(doc, seed)
    hc = hyper.Hyper(n=doc["hc_mult"])
    maps = jax.jit(lambda key: {name: hyper.init_params(k, hc, doc["hidden_size"], jnp.bfloat16)
                                for name, k in zip(MAPS, jax.random.split(key))})
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31)), 49),
                            len(params["layers"]))
    layers = []
    for li, (layer, key) in enumerate(zip(params["layers"], keys)):
        layer = {**layer, **maps(key)}
        if li >= doc["first_k_dense_replace"]:
            layer["router_bias"] = jnp.zeros((doc["num_experts_routed"],), jnp.float32)
        layers.append(layer)
    return {**params, "layers": layers}
