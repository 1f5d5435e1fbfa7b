"""The ``mellum`` family (JetBrains Mellum 2: ``model_type: mellum``, the
Qwen3-MoE line of keys): how a configuration file of this family becomes the
program's model, and where its plain reference is.

Like ``families/afmoe.py`` this module maps the file's published keys onto the
program's config (``cordum_tpu.models.mellum.MellumConfig``: a rotation a KIND
of layer from ``rope_parameters``) and makes seeded weights in the layout the
program reads; the weights are the BENCHMARK's, handed to the program and to
the reference alike.  The one worker, its tap and the freeing of the arenas
are that family's, unchanged: nothing in them knows a model (``make_workers``
hands the program's config and the weights to ``attach_default_tpu_worker``;
the tap notes each step's rows, the program's counters, the blocks both kinds
of walk read and the pages both allocators hold in the shared ``STEPS``, so
the expert layer's and the window's readers serve this family unedited).

A file of this family holds the expert set WHOLE: ``num_experts`` is both the
router's width and the experts held here (``first_expert`` 0), ``vocab_size``
the whole vocabulary.  What is cut is depth (``layer_types`` and
``mlp_layer_types`` with it) and context.
"""
from __future__ import annotations

import math
from typing import Any

from . import afmoe as _afmoe
from . import mellum_reference as reference  # noqa: F401 - the family's plain reference

REQUIRED_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "layer_types", "mlp_layer_types", "num_attention_heads", "num_key_value_heads", "head_dim",
    "vocab_size", "max_position_embeddings", "sliding_window", "use_sliding_window",
    "num_experts", "first_expert", "num_experts_per_tok", "norm_topk_prob", "rope_parameters",
    "rms_norm_eps", "attention_bias", "tie_word_embeddings", "torch_dtype",
)
KINDS = reference.KINDS
#: what ``run.py --rehearse`` cannot know to shrink: the family's own widths
#: at the harness's tiny hidden size
TINY_OWN = {"moe_intermediate_size": 32, "sliding_window": 64}

#: one record per ``backend.step`` of this process: the sparse families' one list
STEPS = _afmoe.STEPS
steps_in = _afmoe.steps_in
make_workers = _afmoe.make_workers
free_device_state = _afmoe.free_device_state


def settle(doc: dict) -> dict:
    """``run.py --rehearse`` overlays the llama family's tiny widths (2
    layers, hidden 64) on the file; bring this family's own keys in line, IN
    PLACE (the run's copy of the file, which the reference reads too): the
    kinds cut to the depth with a full layer last, a tiny expert width and
    window.  A file at its own sizes is left as it is."""
    n = doc["num_hidden_layers"]
    if len(doc["layer_types"]) != n:
        doc["layer_types"] = [KINDS[0]] * (n - 1) + [KINDS[1]]
        doc["mlp_layer_types"] = ["sparse"] * n
        doc.update(TINY_OWN)
    return doc


def validate(doc: dict) -> None:
    """Refuse a file the program's ``MellumConfig`` cannot express exactly."""
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration {doc.get('name')!r} lacks {missing}")
    if doc["tie_word_embeddings"] or doc["attention_bias"] or not doc["use_sliding_window"]:
        raise ValueError("MellumConfig has an untied head, no bias, and window layers")
    if not doc["norm_topk_prob"]:
        raise ValueError("MellumConfig's router normalises the selected scores")
    if set(doc["layer_types"]) - set(KINDS) or set(doc["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("layers are sliding or full attention, and every one is sparse")
    if len(doc["mlp_layer_types"]) != len(doc["layer_types"]):
        raise ValueError("mlp_layer_types is cut with layer_types")
    if set(doc["rope_parameters"]) != set(KINDS):
        raise ValueError("rope_parameters names a rotation for each kind of layer")
    for kind, rp in doc["rope_parameters"].items():
        if rp.get("rope_type", "default") not in ("default", "yarn"):
            raise ValueError(f"{kind} rotates by {rp['rope_type']!r}: default or yarn")
        if rp.get("rope_type") == "yarn" and "attention_factor" not in rp:
            raise ValueError(f"{kind} rotates under YaRN and states no attention_factor")
    if doc["first_expert"] != 0 or doc.get("num_dense_layers", 0) or doc.get("num_shared_experts", 0):
        raise ValueError("the expert set is held whole; no dense layer, no shared expert")
    if doc["torch_dtype"] != "bfloat16":
        raise ValueError("the serving path is measured in bfloat16")


def rotation(rp: dict) -> Any:
    """One kind's ``rope_parameters`` as the program's ``Rotation``."""
    from cordum_tpu.models.mellum import Rotation

    if rp.get("rope_type", "default") == "default":
        return Rotation(theta=float(rp["rope_theta"]))
    return Rotation(theta=float(rp["rope_theta"]), factor=float(rp["factor"]),
                    original_len=int(rp["original_max_position_embeddings"]),
                    beta_fast=float(rp["beta_fast"]), beta_slow=float(rp["beta_slow"]),
                    attention_factor=float(rp["attention_factor"]))


def program_config(doc: dict) -> Any:
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp

    from cordum_tpu.models.mellum import MellumConfig

    validate(settle(doc))
    return MellumConfig(
        vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"], n_kv_heads=doc["num_key_value_heads"],
        head_dim=doc["head_dim"], d_expert=doc["moe_intermediate_size"],
        n_layers=doc["num_hidden_layers"], layer_types=tuple(doc["layer_types"]),
        window=doc["sliding_window"], n_experts=doc["num_experts"],
        first_expert=doc["first_expert"], experts_held=doc["num_experts"],
        top_k=doc["num_experts_per_tok"],
        rope_sliding=rotation(doc["rope_parameters"][KINDS[0]]),
        rope_full=rotation(doc["rope_parameters"][KINDS[1]]),
        norm_eps=float(doc["rms_norm_eps"]), max_seq_len=doc["max_position_embeddings"],
        dtype=jnp.bfloat16,
    )


def param_shapes(doc: dict) -> dict:
    """Leaf shapes in the layout the program reads (``models/mellum.py``
    ``init_params``); every layer is of one shape."""
    settle(doc)
    d, v, hd = doc["hidden_size"], doc["vocab_size"], doc["head_dim"]
    q, kv = doc["num_attention_heads"] * hd, doc["num_key_value_heads"] * hd
    fe, n = doc["moe_intermediate_size"], doc["num_experts"]
    layer = {"norm_in": (d,), "norm_post": (d,), "q_norm": (hd,), "k_norm": (hd,),
             "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d), "router": (d, n),
             "e_gate": (n, d, fe), "e_up": (n, d, fe), "e_down": (n, fe, d)}
    return {"embed": (v, d), "layers": [dict(layer) for _ in range(doc["num_hidden_layers"])],
            "final_norm": (d,), "lm_head": (d, v)}


def n_params(doc: dict) -> int:
    import jax

    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple)))


def make_params(doc: dict, seed: int) -> dict:
    """Seeded weights on the default device: normal(0, 1/sqrt(fan_in))
    matrices in bfloat16 (the embedding normal(0, 1): its rows enter the
    residual stream unscaled, as one of unit variance does after a norm),
    every norm's gain 1, and nothing a trained model would have fitted: the
    router has no bias and none is drawn.  One jitted call a layer (all share
    one compile), so the float32 draws of one layer's 64 experts are the most
    that is held beside the weights."""
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
    init_layer = jax.jit(lambda k: init(k, shapes["layers"][0]))  # every layer is of one shape
    ends = {k: v for k, v in shapes.items() if k != "layers"}
    return {**jax.jit(lambda k: init(k, ends))(keys[-1]),
            "layers": [init_layer(k) for k in keys[:-1]]}
