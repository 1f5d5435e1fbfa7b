"""The ``falcon_h1`` family (TII Falcon-H1: ``model_type: falcon_h1``, a
Mamba-2 state-space mixer and grouped-query attention side by side in every
layer, a multiplier on every branch): how a configuration file of this family
becomes the program's model, and where its plain reference is.

Like ``families/bailing.py`` this module maps the file's published keys onto
the program's config (``cordum_tpu.models.falcon_h1.FalconH1Config``), makes
seeded weights in the layout the program reads (the BENCHMARK's weights, handed
to the program and to the reference alike) and builds the one worker that
serves them.  The worker's drafter and prefix cache are OFF here by name: the
family keeps recurrent state in per-session slots, which can neither be shared
nor rolled back (``kv_positional``), and a worker asked for either refuses it.

A file of this family states one pipeline stage of a deployment:
``num_hidden_layers`` consecutive layers (``kept_layers`` names their published
indices; every layer is of the one kind), ``vocab_size`` the slice of the
vocabulary this chip's embedding and head hold.

**The spread of the weights** (:func:`spreads`): each matrix is drawn at the
standard deviation its published multiplier is made for, ``1 / (multiplier x
sqrt(fan_in))``, so that every branch has unit gain with its multiplier on
and the residual stream, the keys and the logits stay O(1) through the kept
layers.  Nothing a trained model would have fitted is drawn: norms' gains are
1, ``A`` / ``dt_bias`` / ``D`` as Mamba-2 initialises them (``assumed`` in the
file).

The step tap is ``families/bailing.py``'s (rows, the program's counters, the
state slots in use when the step returned), in the sparse families' one
``STEPS`` list, so ``state_slots_held_share`` reads this family's steps too.
"""
from __future__ import annotations

import math
from typing import Any

from . import bailing as _bailing
from . import falcon_h1_reference as reference  # noqa: F401 - the family's plain reference

REQUIRED_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers", "kept_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size",
    "max_position_embeddings", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
    "mamba_n_groups", "mamba_d_conv", "mamba_conv_bias", "mamba_proj_bias", "mamba_rms_norm",
    "mamba_norm_before_gate", "mamba_use_mlp", "attn_layer_indices", "attention_bias", "mlp_bias",
    "projectors_bias", "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_multipliers",
    "ssm_out_multiplier", "mlp_multipliers", "rope_theta", "rope_scaling", "rms_norm_eps",
    "tie_word_embeddings", "torch_dtype",
)
#: what ``run.py --rehearse`` cannot know to shrink: the family's own widths
#: at the harness's tiny hidden size (64, 4 heads of 16)
TINY_OWN = {"mamba_d_ssm": 64, "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
            "mamba_n_groups": 2, "kept_layers": [0, 1]}

#: one record per ``backend.step`` of this process: the sparse families' one list
STEPS = _bailing.STEPS
steps_in = _bailing.steps_in
tap_steps = _bailing.tap_steps
free_device_state = _bailing.free_device_state


def settle(doc: dict) -> dict:
    """``run.py --rehearse`` overlays the llama family's tiny widths on the
    file; bring the mixer's own widths and the kept layers in line, IN PLACE.
    A file at its own sizes is left as it is."""
    if doc["hidden_size"] < doc["mamba_d_ssm"]:
        doc.update(TINY_OWN)
    return doc


def validate(doc: dict) -> None:
    """Refuse a file the program's ``FalconH1Config`` cannot express exactly."""
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration {doc.get('name')!r} lacks {missing}")
    if doc["mamba_d_ssm"] != doc["mamba_n_heads"] * doc["mamba_d_head"]:
        raise ValueError("the mixer's width is its heads times their size")
    if doc["attn_layer_indices"] is not None or not doc["mamba_use_mlp"]:
        raise ValueError("FalconH1Config has attention and a feed-forward in every layer")
    if not doc["mamba_rms_norm"] or doc["mamba_norm_before_gate"] or not doc["mamba_conv_bias"]:
        raise ValueError("the mixer gates, then norms by group; its convolution has a bias")
    if (doc["mamba_proj_bias"] or doc["attention_bias"] or doc["mlp_bias"]
            or doc["projectors_bias"] or doc["tie_word_embeddings"] or doc["rope_scaling"]):
        raise ValueError("FalconH1Config has no other bias, an untied head and plain RoPE")
    if len(doc["kept_layers"]) != doc["num_hidden_layers"]:
        raise ValueError("kept_layers names the num_hidden_layers published layers held")
    if doc["torch_dtype"] != "bfloat16":
        raise ValueError("the serving path is measured in bfloat16")


def program_config(doc: dict) -> Any:
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp

    from cordum_tpu.models.falcon_h1 import FalconH1Config

    validate(settle(doc))
    return FalconH1Config(
        vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
        n_layers=doc["num_hidden_layers"], n_heads=doc["num_attention_heads"],
        n_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
        d_ff=doc["intermediate_size"], ssm_heads=doc["mamba_n_heads"],
        ssm_head_dim=doc["mamba_d_head"], ssm_state=doc["mamba_d_state"],
        ssm_groups=doc["mamba_n_groups"], conv_width=doc["mamba_d_conv"],
        embedding_multiplier=float(doc["embedding_multiplier"]),
        lm_head_multiplier=float(doc["lm_head_multiplier"]),
        attention_in_multiplier=float(doc["attention_in_multiplier"]),
        attention_out_multiplier=float(doc["attention_out_multiplier"]),
        key_multiplier=float(doc["key_multiplier"]),
        ssm_in_multiplier=float(doc["ssm_in_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in doc["ssm_multipliers"]),
        ssm_out_multiplier=float(doc["ssm_out_multiplier"]),
        mlp_multipliers=tuple(float(m) for m in doc["mlp_multipliers"]),
        rope_theta=float(doc["rope_theta"]), norm_eps=float(doc["rms_norm_eps"]),
        max_seq_len=doc["max_position_embeddings"], dtype=jnp.bfloat16,
    )


def in_spans(doc: dict) -> tuple[int, ...]:
    """Columns of ``W_in``'s five spans: ``z | x | B | C | dt``."""
    gn = doc["mamba_n_groups"] * doc["mamba_d_state"]
    return (doc["mamba_d_ssm"], doc["mamba_d_ssm"], gn, gn, doc["mamba_n_heads"])


def param_shapes(doc: dict) -> dict:
    """Leaf shapes in the layout the program reads (``models/falcon_h1.py``
    ``init_params``)."""
    settle(doc)
    d, f, v = doc["hidden_size"], doc["intermediate_size"], doc["vocab_size"]
    q = doc["num_attention_heads"] * doc["head_dim"]
    kv = doc["num_key_value_heads"] * doc["head_dim"]
    d_ssm, h = doc["mamba_d_ssm"], doc["mamba_n_heads"]
    conv = d_ssm + 2 * doc["mamba_n_groups"] * doc["mamba_d_state"]
    layer = {"norm_in": (d,), "w_qkv": (d, q + 2 * kv), "wo": (q, d),
             "w_in": (d, sum(in_spans(doc))), "conv_w": (doc["mamba_d_conv"], conv),
             "conv_b": (conv,), "a_log": (h,), "dt_bias": (h,), "d_skip": (h,),
             "ssm_norm": (d_ssm,), "w_out": (d_ssm, d),
             "norm_ff": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return {"embed": (v, d), "layers": [dict(layer) for _ in range(doc["num_hidden_layers"])],
            "final_norm": (d,), "lm_head": (d, v)}


def n_params(doc: dict) -> int:
    import jax

    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple)))


#: float32 leaves (the rest is bfloat16): the recurrence's three a head
FLOAT32 = ("a_log", "dt_bias", "d_skip")
#: Mamba-2's own initialisation (``assumed.ssm_init`` in the file)
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


def spreads(doc: dict) -> dict:
    """The standard deviation every matrix is drawn at, by leaf name: ``1 /
    (the multiplier its product meets x sqrt(fan_in))``; ``w_qkv`` and ``w_in``
    a value for each of their spans, in the columns' order."""
    d, q = doc["hidden_size"], doc["num_attention_heads"] * doc["head_dim"]
    a_in, root = doc["attention_in_multiplier"], math.sqrt(d)
    return {
        "embed": 1.0 / doc["embedding_multiplier"],
        "w_qkv": (1.0 / (a_in * root), 1.0 / (a_in * doc["key_multiplier"] * root),
                  1.0 / (a_in * root)),
        "wo": 1.0 / (doc["attention_out_multiplier"] * math.sqrt(q)),
        "w_in": tuple(1.0 / (doc["ssm_in_multiplier"] * m * root) for m in doc["ssm_multipliers"]),
        "w_out": 1.0 / (doc["ssm_out_multiplier"] * math.sqrt(doc["mamba_d_ssm"])),
        "w_gate": 1.0 / (doc["mlp_multipliers"][0] * root),
        "w_up": 1.0 / root,
        "w_down": 1.0 / (doc["mlp_multipliers"][1] * math.sqrt(doc["intermediate_size"])),
        "lm_head": 1.0 / (doc["lm_head_multiplier"] * root),
        "conv_w": 1.0 / math.sqrt(doc["mamba_d_conv"]),
    }


def make_params(doc: dict, seed: int) -> dict:
    """Seeded weights on the default device: every matrix normal(0,
    :func:`spreads`) in bfloat16, every norm's gain 1, the convolution's bias
    uniform in (-1/2, 1/2), and in float32 ``A_log`` = log of uniform [1, 16],
    ``dt_bias`` the inverse softplus of log-uniform [1e-3, 1e-1], ``D`` 1; one
    jitted call a distinct set of shapes."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(doc)
    std = spreads(doc)
    q = doc["num_attention_heads"] * doc["head_dim"]
    kv = doc["num_key_value_heads"] * doc["head_dim"]
    columns = {"w_qkv": (q, kv, kv), "w_in": in_spans(doc)}

    def draw(key, name, shape):
        if name == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
        if name == "dt_bias":
            dt0 = jnp.exp(jax.random.uniform(key, shape, jnp.float32, *map(math.log, DT_RANGE)))
            return dt0 + jnp.log(-jnp.expm1(-dt0))
        if name == "d_skip":
            return jnp.ones(shape, jnp.float32)
        if name == "conv_b":
            return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5).astype(jnp.bfloat16)
        if len(shape) == 1:
            return jnp.ones(shape, jnp.bfloat16)
        scale = std[name]
        if name in columns:  # a spread a span of the columns
            scale = jnp.concatenate([jnp.full((n,), s, jnp.float32)
                                     for n, s in zip(columns[name], scale)])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)

    def init(key, tree):
        names = sorted(tree)
        keys = jax.random.split(key, len(names))
        return {n: draw(k, n, tree[n]) for n, k in zip(names, keys)}

    keys = jax.random.split(jax.random.PRNGKey(int(seed) % (2 ** 31)), len(shapes["layers"]) + 1)
    init_layer = jax.jit(lambda k: init(k, shapes["layers"][0]))  # every layer is of one kind
    ends = {k: v for k, v in shapes.items() if k != "layers"}
    return {**jax.jit(lambda k: init(k, ends))(keys[-1]),
            "layers": [init_layer(k) for k in keys[:-1]]}


def make_workers(*, bus: Any, store: Any, cfg: Any, params: dict, pool: dict, seed: int) -> list:
    """The workers that serve this configuration: here one, on one chip, its
    drafter and prefix cache off by name (a state slot can be neither rolled
    back nor shared; the pool says so too)."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.worker.handlers import attach_default_tpu_worker
    from cordum_tpu.worker.runtime import Worker

    if pool.get("speculative") or pool.get("prefix_cache"):
        raise ValueError("this family's pool keeps the drafter and the prefix cache off")
    worker = Worker(bus=bus, store=store, worker_id="bench-w1", pool="tpu",
                    topics=["job.tpu.>"], capabilities=["tpu"], heartbeat_interval_s=1.0)
    attach_default_tpu_worker(
        worker, seed=seed % (2 ** 31), metrics=Metrics(),
        serving_model=cfg, serving_params=params,
        serving_cache_pages=pool["pages"],
        serving_page_size=pool["page_size"], serving_max_sessions=pool["max_sessions"],
        serving_prefill_budget=pool["prefill_budget"],
        serving_max_new_tokens=pool["max_new_tokens"],
        serving_speculative=False, serving_prefix_cache=False)
    tap_steps(worker.serving)
    return [worker]
