"""The ``bailing`` family (inclusionAI Ling-3.0-flash: ``model_type:
bailing_hybrid``, Kimi-delta linear-attention layers with every sixth layer
latent attention, a group-limited sigmoid router): how a configuration file of
this family becomes the program's model, and where its plain reference is.

Like ``families/axk1.py`` this module maps the file's published keys onto the
program's config (``cordum_tpu.models.bailing.BailingConfig``), makes seeded
weights in the layout the program reads (the BENCHMARK's weights, handed to
the program and to the reference alike) and builds the one worker that serves
them.  The worker's drafter and prefix cache are OFF here by name: the family
keeps recurrent state in per-session slots, which can neither be shared nor
rolled back (``kv_positional``), and a worker asked for either refuses it.

A file of this family states the chip's share of a deployment and the cut in
depth by PUBLISHED layer index: ``kept_layers`` lists the published layers
held (their kind follows from ``layer_group_size``, their feed-forward part
from ``first_k_dense_replace``, both as published), ``num_experts`` is the
experts HELD here (``first_expert`` on), ``num_experts_routed`` the router's
published width, ``vocab_size`` the slice of the vocabulary.

The step tap is ``families/axk1.py``'s (rows, the program's expert counters,
the latent walk's slots, in the sparse families' one ``STEPS`` list) with this
family's own keys beside them: the state slots in use when the step returned.
"""
from __future__ import annotations

import math
from typing import Any

from . import axk1 as _axk1
from . import bailing_reference as reference  # noqa: F401 - the family's plain reference

REQUIRED_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "num_hidden_layers", "kept_layers",
    "layer_group_size", "first_k_dense_replace", "num_attention_heads", "head_dim",
    "short_conv_kernel_size", "kda_lower_bound", "kda_safe_gate", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "vocab_size",
    "max_position_embeddings", "num_experts", "num_experts_routed", "first_expert",
    "num_experts_per_tok", "n_group", "topk_group", "num_shared_experts",
    "routed_scaling_factor", "norm_topk_prob", "score_function", "rope_theta", "rope_scaling",
    "rms_norm_eps", "tie_word_embeddings", "torch_dtype", "use_bias", "use_qkv_bias",
)
#: what ``run.py --rehearse`` cannot know to shrink: the family's own widths
#: at the harness's tiny hidden size (64, 4 heads of 16)
TINY_OWN = {"kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
            "num_experts": 16, "num_experts_routed": 64, "kept_layers": [1, 5],
            "num_dense_layers": 1}

#: one record per ``backend.step`` of this process: the sparse families' one list
STEPS = _axk1.STEPS
steps_in = _axk1.steps_in
free_device_state = _axk1.free_device_state


def settle(doc: dict) -> dict:
    """``run.py --rehearse`` overlays the llama family's tiny widths (2
    layers, hidden 64, 4 heads of 16) on the file; bring this family's own
    widths and its kept layers (a dense KDA layer, a latent expert layer) in
    line, IN PLACE.  A file at its own sizes is left as it is."""
    if doc["hidden_size"] < doc["kv_lora_rank"]:
        doc.update(TINY_OWN)
    return doc


def validate(doc: dict) -> None:
    """Refuse a file the program's ``BailingConfig`` cannot express exactly."""
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration {doc.get('name')!r} lacks {missing}")
    if doc["tie_word_embeddings"] or doc["use_bias"] or doc["use_qkv_bias"]:
        raise ValueError("BailingConfig has an untied head and no bias")
    if doc["score_function"] != "sigmoid" or doc["rope_scaling"] or doc["q_lora_rank"]:
        raise ValueError("BailingConfig routes by sigmoid scores, rotates plainly and has no "
                         "query latent")
    if not doc["kda_safe_gate"] or doc["kda_lower_bound"] >= 0:
        raise ValueError("the decay's gate is the bounded one: g in (kda_lower_bound, 0)")
    if len(doc["kept_layers"]) != doc["num_hidden_layers"]:
        raise ValueError("kept_layers names the num_hidden_layers published layers held")
    if doc["first_expert"] + doc["num_experts"] > doc["num_experts_routed"]:
        raise ValueError("the experts held lie outside the router's width")
    if doc["moe_shared_expert_intermediate_size"] != (
            doc["moe_intermediate_size"] * doc["num_shared_experts"]):
        raise ValueError("the shared expert is num_shared_experts experts wide")
    if doc["torch_dtype"] != "bfloat16":
        raise ValueError("the serving path is measured in bfloat16")


def layer_kinds(doc: dict) -> list[tuple[str, bool]]:
    """``(attention kind, dense FFN?)`` of every kept layer, from its
    published index (the reference has the same rule in its own words)."""
    return [("mla" if (i + 1) % doc["layer_group_size"] == 0 else "kda",
             i < doc["first_k_dense_replace"]) for i in doc["kept_layers"]]


def program_config(doc: dict) -> Any:
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp

    from cordum_tpu.models.bailing import BailingConfig

    validate(settle(doc))
    kinds = layer_kinds(doc)
    return BailingConfig(
        vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"], kda_dk=doc["head_dim"], kda_dv=doc["head_dim"],
        conv_width=doc["short_conv_kernel_size"], kda_lower_bound=float(doc["kda_lower_bound"]),
        kv_rank=doc["kv_lora_rank"], nope_dim=doc["qk_nope_head_dim"],
        rope_dim=doc["qk_rope_head_dim"], v_dim=doc["v_head_dim"],
        d_ff=doc["intermediate_size"], d_expert=doc["moe_intermediate_size"],
        layer_kinds=tuple(kind for kind, _ in kinds),
        dense_layers=tuple(i for i, (_, dense) in enumerate(kinds) if dense),
        n_experts=doc["num_experts_routed"], first_expert=doc["first_expert"],
        experts_held=doc["num_experts"], top_k=doc["num_experts_per_tok"],
        n_group=doc["n_group"], topk_group=doc["topk_group"], n_shared=doc["num_shared_experts"],
        route_scale=float(doc["routed_scaling_factor"]), route_norm=bool(doc["norm_topk_prob"]),
        rope_theta=float(doc["rope_theta"]), norm_eps=float(doc["rms_norm_eps"]),
        max_seq_len=doc["max_position_embeddings"], dtype=jnp.bfloat16,
    )


def layer_shapes(doc: dict, kind: str, dense: bool) -> dict:
    d, h, hd = doc["hidden_size"], doc["num_attention_heads"], doc["head_dim"]
    layer = {"norm_in": (d,), "norm_post": (d,)}
    if kind == "kda":
        layer.update(w_qkv=(d, 3 * h * hd), conv_w=(doc["short_conv_kernel_size"], 3 * h * hd),
                     w_a=(d, h * hd), a_log=(h,), a_bias=(h * hd,), w_beta=(d, h), w_g=(d, h),
                     o_norm=(hd,), wo=(h * hd, d))
    else:
        kr, nope = doc["kv_lora_rank"], doc["qk_nope_head_dim"]
        rd, vd = doc["qk_rope_head_dim"], doc["v_head_dim"]
        layer.update(kv_norm=(kr,), wq=(d, h * (nope + rd)), wkva=(d, kr + rd),
                     wkvb=(kr, h * (nope + vd)), wg=(d, h), wo=(h * vd, d))
    if dense:
        f = doc["intermediate_size"]
        layer.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        fe, held = doc["moe_intermediate_size"], doc["num_experts"]
        fs = doc["moe_shared_expert_intermediate_size"]
        layer.update(router=(d, doc["num_experts_routed"]),
                     router_bias=(doc["num_experts_routed"],),
                     e_gate=(held, d, fe), e_up=(held, d, fe), e_down=(held, fe, d),
                     s_gate=(d, fs), s_up=(d, fs), s_down=(fs, d))
    return layer


def param_shapes(doc: dict) -> dict:
    """Leaf shapes in the layout the program reads (``models/bailing.py``
    ``init_params``)."""
    settle(doc)
    d, v = doc["hidden_size"], doc["vocab_size"]
    return {"embed": (v, d),
            "layers": [layer_shapes(doc, kind, dense) for kind, dense in layer_kinds(doc)],
            "final_norm": (d,), "lm_head": (d, v)}


def n_params(doc: dict) -> int:
    import jax

    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple)))


#: float32 leaves (the rest is bfloat16): the decay's two and the selection bias
FLOAT32 = ("a_log", "a_bias", "router_bias")
#: the decay's seeded spread (``assumed.decay`` in the file): ``exp(A_h)``
#: uniform in [0.5, 1], ``b`` uniform in [-9, -1.5] a channel
A_RANGE = (0.5, 1.0)
B_RANGE = (-9.0, -1.5)


def make_params(doc: dict, seed: int) -> dict:
    """Seeded weights on the default device: normal(0, 1/sqrt(fan_in))
    matrices in bfloat16 (the embedding normal(0, 1), as the other latent
    families'), the convolution's taps normal(0, 1/sqrt(width)), every norm's
    gain 1, and the decay's ``a_log`` / ``a_bias`` in float32 over the spread
    the file states; one jitted call a distinct set of shapes.  The selection
    bias is drawn at 0 and then FITTED (:func:`balance_routers`)."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(doc)

    def draw(key, name, shape):
        if name == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
        if name == "a_bias":
            return jax.random.uniform(key, shape, jnp.float32, *B_RANGE)
        if name == "router_bias":
            return jnp.zeros(shape, jnp.float32)
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
    return balance_routers(
        doc, {**jax.jit(lambda k: init(k, ends))(keys[-1]), "layers": layers}, seed)


#: the sequences the selection bias is fitted on, their length (cut to half
#: the file's context), and the fit's passes
BALANCE_ROWS = 4
BALANCE_TOKENS = 1024
BALANCE_PASSES = 120


def balance_routers(doc: dict, params: dict, seed: int) -> dict:
    """Fit every expert layer's selection bias so that the routed experts'
    shares of the picks are even, as ``noaux_tc`` fits it while a model is
    trained: a pass moves an expert's bias against its load's excess over the
    even share (by at most ``step``, which shrinks over the passes), layer
    by layer over seeded sequences through the family's float32 reference,
    each layer fed what the fitted layers before it gave.

    Why it is fitted and not drawn: a seeded KDA sublayer's output has a
    large part common to every token (its values are SiLU's, of positive
    mean), an eighth of the router's input by norm, so a drawn router sends a
    tenth of its experts ten times the even share, WHICH ones is the seed's,
    and with them how many of the held experts a step touches and so how long
    it takes (PERF.md section 6, PR 40).  A trained router of this kind is
    balanced by that very bias."""
    import random

    import jax
    import jax.numpy as jnp

    n_tokens = min(BALANCE_TOKENS, doc["max_position_embeddings"] // 2)
    rng = random.Random(int(seed) + 2)  # the run's and the warm-up's ids are seed's and seed + 1's
    rows = [jnp.asarray([rng.randrange(1, doc["vocab_size"]) for _ in range(n_tokens)], jnp.int32)
            for _ in range(BALANCE_ROWS)]
    ref = reference.Reference(doc, n_tokens)
    n = doc["num_experts_routed"]
    even = BALANCE_ROWS * n_tokens * doc["num_experts_per_tok"] / n

    @jax.jit
    def fit(m, router):
        def one_pass(i, bias):
            sel, _ = reference.route(m, router, bias, **ref.route_kw)
            load = jnp.zeros((n,), jnp.float32).at[sel.reshape(-1)].add(1.0)
            step = 0.02 * (1.0 - i / BALANCE_PASSES) + 0.001
            return bias - step * jnp.clip(load / even - 1.0, -1.0, 1.0)
        return jax.lax.fori_loop(0, BALANCE_PASSES, one_pass, jnp.zeros((n,), jnp.float32))

    xs = [ref.embed(params, row) for row in rows]
    last = max(li for li, w in enumerate(params["layers"]) if "router" in w)
    layers = []
    for li, w in enumerate(params["layers"]):
        if "router" in w:
            x1, m = (jnp.concatenate(part) for part in
                     zip(*(ref.attention_part(x, w, li) for x in xs)))
            w = {**w, "router_bias": fit(m, w["router"])}
            if li < last:  # nothing is fitted behind the last router
                xs = jnp.split(x1 + ref.expert_part(m, w), BALANCE_ROWS)
        elif li < last:
            xs = [ref.layer(x, w, li) for x in xs]
        layers.append(w)
    return {**params, "layers": layers}


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


def tap_steps(engine: Any) -> None:
    """``families/axk1.py``'s tap, and beside each of its records the state
    slots sessions held when the step returned, of the slots there are."""
    _axk1.tap_steps(engine)
    be = engine.backend
    inner = be.step

    def tapped(entries):
        out = inner(entries)
        STEPS[-1].update(state_slots=engine.state_allocator.used,
                         state_slots_total=engine.state_allocator.capacity)
        return out
    be.step = tapped
