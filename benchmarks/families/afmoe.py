"""The ``afmoe`` family (Arcee Trinity: ``model_type: afmoe``): how a
configuration file of this family becomes the program's model, and where its
plain reference is.

Like ``families/llama.py`` this module maps the file's published keys onto
the program's config (``cordum_tpu.models.afmoe.AfmoeConfig``), makes seeded
weights in the layout the program reads, and builds the one worker that
serves them, through ``attach_default_tpu_worker``'s public ``serving_model``
and ``serving_params`` arguments.  The weights are the BENCHMARK's, handed to
the program and to the reference alike.

A file of this family states the chip's share of a deployment: ``num_experts``
is the experts HELD here (``first_expert`` on), ``num_experts_routed`` the
router's published width, ``vocab_size`` the slice of the vocabulary.

It also keeps what the family's per-layer readers read.  The program counts
once (``ServingBackend.last_counters``, the numbers ``ServingStats`` sums and
the ``step`` span carries), but the harness hands readers neither a counter
of the expert layer nor an attribute of a span, so ``make_workers`` wraps
``backend.step`` and notes each step's counters with its time (``STEPS``);
``steps_in`` gives a reader the part inside the window or the traced slice.
Host work of this family's cells only: a list append a step.
"""
from __future__ import annotations

import math
import time
from typing import Any

from . import afmoe_reference as reference  # noqa: F401 - the family's plain reference

REQUIRED_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "num_dense_layers", "layer_types", "num_attention_heads", "num_key_value_heads", "head_dim",
    "vocab_size", "max_position_embeddings", "sliding_window", "num_experts",
    "num_experts_routed", "first_expert", "num_experts_per_tok", "num_shared_experts",
    "route_scale", "route_norm", "score_func", "rope_theta", "rms_norm_eps",
    "tie_word_embeddings", "torch_dtype", "mup_enabled",
)
KINDS = ("sliding_attention", "full_attention")
#: what ``run.py --rehearse`` cannot know to shrink: the family's own widths
#: at the harness's tiny hidden size
TINY_OWN = {"moe_intermediate_size": 32, "sliding_window": 64}

#: one record per ``backend.step`` of this process (see the module docstring)
STEPS: list[dict] = []


def settle(doc: dict) -> dict:
    """``run.py --rehearse`` overlays the llama family's tiny widths (2
    layers, hidden 64) on the file; bring this family's own keys in line, IN
    PLACE (the run's copy of the file, which the reference reads too): the
    kinds cut to the depth with a full layer last, one dense layer, a tiny
    expert width and window.  A file at its own sizes is left as it is."""
    n = doc["num_hidden_layers"]
    if len(doc["layer_types"]) != n:
        doc["layer_types"] = [KINDS[0]] * (n - 1) + [KINDS[1]]
        doc["num_dense_layers"] = min(doc["num_dense_layers"], n - 1)
        doc.update(TINY_OWN)
    return doc


def validate(doc: dict) -> None:
    """Refuse a file the program's ``AfmoeConfig`` cannot express exactly."""
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration {doc.get('name')!r} lacks {missing}")
    if doc["tie_word_embeddings"] or doc.get("rope_scaling"):
        raise ValueError("AfmoeConfig has an untied head and plain RoPE")
    if doc["score_func"] != "sigmoid" or not doc["mup_enabled"]:
        raise ValueError("AfmoeConfig routes by sigmoid scores and scales the embedding")
    if doc.get("n_group", 1) != 1 or doc.get("topk_group", 1) != 1:
        raise ValueError("AfmoeConfig has no expert groups")
    if set(doc["layer_types"]) - set(KINDS):
        raise ValueError(f"layer kinds {sorted(set(doc['layer_types']))}")
    if doc["first_expert"] + doc["num_experts"] > doc["num_experts_routed"]:
        raise ValueError("the experts held lie outside the router's width")
    if doc["torch_dtype"] != "bfloat16":
        raise ValueError("the serving path is measured in bfloat16")


def program_config(doc: dict) -> Any:
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp

    from cordum_tpu.models.afmoe import AfmoeConfig

    validate(settle(doc))
    return AfmoeConfig(
        vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"], n_kv_heads=doc["num_key_value_heads"],
        head_dim=doc["head_dim"], d_ff=doc["intermediate_size"],
        d_expert=doc["moe_intermediate_size"], n_layers=doc["num_hidden_layers"],
        n_dense_layers=doc["num_dense_layers"], layer_types=tuple(doc["layer_types"]),
        window=doc["sliding_window"], n_experts=doc["num_experts_routed"],
        first_expert=doc["first_expert"], experts_held=doc["num_experts"],
        top_k=doc["num_experts_per_tok"], n_shared=doc["num_shared_experts"],
        route_scale=float(doc["route_scale"]), route_norm=bool(doc["route_norm"]),
        rope_theta=float(doc["rope_theta"]), norm_eps=float(doc["rms_norm_eps"]),
        max_seq_len=doc["max_position_embeddings"], dtype=jnp.bfloat16,
    )


def layer_shapes(doc: dict, li: int) -> dict:
    d, hd = doc["hidden_size"], doc["head_dim"]
    q, kv = doc["num_attention_heads"] * hd, doc["num_key_value_heads"] * hd
    layer = {"norm_in": (d,), "norm_post_attn": (d,), "norm_pre_mlp": (d,), "norm_post_mlp": (d,),
             "q_norm": (hd,), "k_norm": (hd,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
             "wg": (d, q), "wo": (q, d)}
    if li < doc["num_dense_layers"]:
        f = doc["intermediate_size"]
        layer.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        fe, held = doc["moe_intermediate_size"], doc["num_experts"]
        fs = fe * doc["num_shared_experts"]
        layer.update(router=(d, doc["num_experts_routed"]),
                     router_bias=(doc["num_experts_routed"],),
                     e_gate=(held, d, fe), e_up=(held, d, fe), e_down=(held, fe, d),
                     s_gate=(d, fs), s_up=(d, fs), s_down=(fs, d))
    return layer


def param_shapes(doc: dict) -> dict:
    """Leaf shapes in the layout the program reads (``models/afmoe.py``
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
    matrices in bfloat16 (the embedding by 1/sqrt(d): the program scales it
    by sqrt(d)), norms at 1, the router's selection bias normal(0, 0.02) in
    float32.  One jitted call a layer (the four expert layers share one
    compile), so the float32 draws of one layer's experts are the most that
    is held beside the weights.

    With every norm's gain at 1 these weights route UNEVENLY (a row's tokens
    pick much the same experts: PERF.md section 6, PR 26).  Post-sublayer
    gains of 1/sqrt(60) cure that and were tried on the chip; they also make
    the int8 control all but vanish beside a router near-tie, so the check
    could no longer tell the two apart, and the gains stayed at 1."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(doc)
    d = doc["hidden_size"]

    def draw(key, name, shape):
        if name == "router_bias":
            return 0.02 * jax.random.normal(key, shape, jnp.float32)
        if len(shape) == 1:
            return jnp.ones(shape, jnp.bfloat16)
        fan_in = d if name == "embed" else shape[-2]
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
    """The workers that serve this configuration: here one, on one chip.
    The family's window layers switch the prefix cache, hibernation and
    migration off by themselves (``ModelSpec.window``)."""
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
        serving_speculative=pool.get("speculative", False))
    tap_steps(worker.serving)
    return [worker]


def tap_steps(engine: Any) -> None:
    """Note every step of ``engine``'s backend in ``STEPS``."""
    be = engine.backend
    inner = be.step
    del STEPS[:]

    def tapped(entries):
        out = inner(entries)
        STEPS.append({
            "at": time.monotonic(),
            "rows": [(len(e.tokens), e.start, e.draft + 1 if e.draft else int(e.sample))
                     for e in entries],
            "counters": dict(getattr(be, "last_counters", None) or {}),
            "window_blocks": getattr(be, "last_window_blocks", 0),
            "full_blocks": getattr(be, "last_attn_blocks", (0, 0))[0],
            "window_pages": engine.window_allocator.used_pages,
            "full_pages": engine.allocator.used_pages,
        })
        return out
    be.step = tapped


def steps_in(run: dict, part: str = "window") -> list[dict]:
    """The noted steps of the run's window, or of its traced slice; none on
    a run of another family (or of a program without the counters)."""
    if part == "slice":
        sl = run.get("slice") or {}
        if "t0" not in sl:
            return []
        t0, t1 = sl["t0"], sl["t1"]
    else:
        t0, t1 = run["t0"], run["t0"] + run["window_s"]
    return [s for s in STEPS if t0 <= s["at"] < t1 and s["counters"]]


def free_device_state(workers: list) -> None:
    """Drop the page arenas, so the reference runs beside the weights alone."""
    for w in workers:
        w.serving.backend.release_arenas()
