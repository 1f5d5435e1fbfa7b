"""AFMoE decoder (Arcee Trinity family) on the serving path.

What the block has that ``models/llama`` has not (docs/SERVING.md §Model
seam, §Two kinds of page, §The expert layer):

  * **sandwich norms** — RMSNorm before AND after attention and before and
    after the feed-forward part; per-head RMSNorm of q and k; the token
    embedding scaled by ``sqrt(d_model)``;
  * **a gated attention output** — ``(o * sigmoid(a Wg)) Wo``;
  * **window and full attention layers in one model** — a window layer
    rotates q and k (RoPE) and sees the last ``window`` keys, a full layer
    has no positional encoding and sees the whole row.  Each kind has its
    own pair of page arenas: the full kind's page tables reach the whole
    context, the window kind's are rings (``attention.window_ring_pages``), so a
    window layer holds a bounded number of pages per sequence;
  * **a dropless expert layer that is told which experts it holds** — the
    sigmoid router scores every token over all ``n_experts`` in float32,
    picks ``top_k`` by score plus a selection-only bias, and this chip
    computes the part of the weighted sum that ITS experts
    (``first_expert .. first_expert + experts_held``) give, as grouped
    products over the assignments sorted by expert (:func:`grouped_products`:
    one Pallas kernel over the touched experts where the program is lowered
    for the TPU, ``models/expert_mlp.py``; ``jax.lax.ragged_dot`` on every
    other platform).  No capacity, so no token is ever dropped; what absent experts
    would add is left out (their chips add it in a deployment), the weights
    still normalised over all ``top_k`` selected.  The shared expert is
    whole on every chip.  Other families' routers are the same code under
    other settings (``models/axk1.py``: groups; ``models/longcat.py``:
    softmax scores, no shared expert, and ``n_identity`` identity experts
    behind the real ones in the router, whose picks add ``w x m`` and take
    no row of the grouped products).

The training-side ``models/moe.py`` is a different layer (capacity-bounded
one-hot dispatch that drops tokens); nothing here uses it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from .attention import (arena_pos_bytes, attn_block_pages, init_kv_pages, paged_attention,
                        walk_label)
from .llama import rms_norm, rope

Params = dict
#: numbers behind a layer's counts where the router has identity experts
#: (:func:`expert_layer`): picks of them, most and fewest real picks a token
IDENTITY_COUNTS = 3

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 128  # the leading dense layers' SwiGLU width
    d_expert: int = 32  # every routed expert's and the shared expert's width
    n_layers: int = 3
    n_dense_layers: int = 1
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, FULL)
    window: int = 32
    n_experts: int = 16  # the router's width: experts of the whole layer
    first_expert: int = 0  # this chip holds [first_expert, first_expert + experts_held)
    experts_held: int = 16
    top_k: int = 2
    # group-limited selection (:func:`route`): the experts in ``n_group``
    # equal groups, a token's picks inside its ``topk_group`` best; 1 and 1
    # is no limit (this family's published router)
    n_group: int = 1
    topk_group: int = 1
    n_shared: int = 1
    n_identity: int = 0  # identity experts behind the real ones in the router (:func:`route`)
    route_score: str = "sigmoid"
    route_scale: float = 2.448
    route_norm: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 256
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if len(self.layer_types) != self.n_layers or not set(self.layer_types) <= {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types} for {self.n_layers} layers")
        if not 0 <= self.first_expert <= self.first_expert + self.experts_held <= self.n_experts:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + self.experts_held}) "
                f"held of {self.n_experts}")
        if self.n_heads % self.n_kv_heads or not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("heads must group evenly; dense layers lead")
        check_routing(self)

    @property
    def window_layers(self) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == SLIDING)

    @property
    def full_layers(self) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == FULL)

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    def serving_spec(self) -> Any:
        return serving_spec(self)


def check_routing(cfg: Any) -> None:
    """Refuse a router :func:`route` cannot select under."""
    if cfg.route_score not in ("sigmoid", "softmax"):
        raise ValueError(f"scores by {cfg.route_score!r}: sigmoid or softmax")
    if cfg.n_identity < 0 or (cfg.n_identity and cfg.n_group > 1):
        raise ValueError("identity experts lie behind the real ones, outside any group")
    if cfg.n_experts % cfg.n_group or not 1 <= cfg.topk_group <= cfg.n_group:
        raise ValueError(f"{cfg.n_experts} experts in {cfg.n_group} groups, "
                         f"{cfg.topk_group} kept")
    if cfg.n_group > 1 and (cfg.n_experts // cfg.n_group < 2
                            or cfg.topk_group * (cfg.n_experts // cfg.n_group) < cfg.top_k):
        raise ValueError("a group needs two experts to score by, the kept groups top_k to pick")


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: AfmoeConfig) -> Params:
    """Seeded weights: normal(0, 1/sqrt(fan_in)) matrices, norms at 1, a
    small selection bias on the router (so that it decides some picks)."""
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    fe, held = cfg.d_expert, cfg.experts_held
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(cfg.dtype)

    ones = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], 16)
        layer = {
            "norm_in": ones(d), "norm_post_attn": ones(d),
            "norm_pre_mlp": ones(d), "norm_post_mlp": ones(d),
            "q_norm": ones(hd), "k_norm": ones(hd),
            "wq": dense(lk[0], (d, h * hd), d), "wk": dense(lk[1], (d, kvh * hd), d),
            "wv": dense(lk[2], (d, kvh * hd), d), "wg": dense(lk[3], (d, h * hd), d),
            "wo": dense(lk[4], (h * hd, d), h * hd),
        }
        if i < cfg.n_dense_layers:
            layer.update(w_gate=dense(lk[5], (d, cfg.d_ff), d), w_up=dense(lk[6], (d, cfg.d_ff), d),
                         w_down=dense(lk[7], (cfg.d_ff, d), cfg.d_ff))
        else:
            fs = fe * cfg.n_shared
            layer.update(
                router=dense(lk[5], (d, cfg.n_experts), d),
                router_bias=0.02 * jax.random.normal(lk[6], (cfg.n_experts,), jnp.float32),
                e_gate=dense(lk[7], (held, d, fe), d), e_up=dense(lk[8], (held, d, fe), d),
                e_down=dense(lk[9], (held, fe, d), fe),
                s_gate=dense(lk[10], (d, fs), d), s_up=dense(lk[11], (d, fs), d),
                s_down=dense(lk[12], (fs, d), fs))
        layers.append(layer)
    return {"embed": dense(keys[-2], (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": ones(d), "lm_head": dense(keys[-1], (d, cfg.vocab_size), d)}


def init_arenas(cfg: AfmoeConfig, num_pages: int, page_size: int, window_pages: int) -> tuple:
    """``(k, v, window k, window v)``: the full layers' arena pair over
    ``num_pages`` pages and the window layers' over ``window_pages``."""
    return (*init_kv_pages(cfg, num_pages, page_size, n_layers=len(cfg.full_layers)),
            *init_kv_pages(cfg, window_pages, page_size, n_layers=len(cfg.window_layers)))


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def route(m: jax.Array, layer: Params, cfg: Any) -> tuple[jax.Array, jax.Array]:
    """Every token over the WHOLE router, in float32: ``(sel [T, k] expert
    ids, w [T, k] weights)``.  The selection bias, where the layer has one,
    takes part in the selection only; the weights are the selected scores
    (``cfg.route_score``: each expert's sigmoid, or a softmax over the
    router's width), normalised over the k selected (held here or not) where
    ``cfg.route_norm`` says so, and scaled.

    THE selection code of every sparse family (``cfg``: an ``AfmoeConfig``,
    or another family's config with the same routing fields).  The router is
    ``n_experts + n_identity`` wide: ids below ``n_experts`` are real
    experts, the ``n_identity`` behind them return their input
    (:func:`expert_layer`).  With ``cfg.n_group`` > 1 the selection is
    group-limited: the experts lie in ``n_group`` equal groups, a group's
    score is the sum of its two best experts', and a token picks its
    ``top_k`` among the experts of its ``topk_group`` best groups only, so
    its experts span at most that many groups (in a deployment: chips).
    Neither step sorts (scope ``moe_group_select``).  A group's score is two
    maximum passes: its best, then the best of what is left when the FIRST
    position that holds the best is taken out, one position and not every
    equal of the best, so two equal best scores sum to twice the best as
    the descending pair of a ``top_k`` would.  A group is kept iff fewer than
    ``topk_group`` groups beat it, where ``j`` beats ``g`` with the larger
    score or, at equal scores, the lower index: ``jax.lax.top_k``'s own order,
    so the kept set is the one a ``top_k`` over the groups names, ties and a
    padding row's all-equal scores included (tests/test_axk1_serving.py holds
    ``sel`` and ``w`` to that form element for element).
    Sigmoid scores, one group and no identity experts is the program it
    always was."""
    logits = jnp.matmul(
        m.astype(jnp.float32), layer["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if cfg.route_score == "softmax"
              else jax.nn.sigmoid(logits))
    pick = scores + layer["router_bias"] if "router_bias" in layer else scores
    if cfg.n_group > 1:
        with jax.named_scope("moe_group_select"):
            t, n = pick.shape
            per = n // cfg.n_group
            by_group = pick.reshape(t, cfg.n_group, per)
            best = jnp.max(by_group, axis=-1)
            first = jnp.argmax(by_group, axis=-1)  # ONE position: an equal score behind it stays
            second = jnp.max(
                jnp.where(jnp.arange(per) == first[..., None], -jnp.inf, by_group), axis=-1)
            score = best + second  # [T, n_group]
            ours, theirs = score[:, :, None], score[:, None, :]
            g = jnp.arange(cfg.n_group)
            beaten_by = (theirs > ours) | ((theirs == ours) & (g[None, :] < g[:, None]))
            keep = jnp.sum(beaten_by, axis=-1) < cfg.topk_group
            pick = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(t, n)
    _, sel = jax.lax.top_k(pick, cfg.top_k)
    w = jnp.take_along_axis(scores, sel, axis=1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel, w * cfg.route_scale


def ragged_products(xs: jax.Array, e_gate: jax.Array, e_up: jax.Array, e_down: jax.Array,
                    counts: jax.Array) -> jax.Array:
    """The grouped products as three ``jax.lax.ragged_dot`` calls: ``xs`` [R,
    d] sorted by held expert, ``counts`` [held] rows a group -> float32 [R,
    d], ``(silu(x Wg) * (x Wu)) Wd`` under each row's expert."""
    gate = jax.lax.ragged_dot(xs, e_gate, counts)
    up = jax.lax.ragged_dot(xs, e_up, counts)
    return jax.lax.ragged_dot(jax.nn.silu(gate) * up, e_down, counts,
                              preferred_element_type=jnp.float32)


def grouped_products(xs: jax.Array, e_gate: jax.Array, e_up: jax.Array, e_down: jax.Array,
                     counts: jax.Array) -> jax.Array:
    """The same products by the form the lowering platform holds: one Pallas
    kernel that reads only the touched experts (``models/expert_mlp.py``)
    where the program is lowered for the TPU and a block of these experts
    fits its VMEM, :func:`ragged_products` everywhere else.  The platform
    and the operands' shapes decide, nothing else."""
    from . import expert_mlp  # imports Pallas: only a program with an expert layer pays

    if not expert_mlp.fits(*e_gate.shape[1:], e_gate.dtype.itemsize):
        return ragged_products(xs, e_gate, e_up, e_down, counts)
    return jax.lax.platform_dependent(
        xs, e_gate, e_up, e_down, counts,
        default=ragged_products, **{expert_mlp.PLATFORM: expert_mlp.expert_mlp})


def expert_layer(
    m: jax.Array, layer: Params, cfg: Any, live: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """This chip's part of the expert layer for ``m`` [T, d]: the shared
    expert (where the family has one) plus the weighted outputs of the HELD
    real experts, float32 [T, d]; and the assignments each held expert got,
    int32 [experts_held].  Slots with ``live`` false (buffer padding) route
    nowhere.  Dropless: every assignment to a held expert is computed,
    whatever the imbalance — the grouped products (:func:`grouped_products`)
    take all ``T * top_k`` assignment rows, sorted by expert, with the rows
    of experts held elsewhere (and of identity experts) behind the last group.

    With ``cfg.n_identity`` identity experts in the router a pick of one adds
    ``w x m`` (``m`` as it came, float32 in the step) and costs neither a row
    of the grouped products nor a weight read.  That part needs no weights
    and no exchange: every chip computes it alike for its own tokens, so it
    is counted ONCE when the chips' shares are added up, as a shared expert
    is.  ``IDENTITY_COUNTS`` numbers then ride behind the counts (int32
    [experts_held + 3]): picks of identity experts by live tokens, and the
    most and the fewest REAL experts a live token picked."""
    t, k, held = m.shape[0], cfg.top_k, cfg.experts_held
    with jax.named_scope("moe_route"):
        sel, w = route(m, layer, cfg)  # from m as it came: float32 in the step
    m_in = m
    m = m.astype(layer["e_gate"].dtype)
    with jax.named_scope("moe_sort"):
        local = sel - cfg.first_expert
        here = (local >= 0) & (local < held) & live[:, None]  # [T, k]
        group = jnp.where(here, local, held).reshape(t * k)
        order = jnp.argsort(group, stable=True)
        counts = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        xs = m[order // k]  # [T * k, d], grouped by held expert
    with jax.named_scope("moe_experts"):
        ys = grouped_products(xs, layer["e_gate"], layer["e_up"], layer["e_down"], counts)
    if cfg.n_shared:
        with jax.named_scope("moe_shared"):
            shared = jnp.matmul(
                jax.nn.silu(m @ layer["s_gate"]) * (m @ layer["s_up"]), layer["s_down"],
                preferred_element_type=jnp.float32)
    with jax.named_scope("moe_combine"):
        # back to assignment order; rows past the last group (experts held
        # elsewhere, identity experts, padding) hold nothing the grouped
        # product defines
        ys = ys[jnp.argsort(order)].reshape(t, k, -1)
        out = jnp.sum(jnp.where(here[..., None], ys * w[..., None], 0.0), axis=1)
    if cfg.n_shared:
        out = shared + out
    if cfg.n_identity:
        with jax.named_scope("moe_identity"):
            zero = sel >= cfg.n_experts  # [T, k]: the router's ids behind the real experts
            out = out + (jnp.sum(jnp.where(zero, w, 0.0), axis=1, keepdims=True)
                         * m_in.astype(jnp.float32))
            real = k - jnp.sum(zero, axis=1, dtype=jnp.int32)  # [T]
            counts = jnp.concatenate([counts, jnp.stack([
                jnp.sum(zero & live[:, None], dtype=jnp.int32),
                jnp.max(jnp.where(live, real, 0)), jnp.min(jnp.where(live, real, k))])])
    return out, counts


# ---------------------------------------------------------------------------
# the ragged serving step
# ---------------------------------------------------------------------------


def ragged_step(
    params: Params,
    k_pages: jax.Array,
    v_pages: jax.Array,
    wk_pages: jax.Array,
    wv_pages: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    window_tables: jax.Array,
    token_seq: jax.Array,
    out_idx: jax.Array,
    cfg: AfmoeConfig,
    *,
    sample_logits: bool = True,
) -> tuple[jax.Array, ...]:
    """One ragged mixed prefill+decode step (the contract of
    ``llama.ragged_step``) over TWO kinds of page: ``k_pages``/``v_pages``
    with ``page_tables`` [S+1, P] for the full layers, ``wk_pages``/
    ``wv_pages`` with the ring tables ``window_tables`` [S+1, R] for the
    window layers.  Returns ``(out, k_pages, v_pages, wk_pages, wv_pages)``
    where ``out`` int32 [T + expert layers x experts_held] is the per-slot
    next-token argmax followed by the assignments each held expert got in
    each expert layer — one array, so one transfer."""
    t_buf = tokens.shape[0]
    h, kvh, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    ps = k_pages.shape[2]
    ring = window_tables.shape[1]
    pos2 = positions[:, None]
    live = token_seq < page_tables.shape[0] - 1  # the last row is the padding row
    page_idx = page_tables[token_seq, positions // ps]  # [T]
    wpage_idx = window_tables[token_seq, (positions // ps) % ring]
    slot = positions % ps
    # a block a kind of page, each from its own arenas' bytes a position
    block_pages, wblock_pages = (
        attn_block_pages(ps, page_tables.shape[1],
                         arena_pos_bytes((kp.shape[3:], vp.shape[3:]), kp.dtype.itemsize),
                         h, kvh, hd)
        for kp, vp in ((k_pages, v_pages), (wk_pages, wv_pages)))
    arena_layer = {li: n for kind in (cfg.full_layers, cfg.window_layers)
                   for n, li in enumerate(kind)}
    counts = []
    dt = params["embed"].dtype
    # the residual stream is float32 ([T, d]: it costs nothing): the norms
    # and above all the router read it unrounded, so a near-tie among the
    # router's scores is broken as a float32 forward breaks it far more
    # often; every matrix product still takes its inputs in ``dt``
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32) * math.sqrt(d)  # [T, d]
    for li, layer in enumerate(params["layers"]):
        windowed = cfg.layer_types[li] == SLIDING
        a = rms_norm(x, layer["norm_in"], cfg.norm_eps).astype(dt)
        q = rms_norm((a @ layer["wq"]).reshape(t_buf, h, hd), layer["q_norm"], cfg.norm_eps)
        k = rms_norm((a @ layer["wk"]).reshape(t_buf, kvh, hd), layer["k_norm"], cfg.norm_eps)
        v = (a @ layer["wv"]).reshape(t_buf, kvh, hd)
        ai = arena_layer[li]
        # every token's K and V is written before the walk (see llama.ragged_step)
        if windowed:
            q = rope(q[:, None], pos2, cfg.rope_theta)[:, 0]
            k = rope(k[:, None], pos2, cfg.rope_theta)[:, 0]
            with jax.named_scope("kv_write"):
                wk_pages = wk_pages.at[ai, wpage_idx, slot].set(k)
                wv_pages = wv_pages.at[ai, wpage_idx, slot].set(v)
            attn = paged_attention(q, wk_pages, wv_pages, ai, window_tables, token_seq,
                                   positions, wblock_pages, window=cfg.window)
        else:
            with jax.named_scope("kv_write"):
                k_pages = k_pages.at[ai, page_idx, slot].set(k)
                v_pages = v_pages.at[ai, page_idx, slot].set(v)
            attn = paged_attention(q, k_pages, v_pages, ai, page_tables, token_seq,
                                   positions, block_pages)
        with jax.named_scope("attn_gate"):
            attn = attn.reshape(t_buf, h * hd) * jax.nn.sigmoid(a @ layer["wg"])
        x = x + rms_norm(attn @ layer["wo"], layer["norm_post_attn"], cfg.norm_eps)
        m = rms_norm(x, layer["norm_pre_mlp"], cfg.norm_eps)  # float32
        if li < cfg.n_dense_layers:
            with jax.named_scope("mlp"):
                mb = m.astype(dt)
                f = (jax.nn.silu(mb @ layer["w_gate"]) * (mb @ layer["w_up"])) @ layer["w_down"]
        else:
            f, n = expert_layer(m, layer, cfg, live)
            counts.append(n)
        x = x + rms_norm(f, layer["norm_post_mlp"], cfg.norm_eps)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(dt)
    tail = jnp.concatenate(counts) if counts else jnp.zeros((0,), jnp.int32)
    if not sample_logits:
        nxt = jnp.zeros((t_buf,), jnp.int32)
    else:
        with jax.named_scope("lm_head"):
            nxt = jnp.argmax(x @ params["lm_head"], axis=-1).astype(jnp.int32)
    return jnp.concatenate([nxt, tail]), k_pages, v_pages, wk_pages, wv_pages


def step_counters(cfg: Any, counts: Any, live_tokens: int) -> dict[str, int]:
    """What one step's ``counts`` (int [expert layers, experts held (+ 3)],
    as :func:`expert_layer` returned them a layer) add to ``ServingStats``:
    assignments the router made, those to experts held here, held experts
    that got a token, and the busiest held expert's tokens, each summed over
    the expert layers; with identity experts in the router also the picks of
    them, and the most and the fewest real experts a live token picked (a
    layer, summed over the layers: a reader divides)."""
    held = counts[:, :cfg.experts_held]
    out = {
        "moe_assignments": live_tokens * cfg.top_k * counts.shape[0],
        "moe_assignments_here": int(held.sum()),
        "moe_experts_touched": int((held > 0).sum()),
        "moe_max_expert_load": int(held.max(axis=1).sum()) if held.size else 0,
    }
    if cfg.n_identity:
        zero, most, fewest = (int(n) for n in counts[:, cfg.experts_held:].sum(axis=0))
        out.update(moe_zero_assignments=zero, moe_real_picks_max=most, moe_real_picks_min=fewest)
    return out


def expert_label(cfg: Any, platform: str) -> dict[str, str]:
    """The ``expert`` role of a sparse family's ``ModelSpec.kernels``: the name
    of the kernel :func:`grouped_products` hands a lowering for ``platform``
    at ``cfg``'s expert shapes, "" where the products are ``ragged_dot``'s.
    The kernel's module imports Pallas: here, at start-up, not in a step."""
    from . import expert_mlp

    held = expert_mlp.holds_kernel(platform, cfg.d_model, cfg.d_expert,
                                   np.dtype(cfg.dtype).itemsize)
    return {"expert": expert_mlp.KERNEL_NAME if held else ""}


def step_report(cfg: Any, counts: Any, live_tokens: int,
                kernels: Mapping[str, str]) -> tuple[dict[str, int], dict[str, str]]:
    """``ModelSpec.count_aux`` of every sparse family: one step's
    :func:`step_counters`, with the work items the grouped products' kernel
    visited where the program holds it (``kernels``: as its own rule makes
    them from the counts that are here already), and what the ``step`` span
    says of them."""
    counters = step_counters(cfg, counts, live_tokens)
    if kernels.get("expert"):
        from . import expert_mlp

        counters["moe_kernel_items"] = int(
            expert_mlp.item_counts(counts[:, :cfg.experts_held]).sum())
    attrs = {"moe_here": str(counters["moe_assignments_here"]),
             "moe_touched": str(counters["moe_experts_touched"]),
             "expert_kernel": kernels.get("expert") or "none",
             "moe_items": str(counters.get("moe_kernel_items", 0))}
    if cfg.n_identity:
        attrs["moe_zero"] = str(counters["moe_zero_assignments"])
        attrs["moe_real_picks"] = (f"{counters['moe_real_picks_min']}-"
                                   f"{counters['moe_real_picks_max']}")
    return counters, attrs


def serving_spec(cfg: AfmoeConfig) -> Any:
    """The family's specification for the serving backend
    (``serving/modelspec.py``): two kinds of page, the experts' counts
    behind the tokens."""
    from ..serving.modelspec import ModelSpec, kv_pair

    def program(sample_logits):
        def ragged_program(p, kp, vp, wkp, wvp, toks, pos, pt, wpt, ts, oi):
            return ragged_step(p, kp, vp, wkp, wvp, toks, pos, pt, wpt, ts, oi, cfg,
                               sample_logits=sample_logits)

        return ragged_program

    return ModelSpec(
        family="afmoe", cfg=cfg, vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        init_params=lambda key: init_params(key, cfg),
        init_arenas=lambda n, ps, w: init_arenas(cfg, n, ps, w),
        program=program, window=cfg.window,
        arenas=(kv_pair(cfg.n_kv_heads, cfg.head_dim),) * 2, value_dim=cfg.head_dim,
        aux_shape=(cfg.n_expert_layers, cfg.experts_held),
        count_aux=lambda counts, live, kernels: step_report(cfg, counts, live, kernels),
        # K and V by head in both kinds of page: whole rows and rings
        kernels=lambda platform, mesh_devices: {
            **walk_label(platform, True, mesh_devices, cfg.window),
            **expert_label(cfg, platform)},
    )


__all__ = ["AfmoeConfig", "IDENTITY_COUNTS", "check_routing", "init_params", "init_arenas", "route",
           "expert_label", "expert_layer", "grouped_products", "ragged_products", "ragged_step",
           "serving_spec", "step_counters", "step_report", "SLIDING", "FULL"]
