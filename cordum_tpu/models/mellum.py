"""Mellum 2 decoder (JetBrains, ``model_type: mellum``: the Qwen3-MoE line of
keys) on the serving path.

The ninth block writing, built FROM the parts the other families run
(docs/SERVING.md §Model seam, §Two kinds of page, §The expert layer) and
holding a copy of none of them:

  * **window and full attention layers in one model, BOTH rotated, each kind
    under a table of its own** — a window layer rotates q and k plainly, a
    full layer under YaRN with its factor on cos and sin
    (:class:`Rotation`; the blend is ``rotary.yarn_inv_freq``, which
    ``models/axk1.py`` calls too).  ``models/afmoe.py``'s full layers have
    no positional encoding and its family has ONE theta; here the step keeps
    the angles of each kind and a layer picks its kind's;
  * **two kinds of page as ``models/afmoe.py`` has them** — the full kind's
    page tables reach the whole context, the window kind's are rings
    (``attention.window_ring_pages``), both walked by
    ``attention.paged_attention`` (the by-head kernel over whole rows and
    over rings where the program is lowered for the TPU);
  * **every layer sparse, the expert set whole** — ``afmoe.expert_layer``
    told ``first_expert`` 0 and ``experts_held`` = ``n_experts``, routed by
    ``afmoe.route`` under a softmax over the router's width with no bias, no
    scale and no shared expert, the selected scores normalised over the
    ``top_k`` (``norm_topk_prob``).  The layer still honours a share
    (``first_expert`` / ``experts_held``), as every sparse family's does;
  * **a plain pre-norm block** — no sandwich norms, no output gate, no
    scale on the embedding, no leading dense layer; per-head RMSNorm of q and
    k (the Qwen3-MoE convention); untied head.

The residual stream is float32 as in ``models/afmoe`` (the router reads it
unrounded); every matrix product takes its inputs in ``cfg.dtype``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from . import rotary
from .afmoe import FULL, SLIDING, check_routing, expert_label, expert_layer, step_report
from .attention import (arena_pos_bytes, attn_block_pages, init_kv_pages, paged_attention,
                        walk_label)
from .llama import rms_norm

Params = dict


@dataclass(frozen=True)
class Rotation:
    """One kind of layer's rotary table (``rope_parameters[kind]``): theta,
    and YaRN's numbers where ``factor`` > 1.  ``attention_factor`` multiplies
    cos and sin, and is stated (1 where the kind rotates plainly)."""
    theta: float = 10000.0
    factor: float = 1.0
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self, dim: int) -> jax.Array:
        return rotary.yarn_inv_freq(dim, self.theta, self.factor, self.original_len,
                                    self.beta_fast, self.beta_slow)


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_expert: int = 32  # every routed expert's width
    n_layers: int = 4
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    window: int = 32
    n_experts: int = 16  # the router's width: experts of the whole layer
    first_expert: int = 0  # this chip holds [first_expert, first_expert + experts_held)
    experts_held: int = 16
    top_k: int = 4
    rope_sliding: Rotation = Rotation()
    rope_full: Rotation = Rotation(factor=4.0, original_len=64, attention_factor=1.1386294)
    norm_eps: float = 1e-6
    max_seq_len: int = 256
    dtype: Any = jnp.bfloat16

    # the family's router (``afmoe.route``): softmax over the whole width,
    # one group, no shared or identity expert, the selected scores normalised
    # over the ``top_k`` (``norm_topk_prob``) and not scaled
    route_score = "softmax"
    route_norm = True
    route_scale = 1.0
    n_group = 1
    topk_group = 1
    n_shared = 0
    n_identity = 0

    def __post_init__(self) -> None:
        if len(self.layer_types) != self.n_layers or not set(self.layer_types) <= {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types} for {self.n_layers} layers")
        if not 0 <= self.first_expert <= self.first_expert + self.experts_held <= self.n_experts:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + self.experts_held}) "
                f"held of {self.n_experts}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("heads group evenly and pair their dimensions")
        check_routing(self)

    @property
    def window_layers(self) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == SLIDING)

    @property
    def full_layers(self) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == FULL)

    def rotation(self, kind: str) -> Rotation:
        return self.rope_sliding if kind == SLIDING else self.rope_full

    def serving_spec(self) -> Any:
        return serving_spec(self)


def init_params(key: jax.Array, cfg: MellumConfig) -> Params:
    """Seeded weights: normal(0, 1/sqrt(fan_in)) matrices, norms at 1, and
    nothing a trained model would have fitted (the router has no bias)."""
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    fe, held = cfg.d_expert, cfg.experts_held
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(cfg.dtype)

    ones = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], 8)
        layers.append({
            "norm_in": ones(d), "norm_post": ones(d), "q_norm": ones(hd), "k_norm": ones(hd),
            "wq": dense(lk[0], (d, h * hd), d), "wk": dense(lk[1], (d, kvh * hd), d),
            "wv": dense(lk[2], (d, kvh * hd), d), "wo": dense(lk[3], (h * hd, d), h * hd),
            "router": dense(lk[4], (d, cfg.n_experts), d),
            "e_gate": dense(lk[5], (held, d, fe), d), "e_up": dense(lk[6], (held, d, fe), d),
            "e_down": dense(lk[7], (held, fe, d), fe)})
    return {"embed": dense(keys[-2], (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": ones(d), "lm_head": dense(keys[-1], (d, cfg.vocab_size), d)}


def init_arenas(cfg: MellumConfig, num_pages: int, page_size: int, window_pages: int) -> tuple:
    """``(k, v, window k, window v)``: the full layers' arena pair over
    ``num_pages`` pages and the window layers' over ``window_pages``."""
    return (*init_kv_pages(cfg, num_pages, page_size, n_layers=len(cfg.full_layers)),
            *init_kv_pages(cfg, window_pages, page_size, n_layers=len(cfg.window_layers)))


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
    cfg: MellumConfig,
    *,
    sample_logits: bool = True,
) -> tuple[jax.Array, ...]:
    """One ragged mixed prefill+decode step (the contract of
    ``llama.ragged_step``) over two kinds of page, as ``afmoe.ragged_step``
    takes them: ``k_pages`` / ``v_pages`` with ``page_tables`` [S+1, P] for
    the full layers, ``wk_pages`` / ``wv_pages`` with the ring tables
    ``window_tables`` [S+1, R] for the window layers.  Returns ``(out,
    k_pages, v_pages, wk_pages, wv_pages)``, ``out`` int32 [T + layers x
    experts_held]: the per-slot next-token argmax, then the assignments each
    held expert got in each layer."""
    t_buf = tokens.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ps = k_pages.shape[2]
    live = token_seq < page_tables.shape[0] - 1  # the last row is the padding row
    slot = positions % ps
    pos = positions.astype(jnp.float32)[:, None]
    # what a layer takes from its KIND: the arenas it writes and walks, their
    # tables, the page each slot's K and V go to (a ring's by the logical
    # page's place in it), the walk's block and window, and the angles and
    # factor it rotates q and k by
    arenas = {FULL: (k_pages, v_pages), SLIDING: (wk_pages, wv_pages)}
    kinds = {}
    for kind, tables, window in ((FULL, page_tables, None), (SLIDING, window_tables, cfg.window)):
        kp, vp = arenas[kind]
        page = positions // ps if window is None else (positions // ps) % tables.shape[1]
        rot = cfg.rotation(kind)
        kinds[kind] = (
            tables, tables[token_seq, page], window,
            attn_block_pages(ps, page_tables.shape[1],
                             arena_pos_bytes((kp.shape[3:], vp.shape[3:]), kp.dtype.itemsize),
                             h, kvh, hd),
            pos * rot.inv_freq(hd)[None, :], rot.attention_factor)
    arena_layer = {li: n for rows in (cfg.full_layers, cfg.window_layers)
                   for n, li in enumerate(rows)}
    counts = []
    dt = params["embed"].dtype
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)  # [T, d], float32 throughout
    for li, layer in enumerate(params["layers"]):
        kind, ai = cfg.layer_types[li], arena_layer[li]
        tables, page_idx, window, block_pages, ang, ratio = kinds[kind]
        a = rms_norm(x, layer["norm_in"], cfg.norm_eps).astype(dt)
        q = rms_norm((a @ layer["wq"]).reshape(t_buf, h, hd), layer["q_norm"], cfg.norm_eps)
        k = rms_norm((a @ layer["wk"]).reshape(t_buf, kvh, hd), layer["k_norm"], cfg.norm_eps)
        v = (a @ layer["wv"]).reshape(t_buf, kvh, hd)
        with jax.named_scope("rope"):
            q, k = rotary.rotate(q, ang, ratio), rotary.rotate(k, ang, ratio)
        # every token's K and V is written before the walk (see llama.ragged_step)
        kp, vp = arenas[kind]
        with jax.named_scope("kv_write"):
            kp = kp.at[ai, page_idx, slot].set(k)
            vp = vp.at[ai, page_idx, slot].set(v)
        arenas[kind] = (kp, vp)
        attn = paged_attention(q, kp, vp, ai, tables, token_seq, positions, block_pages,
                               window=window)
        x = x + attn.reshape(t_buf, h * hd) @ layer["wo"]
        m = rms_norm(x, layer["norm_post"], cfg.norm_eps)  # float32: the router reads it
        f, n = expert_layer(m, layer, cfg, live)
        counts.append(n)
        x = x + f
    x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(dt)
    if not sample_logits:
        nxt = jnp.zeros((t_buf,), jnp.int32)
    else:
        with jax.named_scope("lm_head"):
            nxt = jnp.argmax(x @ params["lm_head"], axis=-1).astype(jnp.int32)
    return (jnp.concatenate([nxt, *counts]), *arenas[FULL], *arenas[SLIDING])


def serving_spec(cfg: MellumConfig) -> Any:
    """The family's specification for the serving backend
    (``serving/modelspec.py``): two kinds of page, both K and V by head, the
    experts' counts behind the tokens under the names ``afmoe`` gives them."""
    from ..serving.modelspec import ModelSpec, kv_pair

    def program(sample_logits):
        def ragged_program(p, kp, vp, wkp, wvp, toks, pos, pt, wpt, ts, oi):
            return ragged_step(p, kp, vp, wkp, wvp, toks, pos, pt, wpt, ts, oi, cfg,
                               sample_logits=sample_logits)

        return ragged_program

    return ModelSpec(
        family="mellum", cfg=cfg, vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        init_params=lambda key: init_params(key, cfg),
        init_arenas=lambda n, ps, w: init_arenas(cfg, n, ps, w),
        program=program, window=cfg.window,
        arenas=(kv_pair(cfg.n_kv_heads, cfg.head_dim),) * 2, value_dim=cfg.head_dim,
        aux_shape=(cfg.n_layers, cfg.experts_held),
        count_aux=lambda counts, live, kernels: step_report(cfg, counts, live, kernels),
        # K and V by head in both kinds of page: whole rows and rings
        kernels=lambda platform, mesh_devices: {
            **walk_label(platform, True, mesh_devices, cfg.window),
            **expert_label(cfg, platform)},
    )


__all__ = ["MellumConfig", "Rotation", "init_params", "init_arenas", "ragged_step",
           "serving_spec", "FULL", "SLIDING"]
