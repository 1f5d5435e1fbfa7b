"""LongCat-Flash decoder (``meituan-longcat/LongCat-Flash-Chat``) on the
serving path.

What the block has that the other families' have not (docs/SERVING.md §The
shortcut-connected block):

  * **a layer that is not "attention then feed-forward"** — a published
    layer holds TWO latent-attention sublayers and TWO dense SwiGLU FFNs in
    sequence, and ONE expert branch that reads the first sublayer's
    post-attention norm and is added only at the layer's end
    (shortcut-connected MoE)::

        x1 = x  + MLA_0(N_in0(x));   m0 = N_post0(x1);   e = MoE(m0)
        x2 = x1 + FFN_0(m0)
        x3 = x2 + MLA_1(N_in1(x2));  m1 = N_post1(x3)
        x_out = x3 + FFN_1(m1) + e

    The branch depends on nothing between ``m0`` and the last add, so the
    program may order it anywhere between them; it takes it FIRST, where its
    input is made (:func:`ragged_step`);
  * **two latents a layer in the cache** — the ONE arena's leading axis is
    sublayers, ``[2 x layers, pages, page_size, latent_width]``: a page id
    names a page of every sublayer, as it names one of every layer elsewhere;
  * **latent attention with rank scaling and plain RoPE** — THE sublayer of
    ``models/axk1.py`` (:func:`~cordum_tpu.models.axk1.mla_sublayer`,
    absorbed form, the cache keeps ``(c | kr)``), with the query behind
    ``wqb`` multiplied by ``sqrt(d_model / q_rank)`` and the normed kv latent
    by ``sqrt(d_model / kv_rank)`` before it is cached and expanded, and a
    rotation with no YaRN;
  * **identity (zero-compute) experts in the router** — the router is
    ``n_experts + n_identity`` wide, softmax scores, a selection bias in the
    choice only, the weights the chosen raw scores times ``route_scale``, NOT
    normalised, no shared expert: THE selection code and THE expert layer of
    every sparse family (``afmoe.route``, ``afmoe.expert_layer``).  A token's
    ``top_k`` picks hold 0 to ``top_k`` real experts, so its expert work
    varies; a pick of an identity expert adds ``w x m``.

No bias anywhere, untied head, every layer alike (no leading dense layer).
The residual stream is float32 as in ``models/afmoe`` (the router reads it
unrounded); every matrix product takes its inputs in ``cfg.dtype``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from .afmoe import IDENTITY_COUNTS, check_routing, expert_layer, step_report
from .axk1 import LANES, held_kernels, mla_sublayer, walk_rows
from .llama import rms_norm
from .rotary import rotate

Params = dict
#: latent-attention sublayers (each with its dense FFN) in one layer
SUBLAYERS = 2


@dataclass(frozen=True)
class LongcatConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    q_rank: int = 32  # q_lora_rank
    kv_rank: int = 32  # kv_lora_rank: the latent a token and sublayer keep
    nope_dim: int = 16  # qk_nope_head_dim
    rope_dim: int = 8  # qk_rope_head_dim: ONE rotated key part, shared by the heads
    v_dim: int = 16  # v_head_dim
    d_ff: int = 128  # each of a layer's two dense SwiGLU FFNs
    d_expert: int = 32  # every real expert's width
    n_layers: int = 2
    n_experts: int = 16  # REAL experts of the whole layer: the router's ids below this
    n_identity: int = 8  # identity experts: the router's ids from ``n_experts`` on
    first_expert: int = 0  # this chip holds real experts [first_expert, first_expert + experts_held)
    experts_held: int = 16
    top_k: int = 4
    route_scale: float = 6.0
    scale_q: bool = True  # mla_scale_q_lora
    scale_kv: bool = True  # mla_scale_kv_lora
    rope_theta: float = 1e7
    norm_eps: float = 1e-5
    max_seq_len: int = 256
    dtype: Any = jnp.bfloat16

    # the family's router (``afmoe.route``): softmax over the whole width,
    # one group, raw scores as weights, no shared expert
    route_score = "softmax"
    route_norm = False
    n_group = 1
    topk_group = 1
    n_shared = 0

    def __post_init__(self) -> None:
        if not 0 <= self.first_expert <= self.first_expert + self.experts_held <= self.n_experts:
            raise ValueError(
                f"real experts [{self.first_expert}, {self.first_expert + self.experts_held}) "
                f"held of {self.n_experts}")
        if self.rope_dim % 2 or self.top_k > self.n_experts + self.n_identity:
            raise ValueError("the rotated part pairs its dimensions; top_k picks within the router")
        check_routing(self)

    @property
    def n_kv_heads(self) -> int:
        """Key heads the walk sees: the absorbed form has ONE, shared."""
        return 1

    @property
    def latent_dim(self) -> int:
        """Numbers the cache keeps a token and SUBLAYER: ``(c | kr)``."""
        return self.kv_rank + self.rope_dim

    @property
    def latent_width(self) -> int:
        """Columns of the arena: ``latent_dim`` in whole 128-lane tiles
        (``Axk1Config.latent_width`` has the reason)."""
        return -(-self.latent_dim // LANES) * LANES

    @property
    def n_sublayers(self) -> int:
        """Rows of the latent arena: every layer's two sublayers."""
        return SUBLAYERS * self.n_layers

    @property
    def softmax_scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5

    @property
    def q_scale(self) -> float:
        return math.sqrt(self.d_model / self.q_rank) if self.scale_q else 1.0

    @property
    def kv_scale(self) -> float:
        return math.sqrt(self.d_model / self.kv_rank) if self.scale_kv else 1.0

    def serving_spec(self) -> Any:
        return serving_spec(self)


def rope(x: jax.Array, positions: jax.Array, cfg: LongcatConfig) -> jax.Array:
    """Plain rotary positions over the ``rope_dim`` rotated dimensions."""
    d = cfg.rope_dim
    inv_freq = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    return rotate(x, positions.astype(jnp.float32)[:, None] * inv_freq[None, :])


# ---------------------------------------------------------------------------
# params, arenas
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: LongcatConfig) -> Params:
    """Seeded weights: normal(0, 1/sqrt(fan_in)) matrices, norms at 1, a
    selection bias of the order of the mean score (``1 / router width``: it
    decides some picks, not all).  The two up-projections behind a rank
    scaling are drawn for it: ``wqb`` and ``wkvb`` at 1/sqrt(d_model), the
    one spread every matrix of the published model starts from and the
    reason the scalings exist (a rank-wide input then gives queries, keys
    and values of the variance a d_model-wide one would; at 1/sqrt(rank)
    the attention's logits are 7 times too wide and every sublayer
    multiplies a rounding error).  A layer: ``sub``, its two sublayers (each
    an attention's matrices and norms and a dense FFN), and the expert
    branch's router, bias and held experts."""
    d, h = cfg.d_model, cfg.n_heads
    fe, held, wide = cfg.d_expert, cfg.experts_held, cfg.n_experts + cfg.n_identity
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(cfg.dtype)

    ones = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731

    def sublayer(k):
        sk = jax.random.split(k, 8)
        return {
            "norm_in": ones(d), "norm_post": ones(d),
            "q_norm": ones(cfg.q_rank), "kv_norm": ones(cfg.kv_rank),
            "wqa": dense(sk[0], (d, cfg.q_rank), d),
            "wqb": dense(sk[1], (cfg.q_rank, h * (cfg.nope_dim + cfg.rope_dim)),
                         cfg.q_rank * cfg.q_scale ** 2),
            "wkva": dense(sk[2], (d, cfg.latent_dim), d),
            "wkvb": dense(sk[3], (cfg.kv_rank, h * (cfg.nope_dim + cfg.v_dim)),
                          cfg.kv_rank * cfg.kv_scale ** 2),
            "wo": dense(sk[4], (h * cfg.v_dim, d), h * cfg.v_dim),
            "w_gate": dense(sk[5], (d, cfg.d_ff), d), "w_up": dense(sk[6], (d, cfg.d_ff), d),
            "w_down": dense(sk[7], (cfg.d_ff, d), cfg.d_ff),
        }

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], SUBLAYERS + 5)
        layers.append({
            "sub": [sublayer(lk[j]) for j in range(SUBLAYERS)],
            "router": dense(lk[-5], (d, wide), d),
            "router_bias": jax.random.normal(lk[-4], (wide,), jnp.float32) / wide,
            "e_gate": dense(lk[-3], (held, d, fe), d), "e_up": dense(lk[-2], (held, d, fe), d),
            "e_down": dense(lk[-1], (held, fe, d), fe),
        })
    return {"embed": dense(keys[-2], (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": ones(d), "lm_head": dense(keys[-1], (d, cfg.vocab_size), d)}


def init_arenas(cfg: LongcatConfig, num_pages: int, page_size: int) -> tuple[jax.Array]:
    """The ONE arena: ``[2 x layers, num_pages, page_size, latent_width]``,
    a row a sublayer, a slot ``(c | kr | zeros to the tile)``."""
    return (jnp.zeros((cfg.n_sublayers, num_pages, page_size, cfg.latent_width), cfg.dtype),)


# ---------------------------------------------------------------------------
# the ragged serving step
# ---------------------------------------------------------------------------


def ragged_step(
    params: Params,
    c_pages: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    token_seq: jax.Array,
    out_idx: jax.Array,
    cfg: LongcatConfig,
    *,
    sample_logits: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """One ragged mixed prefill+decode step (the contract of
    ``llama.ragged_step``) over the latent arena ``c_pages`` [2 x layers, N,
    ps, latent_width].  Returns ``(out, c_pages)``, ``out`` int32 [T + layers
    x (experts_held + 3)]: the per-slot next-token argmax, then a layer's
    counters as ``afmoe.expert_layer`` returns them.

    **The order of the expert branch**: ``e = MoE(m0)`` is computed as soon
    as ``m0`` is, before the first dense FFN, and added with the layer's last
    residual add.  On one chip nothing runs beside it and XLA's scheduler
    places the operations as it likes; in this order ``e`` ([T, d] float32)
    is what lives across the second sublayer, where the other order would
    keep ``m0``: the same bytes.  A deployment's exchange would start here
    and be awaited at the last add (docs/SERVING.md)."""
    t_buf = tokens.shape[0]
    live = token_seq < page_tables.shape[0] - 1  # the last row is the padding row
    counts = []
    dt = params["embed"].dtype
    rows = walk_rows(c_pages, positions, page_tables, token_seq, cfg, dt)
    rope_fn = lambda x, pos: rope(x, pos, cfg)  # noqa: E731

    def ffn(m, sub):
        with jax.named_scope("mlp"):
            mb = m.astype(dt)
            return (jax.nn.silu(mb @ sub["w_gate"]) * (mb @ sub["w_up"])) @ sub["w_down"]

    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)  # [T, d], float32 throughout
    for li, layer in enumerate(params["layers"]):
        e = None
        for j, sub in enumerate(layer["sub"]):
            with jax.named_scope(f"sub{j}"):
                a = rms_norm(x, sub["norm_in"], cfg.norm_eps).astype(dt)
                o, c_pages = mla_sublayer(a, sub, c_pages, SUBLAYERS * li + j, rows, cfg, rope_fn,
                                          q_scale=cfg.q_scale, kv_scale=cfg.kv_scale)
                x = x + o
                m = rms_norm(x, sub["norm_post"], cfg.norm_eps)  # float32
                if j == 0:
                    with jax.named_scope("scmoe_branch"):
                        e, n = expert_layer(m, layer, cfg, live)
                    counts.append(n)
                x = x + ffn(m, sub)
        x = x + e
    x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(dt)
    if not sample_logits:
        nxt = jnp.zeros((t_buf,), jnp.int32)
    else:
        with jax.named_scope("lm_head"):
            nxt = jnp.argmax(x @ params["lm_head"], axis=-1).astype(jnp.int32)
    return jnp.concatenate([nxt, *counts]), c_pages


def serving_spec(cfg: LongcatConfig) -> Any:
    """The family's specification for the serving backend
    (``serving/modelspec.py``): one kind of page with ONE latent arena (a row
    a sublayer), a layer's counters behind the tokens."""
    from ..serving.modelspec import ModelSpec

    def program(sample_logits):
        def ragged_program(p, cp, toks, pos, pt, ts, oi):
            return ragged_step(p, cp, toks, pos, pt, ts, oi, cfg, sample_logits=sample_logits)

        return ragged_program

    return ModelSpec(
        family="longcat", cfg=cfg, vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        init_params=lambda key: init_params(key, cfg),
        init_arenas=lambda n, ps, _w: init_arenas(cfg, n, ps),
        program=program, arenas=(((cfg.latent_width,),),), value_dim=cfg.kv_rank,
        aux_shape=(cfg.n_layers, cfg.experts_held + IDENTITY_COUNTS),
        count_aux=lambda counts, live, kernels: step_report(cfg, counts, live, kernels),
        kernels=lambda platform, mesh_devices: held_kernels(cfg, platform, mesh_devices),
    )


__all__ = ["LongcatConfig", "SUBLAYERS", "init_params", "init_arenas", "ragged_step", "rope",
           "serving_spec"]
