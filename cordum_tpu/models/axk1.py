"""A.X-K1 decoder (``model_type: axk1``, the DeepSeek-V3 line of latent
attention) on the serving path.

What the block has that ``models/llama`` and ``models/afmoe`` have not
(docs/SERVING.md §The latent page, §The absorbed walk):

  * **latent attention (MLA)** — queries through a low-rank pair
    (``wqa`` -> norm -> ``wqb``), keys and values through ONE shared latent
    ``c`` [kv_rank] a token (``wkva`` -> norm) that ``wkvb`` expands per head
    into a position-free key part and a value, and ONE rotated key part
    ``kr`` [rope_dim] shared by all heads (decoupled RoPE, YaRN-scaled).
    **The cache keeps ``(c | kr)`` a token and layer** — ``kv_rank +
    rope_dim`` numbers (576), no head axis, no V — in one arena ``[L, pages,
    page_size, 576]``;
  * **the absorbed form** — the serving step never expands a cached row to
    K and V by head.  With ``wkvb`` split per head into ``Wuk_h`` and
    ``Wuv_h``: ``ql_h = q_nope_h Wuk_h^T``, the score of head ``h`` is ``s
    (ql_h | q_rope_h) . (c | kr)``, ``ol_h = softmax(score_h) c`` and ``o_h =
    ol_h Wuv_h``: the walk (``attention.paged_attention``) sees one shared key
    head 576 wide under all query heads and takes a key's leading 512
    columns as its value.  Identical to the published form in exact
    arithmetic (``benchmarks/families/axk1_reference.py`` is the published
    one).  Prefill chunks and decode rows take the same walk: expanding a
    row costs ``kv_rank x h x (nope + v)`` multiply-adds a key, which a
    chunk of fewer than about 170 slots does not win back;
  * **a group-limited sigmoid router** over ``models/afmoe``'s dropless
    expert layer, which is told which experts this chip holds: THE
    selection code and THE expert layer of both sparse families
    (``afmoe.route``, ``afmoe.expert_layer``), nothing of them copied.

The latent-attention sublayer is ONE function (:func:`mla_sublayer`) that
``models/longcat.py`` calls too, with its own rotation and rank scalings.

Pre-norm residual block, no bias anywhere, untied head, one leading dense
layer.  The residual stream is float32 as in ``models/afmoe`` (the router
reads it unrounded); every matrix product takes its inputs in ``cfg.dtype``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import rotary
from .afmoe import check_routing, expert_label, expert_layer, step_report
from .attention import arena_pos_bytes, attn_block_pages, paged_attention, walk_label
from .llama import rms_norm
from .rotary import rotate, yarn_mscale

Params = dict
LANES = 128  # a TPU tile's minor dimension


@dataclass(frozen=True)
class Axk1Config:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    q_rank: int = 32  # q_lora_rank
    kv_rank: int = 32  # kv_lora_rank: the latent a token keeps
    nope_dim: int = 16  # qk_nope_head_dim
    rope_dim: int = 8  # qk_rope_head_dim: ONE rotated key part, shared by the heads
    v_dim: int = 16  # v_head_dim
    d_ff: int = 128  # the leading dense layers' SwiGLU width
    d_expert: int = 32  # every routed expert's and the shared expert's width
    n_layers: int = 3
    n_dense_layers: int = 1
    n_experts: int = 16  # the router's width: experts of the whole layer
    first_expert: int = 0  # this chip holds [first_expert, first_expert + experts_held)
    experts_held: int = 16
    top_k: int = 4
    n_group: int = 4
    topk_group: int = 2
    n_shared: int = 1
    n_identity: int = 0  # the router holds real experts only (``afmoe.route``)
    route_score: str = "sigmoid"
    route_scale: float = 2.5
    route_norm: bool = True
    rope_theta: float = 10000.0
    # YaRN (rope_scaling): factor, original context, the two betas and the
    # two mscales; factor 1 is plain RoPE
    rope_factor: float = 1.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    max_seq_len: int = 256
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if not 0 <= self.first_expert <= self.first_expert + self.experts_held <= self.n_experts:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + self.experts_held}) "
                f"held of {self.n_experts}")
        if not 0 <= self.n_dense_layers <= self.n_layers or self.rope_dim % 2:
            raise ValueError("dense layers lead; the rotated part pairs its dimensions")
        check_routing(self)

    @property
    def n_kv_heads(self) -> int:
        """Key heads the walk sees: the absorbed form has ONE, shared."""
        return 1

    @property
    def latent_dim(self) -> int:
        """Numbers the cache keeps a token and layer: ``(c | kr)``."""
        return self.kv_rank + self.rope_dim

    @property
    def latent_width(self) -> int:
        """Columns of the arena: ``latent_dim`` in whole 128-lane tiles, the
        rest zeros.  A TPU pads a 576-wide row to 640 in memory whatever the
        shape says; stated in the shape, the arena keeps the page-major
        layout the walk's gather needs (left to the runtime, a padded shape
        is laid out pages-minor and the program copies the whole arena every
        step: PERF.md section 6, PR 30)."""
        return -(-self.latent_dim // LANES) * LANES

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-0.5 x m^2``, ``m`` YaRN's ``mscale_all_dim`` term."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    def serving_spec(self) -> Any:
        return serving_spec(self)


# ---------------------------------------------------------------------------
# rotary positions, YaRN
# ---------------------------------------------------------------------------


def yarn_inv_freq(cfg: Axk1Config) -> jax.Array:
    """The ``rope_dim / 2`` inverse frequencies of the rotated key part under
    the configuration's YaRN numbers (``rotary.yarn_inv_freq``, the one
    writing of the blend)."""
    return rotary.yarn_inv_freq(cfg.rope_dim, cfg.rope_theta, cfg.rope_factor,
                                cfg.rope_original_len, cfg.rope_beta_fast, cfg.rope_beta_slow)


def rope(x: jax.Array, positions: jax.Array, cfg: Axk1Config) -> jax.Array:
    """:func:`rotate` at ``positions`` [T] under YaRN: its inverse
    frequencies, cos and sin scaled by the ``mscale / mscale_all_dim`` ratio
    (1 in the published configuration)."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]
    ratio = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return rotate(x, ang, ratio)


# ---------------------------------------------------------------------------
# params, arenas
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: Axk1Config) -> Params:
    """Seeded weights: normal(0, 1/sqrt(fan_in)) matrices, norms at 1, no
    selection bias.  ``wkvb`` is the published ``[kv_rank, heads x (nope +
    v)]``, a head's key part before its value."""
    d, h = cfg.d_model, cfg.n_heads
    fe, held = cfg.d_expert, cfg.experts_held
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(cfg.dtype)

    ones = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], 16)
        layer = {
            "norm_in": ones(d), "norm_post": ones(d),
            "q_norm": ones(cfg.q_rank), "kv_norm": ones(cfg.kv_rank),
            "wqa": dense(lk[0], (d, cfg.q_rank), d),
            "wqb": dense(lk[1], (cfg.q_rank, h * (cfg.nope_dim + cfg.rope_dim)), cfg.q_rank),
            "wkva": dense(lk[2], (d, cfg.latent_dim), d),
            "wkvb": dense(lk[3], (cfg.kv_rank, h * (cfg.nope_dim + cfg.v_dim)), cfg.kv_rank),
            "wo": dense(lk[4], (h * cfg.v_dim, d), h * cfg.v_dim),
        }
        if i < cfg.n_dense_layers:
            layer.update(w_gate=dense(lk[5], (d, cfg.d_ff), d), w_up=dense(lk[6], (d, cfg.d_ff), d),
                         w_down=dense(lk[7], (cfg.d_ff, d), cfg.d_ff))
        else:
            fs = fe * cfg.n_shared
            layer.update(
                router=dense(lk[5], (d, cfg.n_experts), d),
                e_gate=dense(lk[7], (held, d, fe), d), e_up=dense(lk[8], (held, d, fe), d),
                e_down=dense(lk[9], (held, fe, d), fe),
                s_gate=dense(lk[10], (d, fs), d), s_up=dense(lk[11], (d, fs), d),
                s_down=dense(lk[12], (fs, d), fs))
        layers.append(layer)
    return {"embed": dense(keys[-2], (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": ones(d), "lm_head": dense(keys[-1], (d, cfg.vocab_size), d)}


def init_arenas(cfg: Axk1Config, num_pages: int, page_size: int) -> tuple[jax.Array]:
    """The ONE arena: ``[L, num_pages, page_size, latent_width]``, a slot
    ``(c | kr | zeros to the tile)``."""
    return (jnp.zeros((cfg.n_layers, num_pages, page_size, cfg.latent_width), cfg.dtype),)


# ---------------------------------------------------------------------------
# the ragged serving step
# ---------------------------------------------------------------------------


class WalkRows(NamedTuple):
    """What every latent-attention sublayer of one step shares: where each
    buffer slot's latent is written and how its row is walked."""
    positions: jax.Array  # [T]
    token_seq: jax.Array  # [T] table row of each slot
    page_tables: jax.Array  # [S+1, P]
    page_idx: jax.Array  # [T] the page a slot's latent is written to
    slot: jax.Array  # [T] and the slot in it
    block_pages: int  # pages a trip of the walk gathers
    c_pad: jax.Array  # [T, zeros] from a latent's numbers to the arena's whole tiles
    q_pad: jax.Array  # [T, h, zeros] beside every query (they add nothing to a score)


def walk_rows(c_pages: jax.Array, positions: jax.Array, page_tables: jax.Array,
              token_seq: jax.Array, cfg: Any, dt: Any) -> WalkRows:
    """The step's :class:`WalkRows` over the latent arena ``c_pages`` [rows,
    N, ps, latent_width] (a row a latent-attention sublayer)."""
    t_buf, ps = positions.shape[0], c_pages.shape[2]
    # one shared key head under the h query heads, values ``kv_rank`` wide
    block_pages = attn_block_pages(
        ps, page_tables.shape[1],
        arena_pos_bytes((c_pages.shape[3:],), c_pages.dtype.itemsize), cfg.n_heads, 1, cfg.kv_rank)
    zeros = c_pages.shape[3] - cfg.latent_dim
    return WalkRows(
        positions, token_seq, page_tables, page_tables[token_seq, positions // ps],
        positions % ps, block_pages, jnp.zeros((t_buf, zeros), dt),
        jnp.zeros((t_buf, cfg.n_heads, zeros), dt))


def mla_sublayer(
    a: jax.Array,
    layer: Params,
    c_pages: jax.Array,
    row: int,
    rows: WalkRows,
    cfg: Any,
    rope_fn: Callable[[jax.Array, jax.Array], jax.Array],
    *,
    q_scale: float = 1.0,
    kv_scale: float = 1.0,
    direct_q: bool = False,
    head_gate: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One latent-attention sublayer in absorbed form over row ``row`` of the
    latent arena: ``a`` [T, d] (normed, in the weights' dtype) -> ``(the
    sublayer's output [T, d], c_pages)``.  THE sublayer of every
    latent-attention family: ``cfg`` gives the widths (``n_heads``,
    ``nope_dim``, ``rope_dim``, ``v_dim``, ``kv_rank``, ``latent_dim``),
    ``norm_eps`` and ``softmax_scale``; ``rope_fn(x, positions)`` rotates the
    family's way (YaRN here, plain in ``models/longcat.py``); ``q_scale``
    multiplies the query behind ``wqb`` and ``kv_scale`` the normed kv latent
    before it is cached and expanded (LongCat's rank scalings; 1 is no
    operation at all).  ``direct_q``: the queries come from ONE matrix
    ``wq`` [d, h x (nope + rope)], no query latent (``q_lora_rank`` null:
    ``models/bailing.py``); ``head_gate``: each head's output is multiplied by
    ``sigmoid(a wg)`` (``wg`` [d, h]) before ``wo``.  Without them the program
    is the one it was.  Every token's ``(c | kr)`` is written at ``(page_idx,
    slot)`` before the walk, so a chunk's later tokens see its earlier ones."""
    t_buf = a.shape[0]
    h, nope, rd, vd, rank = cfg.n_heads, cfg.nope_dim, cfg.rope_dim, cfg.v_dim, cfg.kv_rank
    with jax.named_scope("mla_q_proj"):
        if direct_q:
            q = (a @ layer["wq"]).reshape(t_buf, h, nope + rd)
        else:
            cq = rms_norm(a @ layer["wqa"], layer["q_norm"], cfg.norm_eps)
            q = (cq @ layer["wqb"]).reshape(t_buf, h, nope + rd)
        if q_scale != 1.0:
            q = q * q_scale
        q_rope = rope_fn(q[..., nope:], rows.positions)
    with jax.named_scope("mla_kv_proj"):
        ckr = a @ layer["wkva"]  # [T, rank + rd]
        c = rms_norm(ckr[:, :rank], layer["kv_norm"], cfg.norm_eps)
        if kv_scale != 1.0:
            c = c * kv_scale
        latent = jnp.concatenate([c, rope_fn(ckr[:, rank:], rows.positions), rows.c_pad], axis=-1)
    with jax.named_scope("kv_write"):
        c_pages = c_pages.at[row, rows.page_idx, rows.slot].set(latent)
    wkvb = layer["wkvb"].reshape(rank, h, nope + vd)
    with jax.named_scope("mla_absorb_q"):
        ql = jnp.einsum("thn,chn->thc", q[..., :nope], wkvb[..., :nope])  # [T, h, rank]
    # one shared key head under the h query heads; a key's leading
    # ``rank`` columns are its value
    ol = paged_attention(
        jnp.concatenate([ql, q_rope, rows.q_pad], axis=-1), c_pages, None, row, rows.page_tables,
        rows.token_seq, rows.positions, rows.block_pages, v_dim=rank, scale=cfg.softmax_scale)
    with jax.named_scope("mla_absorb_out"):
        o = jnp.einsum("thc,chv->thv", ol, wkvb[..., nope:])  # [T, h, vd]
    if head_gate:
        with jax.named_scope("attn_gate"):
            o = o * jax.nn.sigmoid(a @ layer["wg"])[..., None]
    return o.reshape(t_buf, h * vd) @ layer["wo"], c_pages


def attention_branch(h: jax.Array, layer: Params, c_pages: jax.Array, li: int, rows: WalkRows,
                     cfg: Any, rope_fn: Callable[[jax.Array, jax.Array], jax.Array],
                     dt: Any) -> tuple[jax.Array, jax.Array]:
    """A block's first sublayer WITHOUT its residual add: ``h`` [T, d]
    float32 (what the sublayer reads of the residual) -> ``(the latent
    attention's output over its pre-norm, c_pages)``.  How the output joins
    the residual is the family's: ``x + o`` here, ``models/xing.py``'s
    hyper-connection there."""
    a = rms_norm(h, layer["norm_in"], cfg.norm_eps).astype(dt)
    return mla_sublayer(a, layer, c_pages, li, rows, cfg, rope_fn)


def feed_forward_branch(h: jax.Array, layer: Params, li: int, cfg: Any, live: jax.Array,
                        dt: Any) -> tuple[jax.Array, Any]:
    """A block's second sublayer without its residual add: the dense SwiGLU
    of a leading dense layer or the expert layer, over its pre-norm (float32
    into the router) -> ``(output [T, d], the expert layer's counts or
    None)``."""
    m = rms_norm(h, layer["norm_post"], cfg.norm_eps)  # float32
    if li < cfg.n_dense_layers:
        with jax.named_scope("mlp"):
            mb = m.astype(dt)
            return (jax.nn.silu(mb @ layer["w_gate"]) * (mb @ layer["w_up"])) @ layer["w_down"], None
    return expert_layer(m, layer, cfg, live)


def sampled(x: jax.Array, params: Params, counts: list, cfg: Any,
            sample_logits: bool) -> jax.Array:
    """The step's ``out`` from the last residual ``x`` [T, d]: the per-slot
    next-token argmax behind the final norm, then the expert layers' counts."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(params["embed"].dtype)
    tail = jnp.concatenate(counts) if counts else jnp.zeros((0,), jnp.int32)
    if not sample_logits:
        nxt = jnp.zeros((x.shape[0],), jnp.int32)
    else:
        with jax.named_scope("lm_head"):
            nxt = jnp.argmax(x @ params["lm_head"], axis=-1).astype(jnp.int32)
    return jnp.concatenate([nxt, tail])


def ragged_step(
    params: Params,
    c_pages: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    token_seq: jax.Array,
    out_idx: jax.Array,
    cfg: Axk1Config,
    *,
    sample_logits: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """One ragged mixed prefill+decode step (the contract of
    ``llama.ragged_step``) over the latent arena ``c_pages`` [L, N, ps,
    latent_width].  Returns ``(out, c_pages)``, ``out`` int32 [T + expert
    layers x experts_held]: the per-slot next-token argmax, then the
    assignments each held expert got in each expert layer."""
    live = token_seq < page_tables.shape[0] - 1  # the last row is the padding row
    counts = []
    dt = params["embed"].dtype
    rows = walk_rows(c_pages, positions, page_tables, token_seq, cfg, dt)
    rope_fn = lambda x, pos: rope(x, pos, cfg)  # noqa: E731
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)  # [T, d], float32 throughout
    for li, layer in enumerate(params["layers"]):
        o, c_pages = attention_branch(x, layer, c_pages, li, rows, cfg, rope_fn, dt)
        x = x + o
        f, n = feed_forward_branch(x, layer, li, cfg, live, dt)
        if n is not None:
            counts.append(n)
        x = x + f
    return sampled(x, params, counts, cfg, sample_logits), c_pages


def held_kernels(cfg: Any, platform: str, mesh_devices: int) -> dict[str, str]:
    """``ModelSpec.kernels`` of a family with ONE latent arena and an expert
    layer (this one, ``models/longcat.py``, ``models/bailing.py``): the latent
    walk's kernel and the grouped products', each by its own rule."""
    return {**walk_label(platform, False, mesh_devices), **expert_label(cfg, platform)}


def serving_spec(cfg: Axk1Config) -> Any:
    """The family's specification for the serving backend
    (``serving/modelspec.py``): one kind of page with ONE latent arena, the
    experts' counts behind the tokens."""
    from ..serving.modelspec import ModelSpec

    def program(sample_logits):
        def ragged_program(p, cp, toks, pos, pt, ts, oi):
            return ragged_step(p, cp, toks, pos, pt, ts, oi, cfg, sample_logits=sample_logits)

        return ragged_program

    return ModelSpec(
        family="axk1", cfg=cfg, vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        init_params=lambda key: init_params(key, cfg),
        init_arenas=lambda n, ps, _w: init_arenas(cfg, n, ps),
        program=program, arenas=(((cfg.latent_width,),),), value_dim=cfg.kv_rank,
        aux_shape=(cfg.n_expert_layers, cfg.experts_held),
        count_aux=lambda counts, live, kernels: step_report(cfg, counts, live, kernels),
        kernels=lambda platform, mesh_devices: held_kernels(cfg, platform, mesh_devices),
    )


__all__ = ["Axk1Config", "WalkRows", "attention_branch", "feed_forward_branch", "held_kernels",
           "init_params", "init_arenas", "mla_sublayer", "ragged_step", "rope", "sampled",
           "serving_spec", "walk_rows", "yarn_inv_freq"]
