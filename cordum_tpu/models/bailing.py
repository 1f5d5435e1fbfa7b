"""Bailing-hybrid decoder (``model_type: bailing_hybrid``, inclusionAI's
Ling line with linear attention) on the serving path.

What the block has that the other families have not (docs/SERVING.md §The
state slot):

  * **layers of two kinds of attention in one model** — most layers are
    Kimi Delta Attention (``models/kda.py``: short causal convolutions, a
    per-channel decay, a delta-rule update of a float32 state a row and
    layer), every ``layer_group_size``-th is latent attention
    (``axk1.mla_sublayer``, here with ONE query matrix, no query latent, and
    a head-wise gate before ``Wo``; plain RoPE on the rotated columns);
  * **so a row keeps two kinds of cache** — latent PAGES for the latent
    layers (one arena ``[latent layers, pages, page_size, latent_width]``, as
    ``models/axk1.py``) and a recurrent STATE for the KDA layers, in
    per-session SLOTS with no page axis and no position (``kda.init_state``).
    The step program takes the row's slot beside its page table
    (``ModelSpec.init_state``); a state is advanced in place and cannot be
    un-advanced, so the prefix cache, speculation, hibernation, migration
    and the gang refuse the family (``kv_positional``);
  * **the group-limited sigmoid router and the dropless expert layer of the
    other sparse families** (``afmoe.route``, ``afmoe.expert_layer``):
    selection bias in the choice only, ``n_group`` groups of which
    ``topk_group`` are kept, one shared expert.

Pre-norm residual block, no bias, untied head; ``dense_layers`` names the
layers whose feed-forward part is one dense SwiGLU.  The residual stream is
float32 as in ``models/afmoe`` (the router reads it unrounded); every matrix
product takes its inputs in ``cfg.dtype``; the KDA state and its arithmetic
are float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from . import kda
from .afmoe import check_routing, expert_layer, step_report
from .axk1 import LANES, held_kernels, mla_sublayer, walk_rows
from .llama import rms_norm
from .rotary import rotate

Params = dict
KDA, MLA = "kda", "mla"


@dataclass(frozen=True)
class BailingConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4  # of both kinds of attention
    kda_dk: int = 16  # a KDA head's key (and query) width
    kda_dv: int = 16  # and its value width: the state is [kda_dk, kda_dv] a head
    conv_width: int = 4  # short_conv_kernel_size
    kda_lower_bound: float = -5.0  # the log-decay lies in (kda_lower_bound, 0)
    kv_rank: int = 32  # kv_lora_rank: the latent a token keeps in a latent layer
    nope_dim: int = 16  # qk_nope_head_dim
    rope_dim: int = 8  # qk_rope_head_dim: ONE rotated key part, shared by the heads
    v_dim: int = 16  # v_head_dim
    d_ff: int = 128  # the dense layers' SwiGLU width
    d_expert: int = 32  # every routed expert's and the shared expert's width
    layer_kinds: tuple[str, ...] = (KDA, KDA, MLA)
    dense_layers: tuple[int, ...] = (0,)  # layers whose FFN is dense; the rest hold experts
    n_experts: int = 16  # the router's width: experts of the whole layer
    first_expert: int = 0  # this chip holds [first_expert, first_expert + experts_held)
    experts_held: int = 16
    top_k: int = 4
    n_group: int = 4
    topk_group: int = 2
    n_shared: int = 1
    route_scale: float = 2.5
    route_norm: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 256
    dtype: Any = jnp.bfloat16

    # the family's router (``afmoe.route``): sigmoid scores, real experts only
    route_score = "sigmoid"
    n_identity = 0

    def __post_init__(self) -> None:
        if not set(self.layer_kinds) <= {KDA, MLA} or not self.layer_kinds:
            raise ValueError(f"layer_kinds {self.layer_kinds}: each {KDA!r} or {MLA!r}")
        if not 0 <= self.first_expert <= self.first_expert + self.experts_held <= self.n_experts:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + self.experts_held}) "
                f"held of {self.n_experts}")
        if self.rope_dim % 2 or not set(self.dense_layers) <= set(range(self.n_layers)):
            raise ValueError("the rotated part pairs its dimensions; dense layers are layers")
        check_routing(self)

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def kda_layers(self) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == KDA)

    @property
    def mla_layers(self) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == MLA)

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - len(self.dense_layers)

    @property
    def n_kv_heads(self) -> int:
        """Key heads the latent layers' walk sees: the absorbed form has ONE."""
        return 1

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def latent_width(self) -> int:
        """Columns of the latent arena: whole 128-lane tiles
        (``Axk1Config.latent_width`` has the reason)."""
        return -(-self.latent_dim // LANES) * LANES

    @property
    def softmax_scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5

    def serving_spec(self) -> Any:
        return serving_spec(self)


def rope(x: jax.Array, positions: jax.Array, cfg: BailingConfig) -> jax.Array:
    """Plain rotary positions over the ``rope_dim`` rotated dimensions."""
    d = cfg.rope_dim
    inv_freq = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    return rotate(x, positions.astype(jnp.float32)[:, None] * inv_freq[None, :])


# ---------------------------------------------------------------------------
# params, arenas, state
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: BailingConfig) -> Params:
    """Seeded weights: normal(0, 1/sqrt(fan_in)) matrices, norms at 1, a small
    selection bias, the convolution's taps normal(0, 1/sqrt(width)); the
    decay's ``a_log`` and ``a_bias`` spread so that a token's decay ``exp g``
    runs from about 0.3 to 0.999 over the channels (a state that remembers:
    with every channel near the bound it would forget within a few tokens)."""
    d, h = cfg.d_model, cfg.n_heads
    dk, dv, fe, held = cfg.kda_dk, cfg.kda_dv, cfg.d_expert, cfg.experts_held
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(cfg.dtype)

    ones = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    layers = []
    for i, kind in enumerate(cfg.layer_kinds):
        lk = jax.random.split(keys[i], 24)
        layer = {"norm_in": ones(d), "norm_post": ones(d)}
        if kind == KDA:
            layer.update(
                w_qkv=dense(lk[0], (d, 3 * h * dk), d), w_a=dense(lk[1], (d, h * dk), d),
                conv_w=dense(lk[2], (cfg.conv_width, 3 * h * dk), cfg.conv_width),
                a_log=jnp.log(jax.random.uniform(lk[3], (h,), jnp.float32, 0.5, 1.0)),
                a_bias=jax.random.uniform(lk[4], (h * dk,), jnp.float32, -9.0, -1.5),
                w_beta=dense(lk[5], (d, h), d), w_g=dense(lk[6], (d, h), d),
                o_norm=ones(dv), wo=dense(lk[7], (h * dv, d), h * dv))
        else:
            layer.update(
                kv_norm=ones(cfg.kv_rank),
                wq=dense(lk[0], (d, h * (cfg.nope_dim + cfg.rope_dim)), d),
                wkva=dense(lk[1], (d, cfg.latent_dim), d),
                wkvb=dense(lk[2], (cfg.kv_rank, h * (cfg.nope_dim + cfg.v_dim)), cfg.kv_rank),
                wg=dense(lk[3], (d, h), d), wo=dense(lk[4], (h * cfg.v_dim, d), h * cfg.v_dim))
        if i in cfg.dense_layers:
            layer.update(w_gate=dense(lk[8], (d, cfg.d_ff), d), w_up=dense(lk[9], (d, cfg.d_ff), d),
                         w_down=dense(lk[10], (cfg.d_ff, d), cfg.d_ff))
        else:
            fs = fe * cfg.n_shared
            layer.update(
                router=dense(lk[8], (d, cfg.n_experts), d),
                router_bias=0.02 * jax.random.normal(lk[9], (cfg.n_experts,), jnp.float32),
                e_gate=dense(lk[10], (held, d, fe), d), e_up=dense(lk[11], (held, d, fe), d),
                e_down=dense(lk[12], (held, fe, d), fe),
                s_gate=dense(lk[13], (d, fs), d), s_up=dense(lk[14], (d, fs), d),
                s_down=dense(lk[15], (fs, d), fs))
        layers.append(layer)
    return {"embed": dense(keys[-2], (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": ones(d), "lm_head": dense(keys[-1], (d, cfg.vocab_size), d)}


def init_arenas(cfg: BailingConfig, num_pages: int, page_size: int) -> tuple[jax.Array]:
    """The ONE page arena: ``[latent layers, num_pages, page_size,
    latent_width]``, a slot ``(c | kr | zeros to the tile)``."""
    return (jnp.zeros((len(cfg.mla_layers), num_pages, page_size, cfg.latent_width), cfg.dtype),)


def init_state(cfg: BailingConfig, slots: int) -> tuple[jax.Array, jax.Array]:
    """The KDA layers' ``(state, tail)`` over ``slots`` state slots."""
    return kda.init_state(len(cfg.kda_layers), slots, cfg.n_heads, cfg.kda_dk, cfg.kda_dv,
                          cfg.conv_width, cfg.dtype)


# ---------------------------------------------------------------------------
# the ragged serving step
# ---------------------------------------------------------------------------


def ragged_step(
    params: Params,
    c_pages: jax.Array,
    state: jax.Array,
    tail: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    state_slot: jax.Array,
    token_seq: jax.Array,
    out_idx: jax.Array,
    cfg: BailingConfig,
    *,
    sample_logits: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One ragged mixed prefill+decode step (the contract of
    ``llama.ragged_step``) over the latent arena ``c_pages`` and the KDA
    layers' ``state`` and ``tail`` (``state_slot`` int32 [S+1]: each table
    row's slot).  Returns ``(out, c_pages, state, tail)``, ``out`` int32 [T +
    expert layers x experts_held]: the per-slot next-token argmax, then the
    assignments each held expert got in each expert layer."""
    t_buf = tokens.shape[0]
    live = token_seq < page_tables.shape[0] - 1  # the last row is the padding row
    counts = []
    dt = params["embed"].dtype
    walk = walk_rows(c_pages, positions, page_tables, token_seq, cfg, dt)
    srows = kda.state_rows(positions, token_seq, state_slot)
    rope_fn = lambda x, pos: rope(x, pos, cfg)  # noqa: E731
    row_of = {li: n for kind in (cfg.kda_layers, cfg.mla_layers) for n, li in enumerate(kind)}
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)  # [T, d], float32 throughout
    for li, layer in enumerate(params["layers"]):
        a = rms_norm(x, layer["norm_in"], cfg.norm_eps).astype(dt)
        if cfg.layer_kinds[li] == KDA:
            o, state, tail = kda.kda_sublayer(a, layer, state, tail, row_of[li], srows, cfg)
        else:
            o, c_pages = mla_sublayer(a, layer, c_pages, row_of[li], walk, cfg, rope_fn,
                                      direct_q=True, head_gate=True)
        x = x + o
        m = rms_norm(x, layer["norm_post"], cfg.norm_eps)  # float32
        if li in cfg.dense_layers:
            with jax.named_scope("mlp"):
                mb = m.astype(dt)
                f = (jax.nn.silu(mb @ layer["w_gate"]) * (mb @ layer["w_up"])) @ layer["w_down"]
        else:
            f, n = expert_layer(m, layer, cfg, live)
            counts.append(n)
        x = x + f
    x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(dt)
    tail_counts = jnp.concatenate(counts) if counts else jnp.zeros((0,), jnp.int32)
    if not sample_logits:
        nxt = jnp.zeros((t_buf,), jnp.int32)
    else:
        with jax.named_scope("lm_head"):
            nxt = jnp.argmax(x @ params["lm_head"], axis=-1).astype(jnp.int32)
    return jnp.concatenate([nxt, tail_counts]), c_pages, state, tail


def serving_spec(cfg: BailingConfig) -> Any:
    """The family's specification for the serving backend
    (``serving/modelspec.py``): one kind of page with ONE latent arena, two
    state arrays in slots, the experts' counts behind the tokens."""
    from ..serving.modelspec import ModelSpec

    def program(sample_logits):
        def ragged_program(p, cp, st, tl, toks, pos, pt, ss, ts, oi):
            return ragged_step(p, cp, st, tl, toks, pos, pt, ss, ts, oi, cfg,
                               sample_logits=sample_logits)

        return ragged_program

    return ModelSpec(
        family="bailing", cfg=cfg, vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        init_params=lambda key: init_params(key, cfg),
        init_arenas=lambda n, ps, _w: init_arenas(cfg, n, ps),
        program=program, arenas=(((cfg.latent_width,),),), value_dim=cfg.kv_rank,
        init_state=lambda slots: init_state(cfg, slots), n_state=2,
        aux_shape=(cfg.n_expert_layers, cfg.experts_held),
        count_aux=lambda counts, live, kernels: step_report(cfg, counts, live, kernels),
        # the walk and the grouped products, as the backend stated them before
        # the specification did; the recurrence's ``kda_step`` is chosen at
        # trace time like them and has no label yet (ROADMAP, named debts)
        kernels=lambda platform, mesh_devices: held_kernels(cfg, platform, mesh_devices),
    )


__all__ = ["BailingConfig", "KDA", "MLA", "init_params", "init_arenas", "init_state",
           "ragged_step", "rope", "serving_spec"]
