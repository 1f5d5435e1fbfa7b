"""Falcon-H1 decoder (``model_type: falcon_h1``, TII's hybrid line) on the
serving path.

What the block has that the other families have not (docs/SERVING.md §The
state slot, "pages AND a state in one layer"):

  * **a state-space mixer and attention side by side in EVERY layer** — both
    branches read the SAME normed input and their outputs are summed into
    the residual stream: grouped-query attention over K and V pages by head
    (``attention.paged_attention``, here at 5 query heads a K/V head) and a
    Mamba-2 mixer (``models/ssd.py``) whose cache is a float32 state a row;
  * **so a row keeps two kinds of cache in every layer** — K/V PAGES
    (``[layers, pages, page_size, kv heads, head_dim]`` twice, as
    ``models/llama.py``) and a recurrent STATE with its convolution's tail in
    per-session SLOTS (``ssd.init_state``).  The step program takes the row's
    slot beside its page table (``ModelSpec.init_state``); a state cannot be
    un-advanced, so the prefix cache, speculation, hibernation, migration and
    the gang refuse the family (``kv_positional``), although its pages alone
    are K and V records by head;
  * **a multiplier on every branch** (muP-style, each a key of the published
    config): on the embedding's rows, on the attention's input, keys and
    output, on the mixer's input (one for each span of its in-projection) and
    output, on the feed-forward's gate and output, and on the logits.

Pre-norm residual block, no bias but the convolution's, untied head, RoPE over
the whole head (half-split, no scaling).  The residual stream is float32 (three
branches a layer are summed into it); every matrix product takes its inputs in
``cfg.dtype`` and gives float32, which its multiplier scales before anything is
rounded; the state and its arithmetic are float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from . import kda, ssd
from .attention import (arena_pos_bytes, attn_block_pages, init_kv_pages, paged_attention,
                        walk_label)
from .llama import rms_norm, rope

Params = dict


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 5  # query heads
    n_kv_heads: int = 1
    head_dim: int = 16
    d_ff: int = 128
    ssm_heads: int = 8  # mamba_n_heads
    ssm_head_dim: int = 8  # mamba_d_head: d_ssm = ssm_heads x ssm_head_dim
    ssm_state: int = 16  # mamba_d_state
    ssm_groups: int = 2  # mamba_n_groups: B and C are a group's, shared by its heads
    conv_width: int = 4  # mamba_d_conv
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)  # z, x, B, C, dt
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)  # the gate's, the output's
    rope_theta: float = 1e11
    norm_eps: float = 1e-5
    max_seq_len: int = 256
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if self.n_heads % self.n_kv_heads or self.ssm_heads % self.ssm_groups:
            raise ValueError("query heads divide over the K/V heads, mixer heads over the groups")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2 or self.head_dim % 2:
            raise ValueError("five spans of the in-projection, two multipliers of the "
                             "feed-forward, a head of paired dimensions")

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution sees: ``(x | B | C)``."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    def serving_spec(self) -> Any:
        return serving_spec(self)


def spread(cfg: FalconH1Config) -> dict[str, float]:
    """The standard deviation each matrix is drawn at: the spread its
    multiplier is made for, ``1 / (multiplier x sqrt(fan_in))``, so that every
    branch has unit gain with its multiplier on (1 / sqrt(fan_in) weights
    under ``lm_head_multiplier`` 1/128 would give logits 128 times too small,
    and every comparison of logits would read small for the wrong reason).
    ``w_in``'s is a span's: :func:`init_params` scales its columns by
    ``1 / ssd.in_multipliers``."""
    d = cfg.d_model
    return {
        "embed": 1.0 / cfg.embedding_multiplier,
        "w_q": 1.0 / (cfg.attention_in_multiplier * math.sqrt(d)),
        "w_k": 1.0 / (cfg.attention_in_multiplier * cfg.key_multiplier * math.sqrt(d)),
        "wo": 1.0 / (cfg.attention_out_multiplier * math.sqrt(cfg.n_heads * cfg.head_dim)),
        "w_in": 1.0 / math.sqrt(d),
        "w_out": 1.0 / (cfg.ssm_out_multiplier * math.sqrt(cfg.d_ssm)),
        "w_gate": 1.0 / (cfg.mlp_multipliers[0] * math.sqrt(d)),
        "w_up": 1.0 / math.sqrt(d),
        "w_down": 1.0 / (cfg.mlp_multipliers[1] * math.sqrt(cfg.d_ff)),
        "lm_head": 1.0 / (cfg.lm_head_multiplier * math.sqrt(d)),
    }


def init_params(key: jax.Array, cfg: FalconH1Config) -> Params:
    """Seeded weights: every matrix normal(0, :func:`spread`), norms at 1, the
    convolution's taps normal(0, 1/sqrt(width)) and its bias uniform in
    (-1/2, 1/2); ``A`` uniform in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1]
    through the inverse softplus and ``D`` 1, as Mamba-2 initialises them."""
    d, hd, h, kvh = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    sp = spread(cfg)
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(cfg.dtype)

    ones = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    in_std = sp["w_in"] / ssd.in_multipliers(cfg)  # a column's own
    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], 13)
        dt0 = jnp.exp(jax.random.uniform(lk[8], (cfg.ssm_heads,), jnp.float32,
                                         math.log(1e-3), math.log(1e-1)))
        layers.append({
            "norm_in": ones(d),
            "w_qkv": jnp.concatenate([
                dense(lk[0], (d, h * hd), sp["w_q"]), dense(lk[1], (d, kvh * hd), sp["w_k"]),
                dense(lk[2], (d, kvh * hd), sp["w_q"])], axis=1),
            "wo": dense(lk[3], (h * hd, d), sp["wo"]),
            "w_in": (jax.random.normal(lk[4], (d, in_std.shape[0]), jnp.float32)
                     * in_std).astype(cfg.dtype),
            "conv_w": dense(lk[5], (cfg.conv_width, cfg.conv_dim), 1.0 / math.sqrt(cfg.conv_width)),
            "conv_b": jax.random.uniform(lk[6], (cfg.conv_dim,), jnp.float32, -0.5, 0.5
                                         ).astype(cfg.dtype),
            "a_log": jnp.log(jax.random.uniform(lk[7], (cfg.ssm_heads,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),  # softplus^-1(dt0)
            "d_skip": jnp.ones((cfg.ssm_heads,), jnp.float32),
            "ssm_norm": ones(cfg.d_ssm),
            "w_out": dense(lk[9], (cfg.d_ssm, d), sp["w_out"]),
            "norm_ff": ones(d),
            "w_gate": dense(lk[10], (d, cfg.d_ff), sp["w_gate"]),
            "w_up": dense(lk[11], (d, cfg.d_ff), sp["w_up"]),
            "w_down": dense(lk[12], (cfg.d_ff, d), sp["w_down"]),
        })
    return {"embed": dense(keys[-2], (cfg.vocab_size, d), sp["embed"]), "layers": layers,
            "final_norm": ones(d), "lm_head": dense(keys[-1], (d, cfg.vocab_size), sp["lm_head"])}


def init_state(cfg: FalconH1Config, slots: int) -> tuple[jax.Array, jax.Array]:
    """Every layer's ``(state, tail)`` over ``slots`` state slots."""
    return ssd.init_state(cfg.n_layers, slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                          cfg.ssm_groups, cfg.conv_width, cfg.dtype)


# ---------------------------------------------------------------------------
# the ragged serving step
# ---------------------------------------------------------------------------


def ragged_step(
    params: Params,
    k_pages: jax.Array,
    v_pages: jax.Array,
    state: jax.Array,
    tail: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    state_slot: jax.Array,
    token_seq: jax.Array,
    out_idx: jax.Array,
    cfg: FalconH1Config,
    *,
    sample_logits: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One ragged mixed prefill+decode step (the contract of
    ``llama.ragged_step``) over the K and V arenas and every layer's ``state``
    and ``tail`` (``state_slot`` int32 [S+1]: each table row's slot).  Returns
    ``(out, k_pages, v_pages, state, tail)``, ``out`` int32 [T + 3]: the
    per-slot next-token argmax, then the rows that advanced a state, the
    tokens through the scan and the rows that started from zeros, a layer's."""
    t_buf = tokens.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = params["embed"].dtype
    ps = k_pages.shape[2]
    pos2 = positions[:, None]
    page_idx = page_tables[token_seq, positions // ps]  # [T]: each token's own page
    slot = positions % ps
    block_pages = attn_block_pages(
        ps, page_tables.shape[1],
        arena_pos_bytes((k_pages.shape[3:], v_pages.shape[3:]), k_pages.dtype.itemsize),
        h, kvh, hd)
    srows = kda.state_rows(positions, token_seq, state_slot)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32) * cfg.embedding_multiplier
    for li, layer in enumerate(params["layers"]):
        u = rms_norm(x, layer["norm_in"], cfg.norm_eps).astype(dt)
        with jax.named_scope("attn_proj"):
            ua = u if cfg.attention_in_multiplier == 1 else u * cfg.attention_in_multiplier
            qkv = jnp.dot(ua, layer["w_qkv"], preferred_element_type=jnp.float32)
            q = qkv[:, :h * hd].reshape(t_buf, 1, h, hd)
            k = (qkv[:, h * hd:(h + kvh) * hd] * cfg.key_multiplier).reshape(t_buf, 1, kvh, hd)
            v = qkv[:, (h + kvh) * hd:].reshape(t_buf, kvh, hd).astype(dt)
            q = rope(q, pos2, cfg.rope_theta)[:, 0].astype(dt)
            k = rope(k, pos2, cfg.rope_theta)[:, 0].astype(dt)
        with jax.named_scope("kv_write"):  # every token's K/V before the gather
            k_pages = k_pages.at[li, page_idx, slot].set(k)
            v_pages = v_pages.at[li, page_idx, slot].set(v)
        attn = paged_attention(q, k_pages, v_pages, li, page_tables, token_seq, positions,
                               block_pages)
        a = jnp.dot(attn.reshape(t_buf, h * hd), layer["wo"],
                    preferred_element_type=jnp.float32) * cfg.attention_out_multiplier
        s, state, tail = ssd.mixer(u, layer, state, tail, li, srows, cfg)
        x = x + a + s
        with jax.named_scope("mlp"):
            u2 = rms_norm(x, layer["norm_ff"], cfg.norm_eps).astype(dt)
            gate = jnp.dot(u2, layer["w_gate"], preferred_element_type=jnp.float32)
            up = jnp.dot(u2, layer["w_up"], preferred_element_type=jnp.float32)
            mid = (jax.nn.silu(gate * cfg.mlp_multipliers[0]) * up).astype(dt)
            x = x + jnp.dot(mid, layer["w_down"],
                            preferred_element_type=jnp.float32) * cfg.mlp_multipliers[1]
    counters = jnp.stack([srows.fed, jnp.sum(srows.n), jnp.sum((srows.n > 0) & srows.fresh),
                          kda.rows_prefetched(srows)]).astype(jnp.int32)
    if not sample_logits:
        return jnp.concatenate([jnp.zeros((t_buf,), jnp.int32), counters]), k_pages, v_pages, \
            state, tail
    x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(dt)
    with jax.named_scope("lm_head"):
        # the multiplier is positive: the argmax is the scaled logits' own
        logits = jnp.dot(x, params["lm_head"],
                         preferred_element_type=jnp.float32) * cfg.lm_head_multiplier
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.concatenate([nxt, counters]), k_pages, v_pages, state, tail


def step_counters(counts: Any, live: int) -> dict[str, int]:
    """``ServingStats.model`` addends of one step's counters (``ragged_step``'s
    four, as the program returned them)."""
    rows, tokens, fresh, prefetched = (int(n) for n in counts)
    return {"state_rows_advanced": rows, "state_tokens_scanned": tokens,
            "state_rows_fresh": fresh, "state_rows_prefetched": prefetched}


def step_report(counts: Any, live: int,
                kernels: Mapping[str, str]) -> tuple[dict[str, int], dict[str, str]]:
    """``ModelSpec.count_aux``: one step's :func:`step_counters` and what the
    ``step`` span says of them, with the form the recurrence takes in this
    backend's program (``kernels``)."""
    counters = step_counters(counts, live)
    return counters, {"state_fresh": str(counters["state_rows_fresh"]),
                      "state_prefetched": str(counters["state_rows_prefetched"]),
                      "state_kernel": kernels.get("state") or "none"}


def held_kernels(platform: str, mesh_devices: int) -> dict[str, str]:
    """``ModelSpec.kernels``: the by-head walk's kernel and the mixer's
    recurrence, each by its own rule (``ssd.holds_kernel`` imports Pallas
    where it holds: here, at start-up)."""
    return {**walk_label(platform, True, mesh_devices),
            "state": ssd.KERNEL_NAME if ssd.holds_kernel(platform) else ""}


def serving_spec(cfg: FalconH1Config) -> Any:
    """The family's specification for the serving backend
    (``serving/modelspec.py``): one kind of page, K and V by head, in every
    layer, two state arrays in slots, four counters behind the tokens."""
    from ..serving.modelspec import ModelSpec, kv_pair

    def program(sample_logits):
        def ragged_program(p, kp, vp, st, tl, toks, pos, pt, ss, ts, oi):
            return ragged_step(p, kp, vp, st, tl, toks, pos, pt, ss, ts, oi, cfg,
                               sample_logits=sample_logits)

        return ragged_program

    return ModelSpec(
        family="falcon_h1", cfg=cfg, vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        init_params=lambda key: init_params(key, cfg),
        init_arenas=lambda n, ps, _w: init_kv_pages(cfg, n, ps),
        program=program, arenas=(kv_pair(cfg.n_kv_heads, cfg.head_dim),), value_dim=cfg.head_dim,
        init_state=lambda slots: init_state(cfg, slots), n_state=2,
        aux_shape=(4,), count_aux=step_report, kernels=held_kernels,
    )


__all__ = ["FalconH1Config", "held_kernels", "init_params", "init_state", "ragged_step",
           "serving_spec", "spread", "step_counters", "step_report"]
