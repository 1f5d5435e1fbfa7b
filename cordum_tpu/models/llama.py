"""Llama-family decoder, TPU-first.

This is the flagship model the TPU worker executes for inference jobs
(BASELINE.json config #5: "Llama-3-8B JAX inference step behind safety-kernel
REQUIRE_APPROVAL").  Design choices for the MXU/ICI:

  * functional pytree params + pure ``forward`` — everything jits, no
    framework indirection; params default to bfloat16 (MXU-native)
  * GQA attention with RoPE, RMSNorm, SwiGLU — Llama-3 architecture family
  * sharding by annotation: :func:`param_specs` gives the Megatron-style
    tensor-parallel layout (column-parallel qkv/gate, row-parallel
    out/down), activations are constrained to ``(dp, sp, ·)`` so long
    sequences shard over the ``sp`` axis; XLA GSPMD inserts the ICI
    collectives (all-gather for KV over ``sp``, psum for row-parallel
    matmuls) — no hand-written NCCL-style code, per the scaling-book recipe
  * static shapes, ``lax``-friendly: causal mask built with iota/compare,
    no data-dependent Python control flow
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP
from .attention import (arena_pos_bytes, attn_block_pages, init_kv_pages, paged_attention,
                        walk_label)

Params = dict


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1536
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    # long-context: ring attention over `sp` (K/V rotate via ppermute, no
    # device ever holds the full sequence) instead of the KV all-gather
    use_ring_attention: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, rope_theta=500000.0, max_seq_len=8192,
        )

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        return cls(vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=128)

    def serving_spec(self) -> Any:
        return serving_spec(self)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    keys = jax.random.split(key, cfg.n_layers + 2)
    d, h, kvh, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff

    def dense(k, shape, scale_dim):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(scale_dim)).astype(cfg.dtype)

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], 7)
        layers.append(
            {
                "attn_norm": jnp.ones((d,), cfg.dtype),
                "wq": dense(lk[0], (d, h * hd), d),
                "wk": dense(lk[1], (d, kvh * hd), d),
                "wv": dense(lk[2], (d, kvh * hd), d),
                "wo": dense(lk[3], (h * hd, d), h * hd),
                "mlp_norm": jnp.ones((d,), cfg.dtype),
                "w_gate": dense(lk[4], (d, f), d),
                "w_up": dense(lk[5], (d, f), d),
                "w_down": dense(lk[6], (f, d), f),
            }
        )
    return {
        "embed": dense(keys[-2], (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), cfg.dtype),
        "lm_head": dense(keys[-1], (d, cfg.vocab_size), d),
    }


def param_specs(cfg: LlamaConfig) -> Params:
    """Megatron-style TP layout as a PartitionSpec pytree."""
    layer = {
        "attn_norm": P(),
        "wq": P(None, AXIS_TP),
        "wk": P(None, AXIS_TP),
        "wv": P(None, AXIS_TP),
        "wo": P(AXIS_TP, None),
        "mlp_norm": P(),
        "w_gate": P(None, AXIS_TP),
        "w_up": P(None, AXIS_TP),
        "w_down": P(AXIS_TP, None),
    }
    return {
        "embed": P(AXIS_TP, None),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
        "final_norm": P(),
        "lm_head": P(None, AXIS_TP),
    }


def shard_params(params: Params, cfg: LlamaConfig, mesh: Mesh) -> Params:
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: isinstance(x, jnp.ndarray) or dataclasses.is_dataclass(x),
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: [B, T, H, Dh], positions: [B, T]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, Dh/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _attention(q, k, v, cfg: LlamaConfig, *, causal: bool = True, q_offset=None):
    """SDPA with GQA head expansion; fp32 softmax accumulation."""
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    rep = cfg.n_heads // cfg.n_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
    if causal:
        q_pos = jnp.arange(tq)[:, None] if q_offset is None else q_offset[:, :, None]
        k_pos = jnp.arange(tk)[None, :]
        mask = q_pos >= k_pos  # [Tq, Tk] or [B, Tq, Tk]
        if mask.ndim == 2:
            mask = mask[None, None, :, :]
        else:
            mask = mask[:, None, :, :]
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block(x, layer, cfg: LlamaConfig, positions, constrain, mesh=None):
    b, t, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    attn_in = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = (attn_in @ layer["wq"]).reshape(b, t, h, hd)
    k = (attn_in @ layer["wk"]).reshape(b, t, kvh, hd)
    v = (attn_in @ layer["wv"]).reshape(b, t, kvh, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if cfg.use_ring_attention and mesh is not None and mesh.shape.get(AXIS_SP, 1) > 1:
        # ring flavor: K/V never materialize the full sequence anywhere —
        # chunks rotate the sp ring with an online softmax (long contexts)
        from ..ops.ring_attention import ring_attention

        attn = ring_attention(q, k, v, mesh)
    else:
        # context parallelism (all-gather flavor): Q stays sequence-sharded
        # over `sp`; K/V are constrained to full sequence, so GSPMD inserts
        # the all-gather over the sp axis
        k = constrain(k, P(AXIS_DP, None, None, None))
        v = constrain(v, P(AXIS_DP, None, None, None))
        attn = _attention(q, k, v, cfg, q_offset=positions)
    x = x + (attn.reshape(b, t, h * hd) @ layer["wo"])
    x = constrain(x, P(AXIS_DP, AXIS_SP, None))

    mlp_in = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    gate = jax.nn.silu(mlp_in @ layer["w_gate"])
    up = mlp_in @ layer["w_up"]
    x = x + ((gate * up) @ layer["w_down"])
    x = constrain(x, P(AXIS_DP, AXIS_SP, None))
    return x


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    mesh: Optional[Mesh] = None,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Logits for next-token prediction; tokens: [B, T] int32 → [B, T, V]."""
    if mesh is not None and AXIS_SP in mesh.axis_names:
        def constrain(x, spec):
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    else:
        def constrain(x, spec):  # single-device / no-mesh path
            return x

    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    x = params["embed"][tokens]  # gather; embed sharded over tp on vocab dim
    x = constrain(x, P(AXIS_DP, AXIS_SP, None))
    for layer in params["layers"]:
        x = _block(x, layer, cfg, positions, constrain, mesh=mesh)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["lm_head"]


# ---------------------------------------------------------------------------
# the dense ragged serving step (the paged cache and the attention's walk it
# runs over are every family's: ``models/attention.py``)
# ---------------------------------------------------------------------------


def serving_spec(cfg: LlamaConfig) -> Any:
    """The family's specification for the serving backend
    (``serving/modelspec.py``): one kind of page, no counters."""
    from ..serving.modelspec import ModelSpec, kv_pair

    def program(sample_logits):
        def ragged_program(p, kp, vp, toks, pos, pt, ts, oi):
            return ragged_step(p, kp, vp, toks, pos, pt, ts, oi, cfg, sample_logits=sample_logits)

        return ragged_program

    return ModelSpec(
        family="llama", cfg=cfg, vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        init_params=lambda key: init_params(key, cfg),
        init_arenas=lambda n, ps, _w: init_kv_pages(cfg, n, ps),
        program=program, arenas=(kv_pair(cfg.n_kv_heads, cfg.head_dim),), value_dim=cfg.head_dim,
        kernels=lambda platform, mesh_devices: walk_label(platform, True, mesh_devices),
    )


def ragged_step(
    params: Params,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    token_seq: jax.Array,
    out_idx: jax.Array,
    cfg: LlamaConfig,
    *,
    sample_logits: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One ragged mixed prefill+decode step over the paged KV cache — the
    Ragged Paged Attention entry point (PAPERS.md): a single XLA program
    serves any mix of prefill chunks and decode steps over arbitrary
    per-sequence lengths.

    The batch dimension is **tokens, not sequences**: a decode step
    contributes one token, a prefill chunk contributes its whole slice, and
    they ride the same flat buffer.

    tokens: [T] int32 flat token buffer (decode last-tokens and prefill
    chunk tokens interleaved; tail padded with 0s mapped to the padding
    row); positions: [T] int32 global sequence position of each token (==
    the page slot it writes); page_tables: [S+1, P] int32 per-sequence page
    tables — row S is the all-null padding row; token_seq: [T] int32 row of
    ``page_tables`` each token belongs to (padding tokens → S); out_idx:
    [S] int32 index into the token buffer of each sequence's last fed token
    (the sampling position; unused rows point anywhere).  Returns
    (next_tokens [T] int32 — the next-token argmax after every fed buffer
    position; a sequence's sample is row ``out_idx[s]``, a draft row's
    per-position verification votes are its contiguous token slots —
    k_pages, v_pages).

    Shape discipline is the whole point: every operand has a static shape
    regardless of how many sequences are live or how long each one is, so
    the program compiles exactly ONCE — no prompt-length buckets, no batch
    buckets, no recompile cliff when sessions join or leave.  Raggedness is
    expressed through the metadata: each token writes its K/V at
    ``(page_tables[token_seq[t]][positions[t] // ps], positions[t] % ps)``
    *before* the gather, then attends to its own sequence's pages under the
    causal mask ``k_pos <= position`` — in-chunk tokens see each other
    exactly as a full-sequence forward would, padding rows park on the null
    page, and no token can reach another sequence's pages because the
    gather walks only its own page-table row.  The attention is
    :func:`paged_attention`: the walk runs over TILES of a table row's
    slots, not over the buffer slots — a row's fed slots are cut into tiles
    of ``ATTN_TILE_SLOTS``, a tile's pages are gathered once a block of
    :func:`attn_block_pages` pages and its slots are the rows of
    grouped-query products (K and V are read as stored, never repeated);
    the tiles are ordered by length and walked a group at a time, each group
    to the block that holds its longest tile — traced trip counts from
    ``token_seq`` and ``positions``, so the cost follows the slots that are
    fed and their rows' lengths, not the buffer's size nor the context the
    table could hold.  It leans on this function's own packing contract: a
    row's slots are contiguous in the buffer.  (A jnp formulation that runs
    anywhere; a Pallas kernel walking the page table in VMEM is the TPU
    upgrade path.)

    ``sample_logits`` is a STATIC flag for serving-gang followers
    (docs/SERVING.md §Sharded serving): rank 0 alone owns sampling, so
    follower ranks compile with ``sample_logits=False`` and get a program
    whose lm_head projection + argmax are dead-code-eliminated — they still
    produce byte-identical K/V arena updates (the writes depend only on the
    transformer stack), but return an all-zeros token buffer nothing
    reads."""
    t_buf = tokens.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ps = k_pages.shape[2]
    pos2 = positions[:, None]  # [T, 1]
    page_idx = page_tables[token_seq, positions // ps]  # [T] — each token's own page
    slot = positions % ps
    block_pages = attn_block_pages(
        ps, page_tables.shape[1],
        arena_pos_bytes((k_pages.shape[3:], v_pages.shape[3:]), k_pages.dtype.itemsize),
        h, kvh, hd)
    # the named scopes are metadata only: they label the operations in a
    # device trace (per-kernel time by scope) and change none of them
    with jax.named_scope("embed"):
        x = params["embed"][tokens][:, None, :]  # [T, 1, d]
    for li, layer in enumerate(params["layers"]):
        attn_in = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q = (attn_in @ layer["wq"]).reshape(t_buf, 1, h, hd)
        k = (attn_in @ layer["wk"]).reshape(t_buf, 1, kvh, hd)
        v = (attn_in @ layer["wv"]).reshape(t_buf, 1, kvh, hd)
        q = rope(q, pos2, cfg.rope_theta)
        k = rope(k, pos2, cfg.rope_theta)
        # write EVERY token's K/V before the gather: a prefill chunk's later
        # tokens must attend to its earlier ones within the same call (the
        # causal mask cuts the other direction), and a decode token must
        # attend to itself
        with jax.named_scope("kv_write"):
            k_pages = k_pages.at[li, page_idx, slot].set(k[:, 0])
            v_pages = v_pages.at[li, page_idx, slot].set(v[:, 0])
        # attn_gather and attn_scores label the body of the block walk
        attn = paged_attention(
            q[:, 0], k_pages, v_pages, li, page_tables, token_seq, positions, block_pages)
        x = x + (attn.reshape(t_buf, 1, h * hd) @ layer["wo"])
        with jax.named_scope("mlp"):
            mlp_in = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            gate = jax.nn.silu(mlp_in @ layer["w_gate"])
            up = mlp_in @ layer["w_up"]
            x = x + ((gate * up) @ layer["w_down"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    # lm_head over EVERY buffer row: speculative verification needs the
    # next-token prediction at each fed draft position, not just the
    # sequence-final one — a draft row's k+1 per-position argmaxes are the
    # accept-prefix votes (docs/SERVING.md §Speculative decoding).  The
    # per-row argmax at ``out_idx`` positions is unchanged math, so
    # non-draft sampling reads ``preds[out_idx]`` and gets exactly the
    # tokens the sequence-final projection produced; padding rows project
    # too but nothing reads them.
    if not sample_logits:
        # follower ranks: K/V writes above are the whole job — skip the
        # [T, V] projection entirely (static flag → XLA never emits it)
        return jnp.zeros((t_buf,), jnp.int32), k_pages, v_pages
    with jax.named_scope("lm_head"):
        logits = x[:, 0] @ params["lm_head"]  # [T, V]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, k_pages, v_pages


def loss_fn(params: Params, tokens: jax.Array, cfg: LlamaConfig, *, mesh=None) -> jax.Array:
    """Next-token cross entropy over all positions but the last."""
    logits = forward(params, tokens, cfg, mesh=mesh).astype(jnp.float32)
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# training step (used by the multi-chip dry run + training jobs)
# ---------------------------------------------------------------------------


def make_train_step(cfg: LlamaConfig, mesh: Mesh, optimizer=None):
    """Build a jitted SPMD train step: params sharded per :func:`param_specs`,
    batch over ``(dp, sp)``; gradients/optimizer states inherit param
    shardings via jit output shardings."""
    import optax

    opt = optimizer or optax.adamw(3e-4, weight_decay=0.01)
    pspecs = param_specs(cfg)
    param_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    batch_sharding = NamedSharding(mesh, P(AXIS_DP, AXIS_SP))

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg, mesh=mesh))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    jstep = jax.jit(
        step,
        in_shardings=(param_shardings, None, batch_sharding),
        out_shardings=(param_shardings, None, None),
        donate_argnums=(0, 1),
    )

    def init(key):
        params = init_params(key, cfg)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs
        )
        opt_state = opt.init(params)
        return params, opt_state

    return init, jstep
