"""Ring attention: sequence-parallel exact attention over the ``sp`` axis.

Long-context jobs shard the sequence across devices; each device holds a
Q/K/V chunk.  K/V chunks rotate around the ring via ``ppermute`` (ICI
neighbor exchanges — bandwidth-optimal, no all-gather memory spike) while
each device accumulates its Q chunk's attention with an online (flash-style)
softmax: running max ``m``, normalizer ``l``, and unnormalized accumulator.
After ``sp`` steps every Q has attended to every K/V without any device ever
holding the full sequence.

This is the "ring attention or all-to-all sequence parallelism" requirement
(task brief / SURVEY §5 long-context): the all-to-all (KV-gather) flavor
lives in ``models/llama.py``; this op is the ring flavor for sequences too
long to gather.  Compute overlaps transfer naturally: XLA schedules the
next ppermute concurrently with the current chunk's matmuls.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import AXIS_DP, AXIS_SP

_NEG = -1e30


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool, scale: float):
    """Per-device body under shard_map; q: [B, Tq, H, D], k/v: [B, Tk, Hkv, D]."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    rep = h // k.shape[2]
    if rep > 1:  # GQA: expand KV heads once locally
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    q32 = q.astype(jnp.float32)
    q_pos = idx * tq + jnp.arange(tq)

    m0 = jnp.full((b, h, tq), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    acc0 = jnp.zeros((b, h, tq, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        m, l, acc, k_cur, v_cur = carry
        owner = (idx - step) % n  # which shard's K/V we currently hold
        k_pos = owner * tk + jnp.arange(tk)
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", q32, k_cur.astype(jnp.float32)) * scale
        )
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None, :, :], scores, _NEG)
        chunk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, chunk_max)
        correction = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])
        l = l * correction + jnp.sum(p, axis=-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32)
        )
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return new_m, l, acc, k_nxt, v_nxt

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, H, Tq, D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    sp_axis: str = AXIS_SP,
    dp_axis: str = AXIS_DP,
) -> jax.Array:
    """Sequence-parallel attention.  q: [B, T, H, D]; k/v: [B, T, Hkv, D]
    with T sharded over ``sp_axis`` and B over ``dp_axis``.  Returns [B, T, H, D]
    with the same sharding as q."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(dp_axis, sp_axis, None, None)
    fn = jax.shard_map(
        partial(_ring_attention_local, axis_name=sp_axis, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def reference_attention(q, k, v, *, causal: bool = True) -> jax.Array:
    """Single-device exact attention for correctness checks."""
    b, t, h, d = q.shape
    rep = h // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores / math.sqrt(d)
    if causal:
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(mask[None, None], scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", probs, v.astype(jnp.float32))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
