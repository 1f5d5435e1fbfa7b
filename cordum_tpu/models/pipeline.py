"""Pipeline parallelism (``pp``): GPipe-style microbatch pipeline as a
shard_map program.

Each ``pp`` rank owns a contiguous stage of decoder layers (the stacked
per-stage params are sharded ``P('pp', ...)`` on their leading stage axis).
Microbatches stream through the ring: at every schedule tick each stage
applies its layers to the activation it holds, the last stage accumulates
logits/loss, and activations ``ppermute`` one hop down the pipeline — the
classic ``M + S - 1``-tick GPipe schedule with bubble ticks masked out.
``jax.grad`` differentiates straight through the ``ppermute`` chain, so the
backward pipeline falls out of autodiff (reverse permutes), no hand-written
schedule needed.

Composes with ``dp``: microbatch rows are sharded over ``dp`` and the loss
is averaged with a ``psum`` over both axes.  (``tp`` within a stage composes
via the same param-spec mechanism as models/llama.py; kept off in round 1
to keep the stage program small.)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import AXIS_DP, AXIS_PP
from .llama import LlamaConfig, rms_norm, rope


@dataclass(frozen=True)
class PipelineConfig:
    base: LlamaConfig = LlamaConfig.tiny()
    n_stages: int = 2
    n_microbatches: int = 2

    @property
    def layers_per_stage(self) -> int:
        assert self.base.n_layers % self.n_stages == 0, "n_layers must divide n_stages"
        return self.base.n_layers // self.n_stages


def init_params(key: jax.Array, cfg: PipelineConfig) -> dict:
    """Per-stage layer params stacked on a leading [n_stages, L/S] axis."""
    base = cfg.base
    d, h, kvh, hd, f = base.d_model, base.n_heads, base.n_kv_heads, base.head_dim, base.d_ff
    s, lps = cfg.n_stages, cfg.layers_per_stage
    ks = jax.random.split(key, 9)

    def dense(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(scale)).astype(base.dtype)

    def stack(k, shape, scale):
        return dense(k, (s, lps, *shape), scale)

    return {
        "embed": dense(ks[0], (base.vocab_size, d), d),
        "stages": {
            "attn_norm": jnp.ones((s, lps, d), base.dtype),
            "wq": stack(ks[1], (d, h * hd), d),
            "wk": stack(ks[2], (d, kvh * hd), d),
            "wv": stack(ks[3], (d, kvh * hd), d),
            "wo": stack(ks[4], (h * hd, d), h * hd),
            "mlp_norm": jnp.ones((s, lps, d), base.dtype),
            "w_gate": stack(ks[5], (d, f), d),
            "w_up": stack(ks[6], (d, f), d),
            "w_down": stack(ks[7], (f, d), f),
        },
        "final_norm": jnp.ones((d,), base.dtype),
        "lm_head": dense(ks[8], (d, base.vocab_size), d),
    }


def param_specs(cfg: PipelineConfig) -> dict:
    stage_spec = {k: P(AXIS_PP, *([None] * (3 if k.endswith("norm") else 4))[1:])
                  for k in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                            "w_gate", "w_up", "w_down")}
    # leading axis is the stage axis; norms are [S, L, D], weights [S, L, D, F]
    stage_spec = {
        k: (P(AXIS_PP, None, None) if k.endswith("norm") else P(AXIS_PP, None, None, None))
        for k in stage_spec
    }
    return {
        "embed": P(),
        "stages": stage_spec,
        "final_norm": P(),
        "lm_head": P(),
    }


def _stage_apply(stage_params: dict, x: jax.Array, positions: jax.Array, base: LlamaConfig) -> jax.Array:
    """Apply this stage's [L/S] layers to x: [mb, T, D] (scan over layers)."""

    def layer_step(h, layer):
        b, t, d = h.shape
        nh, kvh, hd = base.n_heads, base.n_kv_heads, base.head_dim
        attn_in = rms_norm(h, layer["attn_norm"], base.norm_eps)
        q = (attn_in @ layer["wq"]).reshape(b, t, nh, hd)
        k = (attn_in @ layer["wk"]).reshape(b, t, kvh, hd)
        v = (attn_in @ layer["wv"]).reshape(b, t, kvh, hd)
        q = rope(q, positions, base.rope_theta)
        k = rope(k, positions, base.rope_theta)
        rep = nh // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, nh * hd)
        h = h + attn @ layer["wo"]
        mlp_in = rms_norm(h, layer["mlp_norm"], base.norm_eps)
        h = h + (jax.nn.silu(mlp_in @ layer["w_gate"]) * (mlp_in @ layer["w_up"])) @ layer["w_down"]
        return h, None

    x, _ = jax.lax.scan(layer_step, x, stage_params)
    return x


def _pipeline_local(params: dict, tokens_mb: jax.Array, cfg: PipelineConfig,
                    *, pp_axis: str, dp_axis: str) -> tuple[jax.Array, jax.Array]:
    """Per-device body: tokens_mb [M, mb_local, T] → ([1,1] loss sum, [1,1]
    token count).  The cross-device reduction happens OUTSIDE the shard_map:
    claiming a replicated scalar output (out_specs=P()) would need the
    replication checker (``check_vma``) to prove it through the fori_loop,
    so each device returns its mapped partial sums instead."""
    base = cfg.base
    s = jax.lax.axis_size(pp_axis)
    stage = jax.lax.axis_index(pp_axis)
    m, mb, t = tokens_mb.shape
    d = base.d_model
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (mb, t))
    # this device's stage params: stacked leading axis is already sharded to
    # size 1 under shard_map → squeeze it
    stage_params = jax.tree.map(lambda p: p[0], params["stages"])

    n_ticks = m + s - 1
    perm = [(i, (i + 1) % s) for i in range(s)]

    def tick(i, carry):
        recv, loss_sum, tok_count = carry
        # stage 0 injects microbatch i (when in range); others use recv
        mb_idx = jnp.clip(i, 0, m - 1)
        injected = params["embed"][jax.lax.dynamic_index_in_dim(tokens_mb, mb_idx, 0, keepdims=False)]
        x = jnp.where(stage == 0, injected.astype(base.dtype), recv)
        y = _stage_apply(stage_params, x, positions, base)
        # last stage: compute loss for the microbatch that just completed
        out_idx = i - (s - 1)
        valid_out = jnp.logical_and(stage == s - 1, out_idx >= 0)
        tgt_mb = jax.lax.dynamic_index_in_dim(
            tokens_mb, jnp.clip(out_idx, 0, m - 1), 0, keepdims=False
        )
        h = rms_norm(y, params["final_norm"], base.norm_eps)
        logits = (h @ params["lm_head"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(logp, tgt_mb[:, 1:][..., None], axis=-1)[..., 0]
        # accumulate as [1,1] (never rank 0) so the per-device partial
        # sums map straight onto the (dp, pp) out_specs
        valid = valid_out.astype(jnp.float32).reshape(1, 1)
        loss_sum = loss_sum + valid * jnp.sum(nll, keepdims=True)
        tok_count = tok_count + valid * float(nll.size)
        recv = jax.lax.ppermute(y, pp_axis, perm)
        return recv, loss_sum, tok_count

    recv0 = jnp.zeros((mb, t, d), base.dtype)
    zero11 = jnp.zeros((1, 1), jnp.float32)
    _, loss_sum, tok_count = jax.lax.fori_loop(
        0, n_ticks, tick, (recv0, zero11, zero11)
    )
    return loss_sum, tok_count


def make_loss_fn(cfg: PipelineConfig, mesh: Mesh, *, pp_axis: str = AXIS_PP, dp_axis: str = AXIS_DP):
    pspecs = param_specs(cfg)
    tok_spec = P(None, dp_axis, None)  # [M, mb, T], mb sharded over dp
    part_spec = P(dp_axis, pp_axis)  # per-device [1,1] partial sums

    def loss(params, tokens_mb):
        fn = jax.shard_map(
            partial(_pipeline_local, cfg=cfg, pp_axis=pp_axis, dp_axis=dp_axis),
            mesh=mesh,
            in_specs=(pspecs, tok_spec),
            out_specs=(part_spec, part_spec),
            check_vma=False,
        )
        loss_sums, tok_counts = fn(params, tokens_mb)
        return jnp.sum(loss_sums) / jnp.maximum(jnp.sum(tok_counts), 1.0)

    return loss


def make_train_step(cfg: PipelineConfig, mesh: Mesh, optimizer=None):
    import optax

    opt = optimizer or optax.adamw(3e-4)
    pspecs = param_specs(cfg)
    param_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    tok_sharding = NamedSharding(mesh, P(None, AXIS_DP, None))
    loss_fn = make_loss_fn(cfg, mesh)

    def step(params, opt_state, tokens_mb):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens_mb)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    jstep = jax.jit(
        step,
        in_shardings=(param_shardings, None, tok_sharding),
        out_shardings=(param_shardings, None, None),
        donate_argnums=(0, 1),
    )

    def init(key):
        params = init_params(key, cfg)
        params = jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), params, pspecs
        )
        return params, opt.init(params)

    return init, jstep


def microbatch(tokens: jax.Array, n_micro: int) -> jax.Array:
    """[B, T] → [M, B/M, T]."""
    b, t = tokens.shape
    assert b % n_micro == 0, f"batch {b} not divisible by {n_micro} microbatches"
    return tokens.reshape(n_micro, b // n_micro, t)
