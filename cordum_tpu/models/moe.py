"""Mixture-of-Experts decoder blocks with expert parallelism (``ep``).

Extends the Llama-family decoder (models/llama.py) with a switch-style MoE
FFN: top-k routing, capacity-bounded one-hot dispatch (static shapes — no
gather/scatter with data-dependent sizes, so XLA tiles everything onto the
MXU), experts sharded over the ``ep`` mesh axis so expert FFN weights live
``n_experts/ep`` per device and token dispatch rides ICI all-to-alls that
GSPMD inserts from the shardings.

Router/dispatch design (compiler-friendly):
  * router logits → top-k expert ids + weights
  * position-in-expert computed with a cumulative-sum over the one-hot
    dispatch mask; tokens beyond ``capacity`` drop to the residual path
  * dispatch/combine as einsums against the one-hot mask (dense, static)

This is the TRAINING path, and it drops tokens beyond ``capacity``.  The
serving path's expert layer is ``models/afmoe.py`` ``expert_layer``: dropless,
told which experts it holds, grouped products over tokens sorted by expert.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import AXIS_DP, AXIS_EP, AXIS_SP, AXIS_TP
from . import llama as llama_mod
from .llama import LlamaConfig, rms_norm


@dataclass(frozen=True)
class MoEConfig:
    base: LlamaConfig = LlamaConfig.tiny()
    n_experts: int = 4
    top_k: int = 2
    capacity_factor: float = 1.25
    d_expert: int = 0  # 0 → base.d_ff

    @property
    def d_ff(self) -> int:
        return self.d_expert or self.base.d_ff

    @classmethod
    def tiny(cls) -> "MoEConfig":
        return cls(base=LlamaConfig.tiny(), n_experts=4, top_k=2)


def init_moe_layer(key: jax.Array, cfg: MoEConfig) -> dict:
    d, f, e = cfg.base.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)

    def dense(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(scale)).astype(cfg.base.dtype)

    return {
        "router": dense(ks[0], (d, e), d).astype(jnp.float32),  # fp32 routing
        "w_gate": dense(ks[1], (e, d, f), d),
        "w_up": dense(ks[2], (e, d, f), d),
        "w_down": dense(ks[3], (e, f, d), f),
    }


def moe_layer_specs() -> dict:
    """Experts sharded over ep; expert-internal FFN dim over tp."""
    return {
        "router": P(),
        "w_gate": P(AXIS_EP, None, AXIS_TP),
        "w_up": P(AXIS_EP, None, AXIS_TP),
        "w_down": P(AXIS_EP, AXIS_TP, None),
    }


def moe_ffn(x: jax.Array, layer: dict, cfg: MoEConfig, constrain=lambda v, s: v):
    """x: [B, T, D] → [B, T, D] plus aux losses dict."""
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n = b * t
    tokens = x.reshape(n, d)

    logits = tokens.astype(jnp.float32) @ layer["router"]  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_idx = jax.lax.top_k(probs, k)  # [N, k]
    topk_p = topk_p / jnp.maximum(jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9)

    capacity = max(1, int(cfg.capacity_factor * n * k / e))
    # one-hot dispatch with capacity: mask[N, k, E]
    onehot = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32)  # [N, k, E]
    # position of each (token, slot) within its expert queue
    flat = onehot.reshape(n * k, e)
    pos = jnp.cumsum(flat, axis=0) - flat  # positions start at 0
    pos = pos.reshape(n, k, e)
    within_cap = (pos < capacity).astype(jnp.float32) * onehot
    pos_idx = jnp.einsum("nke,nke->nk", pos, within_cap).astype(jnp.int32)  # [N,k]
    cap_onehot = jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)  # [N,k,C]
    # dispatch tensor [N, k, E, C] → combine weights folded in later
    dispatch = within_cap[..., None] * cap_onehot[:, :, None, :]
    # expert inputs [E, C, D]
    expert_in = jnp.einsum("nkec,nd->ecd", dispatch, tokens.astype(jnp.float32)).astype(x.dtype)
    expert_in = constrain(expert_in, P(AXIS_EP, None, None))
    # expert FFN (batched over E; E sharded over ep)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, layer["w_gate"]))
    up = jnp.einsum("ecd,edf->ecf", expert_in, layer["w_up"])
    out = jnp.einsum("ecf,efd->ecd", gate * up, layer["w_down"])  # [E, C, D]
    out = constrain(out, P(AXIS_EP, None, None))
    # combine back to tokens with routing weights
    combine = dispatch * topk_p[..., None, None]  # [N, k, E, C]
    y = jnp.einsum("nkec,ecd->nd", combine.astype(jnp.float32), out.astype(jnp.float32))

    # aux load-balancing loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    ce = jnp.mean(onehot.sum(1), axis=0)  # fraction of tokens per expert
    aux_loss = e * jnp.sum(me * ce)
    return y.reshape(b, t, d).astype(x.dtype), {"moe_aux_loss": aux_loss}


# ---------------------------------------------------------------------------
# full MoE decoder: llama attention + MoE FFN every layer
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: MoEConfig) -> dict:
    base_params = llama_mod.init_params(key, cfg.base)
    moe_keys = jax.random.split(jax.random.fold_in(key, 7), cfg.base.n_layers)
    for i, layer in enumerate(base_params["layers"]):
        layer.pop("w_gate", None)
        layer.pop("w_up", None)
        layer.pop("w_down", None)
        layer["moe"] = init_moe_layer(moe_keys[i], cfg)
    return base_params


def param_specs(cfg: MoEConfig) -> dict:
    specs = llama_mod.param_specs(cfg.base)
    for layer in specs["layers"]:
        layer.pop("w_gate", None)
        layer.pop("w_up", None)
        layer.pop("w_down", None)
        layer["moe"] = moe_layer_specs()
    return specs


def forward(params: dict, tokens: jax.Array, cfg: MoEConfig, *, mesh: Optional[Mesh] = None):
    """[B, T] → (logits [B, T, V], aux {moe_aux_loss})."""
    base = cfg.base
    if mesh is not None:
        def constrain(v, spec):
            return jax.lax.with_sharding_constraint(v, NamedSharding(mesh, spec))
    else:
        def constrain(v, spec):
            return v

    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    x = params["embed"][tokens]
    x = constrain(x, P(AXIS_DP, AXIS_SP, None))
    aux_total = jnp.zeros((), jnp.float32)
    for layer in params["layers"]:
        attn_in = rms_norm(x, layer["attn_norm"], base.norm_eps)
        h, kvh, hd = base.n_heads, base.n_kv_heads, base.head_dim
        q = (attn_in @ layer["wq"]).reshape(b, t, h, hd)
        k = (attn_in @ layer["wk"]).reshape(b, t, kvh, hd)
        v = (attn_in @ layer["wv"]).reshape(b, t, kvh, hd)
        q = llama_mod.rope(q, positions, base.rope_theta)
        k = llama_mod.rope(k, positions, base.rope_theta)
        k = constrain(k, P(AXIS_DP, None, None, None))
        v = constrain(v, P(AXIS_DP, None, None, None))
        attn = llama_mod._attention(q, k, v, base, q_offset=positions)
        x = x + attn.reshape(b, t, h * hd) @ layer["wo"]
        x = constrain(x, P(AXIS_DP, AXIS_SP, None))
        ffn_in = rms_norm(x, layer["mlp_norm"], base.norm_eps)
        y, aux = moe_ffn(ffn_in, layer["moe"], cfg, constrain)
        aux_total = aux_total + aux["moe_aux_loss"]
        x = x + y
        x = constrain(x, P(AXIS_DP, AXIS_SP, None))
    x = rms_norm(x, params["final_norm"], base.norm_eps)
    return x @ params["lm_head"], {"moe_aux_loss": aux_total / max(1, base.n_layers)}


def loss_fn(params: dict, tokens: jax.Array, cfg: MoEConfig, *, mesh=None, aux_weight: float = 0.01):
    logits, aux = forward(params, tokens, cfg, mesh=mesh)
    logits = logits.astype(jnp.float32)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux_weight * aux["moe_aux_loss"]


def make_train_step(cfg: MoEConfig, mesh: Mesh, optimizer=None):
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
        return params, opt.init(params)

    return init, jstep
