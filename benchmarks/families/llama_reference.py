"""Plain reference of the llama-family decoder: RMSNorm, rotary positions,
grouped-query attention, SwiGLU, untied output head.

Straightforward ``jax.numpy`` in float32 under matmul precision "highest",
no cache, no batching, no paging; it imports nothing of the program.  It
follows the published equations of Mistral-7B-v0.3 / InternLM2 (the
Llama-2 block); departures: none.  The weights come in as the benchmark's
bf16 pytree and are upcast ONE LAYER AT A TIME, so the float32 copy of a
layer (0.9 GB at 7B widths) is all it adds beside them.

``lower_precision=True`` is the CONTROL, not a reference: the same
equations with every matrix multiplication in int8 x int8 (per-output-
channel weight scales, per-row dynamic activation scales, int32
accumulation), the nearest precision below bfloat16 that a v5e multiplies
natively.  `correct` must come out false on it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _quant_matmul(x, w):
    """``x @ w`` through int8: rows of ``x`` and columns of ``w`` are scaled
    to [-127, 127], rounded, multiplied with int32 accumulation, rescaled."""
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
    sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0 + 1e-30
    xq = jnp.round(x / sx).astype(jnp.int8)
    wq = jnp.round(w / sw).astype(jnp.int8)
    acc = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _mm(x, w, lower_precision: bool):
    if lower_precision:
        return _quant_matmul(x, w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, H, Dh], positions 0..T-1; half-split rotation (the HF layout)."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def layer_forward(x, layer, *, n_heads: int, n_kv_heads: int, head_dim: int,
                  rope_theta: float, eps: float, lower_precision: bool = False):
    """One decoder block over a whole sequence ``x`` [T, d] in float32."""
    w = {k: v.astype(jnp.float32) for k, v in layer.items()}
    t = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _mm(h, w["wq"], lower_precision).reshape(t, n_heads, head_dim)
    k = _mm(h, w["wk"], lower_precision).reshape(t, n_kv_heads, head_dim)
    v = _mm(h, w["wv"], lower_precision).reshape(t, n_kv_heads, head_dim)
    q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    group = n_heads // n_kv_heads
    q = q.reshape(t, n_kv_heads, group, head_dim)
    scores = jnp.einsum("qkgd,skd->kgqs", q, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(head_dim)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("kgqs,skd->qkgd", probs, v,
                      precision=jax.lax.Precision.HIGHEST).reshape(t, n_heads * head_dim)
    x = x + _mm(attn, w["wo"], lower_precision)
    h = _rms_norm(x, w["mlp_norm"], eps)
    gate = _mm(h, w["w_gate"], lower_precision)
    up = _mm(h, w["w_up"], lower_precision)
    return x + _mm(jax.nn.silu(gate) * up, w["w_down"], lower_precision)


def head_forward(x, final_norm, lm_head, chosen, *, eps: float, lower_precision: bool = False):
    """Logits of every position, reduced at once to what the check reads:
    the best logit, its token, and the logit of ``chosen`` [T]."""
    h = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    logits = _mm(h, lm_head.astype(jnp.float32), lower_precision)
    top = jnp.max(logits, axis=-1)
    arg = jnp.argmax(logits, axis=-1)
    got = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return top, arg, got


class Reference:
    """Teacher-forced forward over one padded sequence at a time.  Three
    jitted pieces (embed, one layer, head) serve every layer and every
    sequence of a run: the sequence is right-padded to ``pad_to`` (causal
    attention makes the padding inert)."""

    def __init__(self, doc: dict, pad_to: int) -> None:
        self.pad_to = int(pad_to)
        kw = dict(n_heads=doc["num_attention_heads"], n_kv_heads=doc["num_key_value_heads"],
                  head_dim=doc["head_dim"], rope_theta=float(doc["rope_theta"]),
                  eps=float(doc["rms_norm_eps"]))
        eps = kw["eps"]
        self._embed = jax.jit(lambda e, toks: e[toks].astype(jnp.float32))
        self._layer = {lp: jax.jit(lambda x, layer, lp=lp: layer_forward(
            x, layer, lower_precision=lp, **kw)) for lp in (False, True)}
        self._head = {lp: jax.jit(lambda x, n, w, c, lp=lp: head_forward(
            x, n, w, c, eps=eps, lower_precision=lp)) for lp in (False, True)}

    def logits_of(self, params: dict, tokens: list[int], chosen: list[int],
                  *, lower_precision: bool = False):
        """→ (top, argmax, logit of ``chosen[p]``) per position p of
        ``tokens``, as numpy arrays of len(tokens)."""
        import numpy as np

        n = len(tokens)
        if n > self.pad_to or len(chosen) != n:
            raise ValueError((n, len(chosen), self.pad_to))
        toks = np.zeros((self.pad_to,), np.int32)
        toks[:n] = tokens
        cho = np.zeros((self.pad_to,), np.int32)
        cho[:n] = chosen
        x = self._embed(params["embed"], jnp.asarray(toks))
        for layer in params["layers"]:
            x = self._layer[lower_precision](x, layer)
        top, arg, got = self._head[lower_precision](
            x, params["final_norm"], params["lm_head"], jnp.asarray(cho))
        return np.asarray(top)[:n], np.asarray(arg)[:n], np.asarray(got)[:n]
