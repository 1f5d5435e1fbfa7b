"""Plain reference of the Xing4.0 decoder (``model_type: xing4_0``): the
DeepSeek-V3 line's latent attention and sigmoid-routed experts under a
residual of ``hc_mult`` streams a token joined by manifold-constrained
hyper-connections (mHC; Xie et al., arXiv 2512.24880, on Hyper-Connections,
Zhu et al., arXiv 2409.19606).

Straightforward ``jax.numpy`` in float32 under matmul precision "highest",
no cache, no batching, no paging, no kernels; it imports nothing of the
program.  The equations are ISSUE 49's (from the catalog row's keys and the
two papers; the points the five ``hc_*`` / ``mhc_*`` keys do not settle are
under ``assumed`` in the configuration file).  With ``n = hc_mult``, ``C =
hidden_size`` and a token's stream ``X`` in ``R^{n x C}``:

* entry: ``X[j] = embed(token)`` for every ``j``; exit: ``x = sum_j X[j]``,
  the final norm, the head;
* round every sublayer ``F`` (latent attention; then the dense SwiGLU or the
  expert layer), from the sublayer's own ``phi`` [n (n + 2), n C] (rows
  ``Phi_pre^T | Phi_post^T | Phi_res^T``), ``alpha`` [3] and ``bias`` [n (n +
  2)] (``b_pre | b_post | B_res`` row-major)::

      u = vec(X);  u^ = u / sqrt(mean(u^2) + rms_norm_eps)        (no gain)
      H_pre  = sigmoid(alpha_pre (u^ Phi_pre) + b_pre)
      H_post = 2 sigmoid(alpha_post (u^ Phi_post) + b_post)
      M = exp(clamp(alpha_res mat(u^ Phi_res) + B_res, clamp_min, clamp_max))
      hc_sinkhorn_iters times: M <- M / (column sums + hc_eps)
                               M <- M / (row sums + hc_eps)
      h = sum_j H_pre[j] X[j];  y = F(norm(h));
      X'[i] = sum_j M[i, j] X[j] + H_post[i] y

* the sublayers are A.X-K1's in their PUBLISHED form
  (``axk1_reference.attention_part``: keys and values expanded by head, not
  absorbed); the router is sigmoid over all experts with the selection bias
  in the choice only (``bailing_reference.route``, one group), the experts a
  plain loop over the held ones.  Those functions are imported: the same
  mathematics, plain functions of arrays that know no model.

Departures from the published description: none of mathematics; of scale,
those of ``axk1_reference`` (attention a group of heads and a block of
queries at a time, the feed-forward parts a block of tokens at a time, one
expert upcast at a time) and one of this file: ``attention_part`` returns its
input plus the attention's output, so the output is taken as the difference
(an error of one float32 rounding of ``h``, 1e-7 of a logit's unit).  The
class extends ``axk1_reference.Reference`` and keeps its jitted pieces.

``static_maps=True`` is a CONTROL, not a reference: the three ``alpha`` at 0,
the maps that a program would compute had it left the token-dependent part
out.  ``lower_precision=True`` is the other (``axk1_reference``'s int8
products in every sublayer; the maps and the router stay float32, as the
program keeps them).  ``correct`` must come out false on both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import axk1_reference
from .axk1_reference import HI, bucket_of
from .bailing_reference import route

ATTN_KEYS = ("norm_in", "q_norm", "kv_norm", "wqa", "wqb", "wkva", "wkvb", "wo")


def open_maps(x, w, *, n, iters, eps, clamp, norm_eps, static):
    """``x`` [T, n, C] -> ``(h [T, C], H_post [T, n], H_res [T, n, n])``."""
    t = x.shape[0]
    alpha = jnp.zeros((3,), jnp.float32) if static else w["alpha"].astype(jnp.float32)
    bias = w["bias"].astype(jnp.float32)
    u = x.reshape(t, -1)
    u = u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + norm_eps)
    z = jnp.matmul(u, w["phi"].astype(jnp.float32).T, precision=HI)  # [T, n (n + 2)]
    h_pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + bias[n:2 * n])
    r = alpha[2] * z[:, 2 * n:].reshape(t, n, n) + bias[2 * n:].reshape(n, n)
    m = jnp.exp(jnp.clip(r, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)  # column sums: over i of M[i, j]
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)  # row sums: over j
    return jnp.einsum("tj,tjc->tc", h_pre, x, precision=HI), h_post, m


def close_maps(x, y, h_post, h_res):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``."""
    return jnp.einsum("tij,tjc->tic", h_res, x, precision=HI) + h_post[:, :, None] * y[:, None, :]


class Reference(axk1_reference.Reference):
    """Teacher-forced forward over one padded sequence at a time (right
    padding is inert: attention is causal and every map is a token's own).
    A.X-K1's reference with a stream in place of its residual: that class's
    jitted pieces (the attention part, the pre-norm, the dense and the
    experts' feed-forward, the head) serve as they are; this one adds the two
    maps, the biased router and the streams at both ends."""

    def __init__(self, doc: dict, pad_to: int, *, static_maps: bool = False) -> None:
        super().__init__(doc, pad_to)
        n = int(doc["hc_mult"])
        self.route_kw = dict(
            top_k=doc["num_experts_per_tok"], n_group=doc["n_group"], topk_group=doc["topk_group"],
            route_scale=float(doc["routed_scaling_factor"]), route_norm=bool(doc["norm_topk_prob"]))
        mkw = dict(n=n, iters=int(doc["hc_sinkhorn_iters"]), eps=float(doc["hc_eps"]),
                   clamp=(float(doc["mhc_h_res_clamp_min"]), float(doc["mhc_h_res_clamp_max"])),
                   norm_eps=float(doc["rms_norm_eps"]), static=static_maps)
        self._streams = jax.jit(lambda x: jnp.repeat(x[:, None, :], n, axis=1))
        self._summed = jax.jit(lambda x: jnp.sum(x, axis=1))
        self._open = jax.jit(lambda x, w: open_maps(x, w, **mkw))
        self._close = jax.jit(close_maps)
        self._route = jax.jit(lambda m, r, b: route(m, r, b, **self.route_kw))

    def attention(self, h, w: dict, lp: bool = False):
        """The first sublayer over what the open map contracted, ``h`` [T,
        C]: ``attention_part`` gives its input plus the attention's output."""
        return self._attn[lp](h, {k: w[k] for k in ATTN_KEYS}) - h

    def feed_forward(self, h, w: dict, li: int, lp: bool = False):
        """The second sublayer over what the open map contracted, ``h`` [T, C]."""
        m = self._pre(h, w["norm_post"])
        if li < self.n_dense:
            return self._ffn[lp](m, w["w_gate"], w["w_up"], w["w_down"])
        sel, wt = self._route(m, w["router"], w["router_bias"])
        f = self._ffn[lp](m, w["s_gate"], w["s_up"], w["s_down"])
        for e in range(w["e_gate"].shape[0]):  # the experts held, one at a time
            f = f + self._expert[lp](m, sel, wt, self.first_expert + e,
                                     w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        return f

    def layer(self, x, w: dict, li: int, lp: bool = False):
        """One decoder block over a whole sequence's stream ``x`` [T, n, C]."""
        h, post, res = self._open(x, w["hc_attn"])
        x = self._close(x, self.attention(h, w, lp), post, res)
        h, post, res = self._open(x, w["hc_ffn"])
        return self._close(x, self.feed_forward(h, w, li, lp), post, res)

    def logits_of(self, params: dict, tokens: list[int], chosen: list[int],
                  *, lower_precision: bool = False):
        """→ (top, argmax, logit of ``chosen[p]``) per position p of
        ``tokens``, as numpy arrays of len(tokens)."""
        import numpy as np

        n = len(tokens)
        if n > self.pad_to or len(chosen) != n:
            raise ValueError((n, len(chosen), self.pad_to))
        pad = bucket_of(n)
        toks = np.zeros((pad,), np.int32)
        toks[:n] = tokens
        cho = np.zeros((pad,), np.int32)
        cho[:n] = chosen
        x = self._streams(self._embed(params["embed"], jnp.asarray(toks)))
        for li, w in enumerate(params["layers"]):
            x = self.layer(x, w, li, lower_precision)
        top, arg, got = self._head[lower_precision](
            self._summed(x), params["final_norm"], params["lm_head"], jnp.asarray(cho))
        return np.asarray(top)[:n], np.asarray(arg)[:n], np.asarray(got)[:n]
