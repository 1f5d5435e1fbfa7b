"""Plain reference of the AFMoE decoder (Arcee Trinity family, ``model_type:
afmoe``): sandwich RMSNorms, per-head q/k norms, a sigmoid output gate on the
attention, window layers with rotary positions and full layers with none, a
sigmoid router with a selection-only bias over all routed experts, one shared
expert, untied output head.

Straightforward ``jax.numpy`` in float32 under matmul precision "highest",
no cache, no batching, no paging, no kernels; it imports nothing of the
program.  The equations are ISSUE 26's (from the catalog row and the published
``modeling_afmoe.py`` as remembered; the points taken on trust are under
``assumed`` in the configuration file).  Departures: none from those
equations; two of scale, neither changing a number that is compared:

* attention is computed a block of queries at a time (``Q_BLOCK``) against
  all keys under the mask, so 16384 positions fit beside the weights (the
  whole ``[heads, T, T]`` score array would be 51 GB);
* the weights come in as the benchmark's bf16 pytree and are upcast a piece
  at a time: attention, the dense or shared part, and ONE EXPERT at a time
  (an expert layer's 32 experts would be 3.6 GB in float32 at once).

It is given the chip's share as the program is: ``first_expert`` and the
number of experts held (the leading dimension of the expert weights).  The
router scores all ``num_experts_routed`` experts, the weights are normalised
over all selected, and only the held experts' terms are summed: with all
experts held that is the whole layer.

``lower_precision=True`` is the CONTROL, not a reference: the same equations
with every matrix multiplication in int8 x int8 (per-output-channel weight
scales, per-row dynamic activation scales, int32 accumulation), the nearest
precision below bfloat16 that a v5e multiplies natively.  ``correct`` must
come out false on it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256
#: sequences are right-padded to one of these (causal attention makes the
#: padding inert), so a run compiles a few shapes and not one per length
BUCKETS = (128, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768, 65536, 131072, 262144)
HI = jax.lax.Precision.HIGHEST


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
    w = w.astype(jnp.float32)
    if lower_precision:
        return _quant_matmul(x, w)
    return jnp.matmul(x, w, precision=HI)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x: [T, H, Dh], positions 0..T-1; half-split rotation (the HF layout)."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _swiglu(m, gate, up, down, lp):
    return _mm(jax.nn.silu(_mm(m, gate, lp)) * _mm(m, up, lp), down, lp)


def attention_part(x, w, *, n_heads, n_kv_heads, head_dim, window, rope_theta, eps,
                   lower_precision=False):
    """``x -> x1 = x + norm_post_attn((o * sigmoid(g)) Wo)`` over a whole
    sequence ``x`` [T, d]; ``window`` None makes it a full layer (no
    positional encoding), a number a window layer (rotary q and k; position
    p sees keys p - window + 1 .. p)."""
    lp = lower_precision
    t = x.shape[0]
    a = _rms_norm(x, w["norm_in"], eps)
    q = _rms_norm(_mm(a, w["wq"], lp).reshape(t, n_heads, head_dim), w["q_norm"], eps)
    k = _rms_norm(_mm(a, w["wk"], lp).reshape(t, n_kv_heads, head_dim), w["k_norm"], eps)
    v = _mm(a, w["wv"], lp).reshape(t, n_kv_heads, head_dim)
    g = _mm(a, w["wg"], lp)
    if window is not None:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    group = n_heads // n_kv_heads
    qb = min(Q_BLOCK, t)
    q = q.reshape(t // qb, qb, n_kv_heads, group, head_dim)
    k_pos = jnp.arange(t)

    def one_block(args):
        qs, q0 = args  # [qb, kvh, group, hd], first position of the block
        scores = jnp.einsum("qkgd,skd->kgqs", qs, k, precision=HI) / math.sqrt(head_dim)
        q_pos = q0 + jnp.arange(qb)
        seen = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            seen &= q_pos[:, None] - k_pos[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs, v, precision=HI)

    o = jax.lax.map(one_block, (q, jnp.arange(t // qb) * qb)).reshape(t, n_heads * head_dim)
    return x + _rms_norm(_mm(o * jax.nn.sigmoid(g), w["wo"], lp), w["norm_post_attn"], eps)


def route(m, router, bias, *, top_k, route_scale, route_norm):
    """Scores of every token over all routed experts, in float32 whatever
    the control does elsewhere (the published router runs in float32):
    ``(sel [T, k], w [T, k])``; the bias decides the selection only."""
    s = jax.nn.sigmoid(jnp.matmul(m, router.astype(jnp.float32), precision=HI))
    _, sel = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    if route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel, w * route_scale


def expert_term(m, sel, w, expert_id, gate, up, down, lower_precision=False):
    """``w_e * Expert_e(m)`` for the tokens that selected expert ``expert_id``,
    zero for the others (computed for all and masked: plain, not fast)."""
    weight = jnp.sum(jnp.where(sel == expert_id, w, 0.0), axis=1, keepdims=True)  # [T, 1]
    return weight * _swiglu(m, gate, up, down, lower_precision)


class Reference:
    """Teacher-forced forward over one padded sequence at a time.  The
    jitted pieces (embedding, an attention part per kind of layer, the dense
    feed-forward, the router, one expert, the closing norm, the head) serve
    every layer and every sequence of a run; a sequence is right-padded to
    its own bucket of ``BUCKETS``; ``pad_to`` only bounds its length."""

    def __init__(self, doc: dict, pad_to: int) -> None:
        self.pad_to = int(pad_to)
        self.doc = doc
        eps = float(doc["rms_norm_eps"])
        self.eps = eps
        self.d = doc["hidden_size"]
        self.first_expert = int(doc.get("first_expert", 0))
        self.layer_types = list(doc["layer_types"])
        self.n_dense = int(doc["num_dense_layers"])
        akw = dict(n_heads=doc["num_attention_heads"], n_kv_heads=doc["num_key_value_heads"],
                   head_dim=doc["head_dim"], rope_theta=float(doc["rope_theta"]), eps=eps)
        window = int(doc["sliding_window"])
        rkw = dict(top_k=doc["num_experts_per_tok"], route_scale=float(doc["route_scale"]),
                   route_norm=bool(doc["route_norm"]))
        scale = math.sqrt(self.d) if doc.get("mup_enabled") else 1.0
        self._embed = jax.jit(lambda e, toks: e[toks].astype(jnp.float32) * scale)
        self._attn = {
            (kind, lp): jax.jit(lambda x, w, win=win, lp=lp: attention_part(
                x, w, window=win, lower_precision=lp, **akw))
            for kind, win in (("sliding_attention", window), ("full_attention", None))
            for lp in (False, True)}
        self._pre = jax.jit(lambda x, n: _rms_norm(x, n, eps))
        self._close = jax.jit(lambda x1, f, n: x1 + _rms_norm(f, n, eps))
        self._route = jax.jit(lambda m, r, b: route(m, r, b, **rkw))
        self._ffn = {lp: jax.jit(lambda m, g, u, dn, lp=lp: _swiglu(m, g, u, dn, lp))
                     for lp in (False, True)}
        self._expert = {lp: jax.jit(lambda m, sel, w, e, g, u, dn, lp=lp: expert_term(
            m, sel, w, e, g, u, dn, lp)) for lp in (False, True)}
        self._head = {lp: jax.jit(lambda x, n, w, c, lp=lp: head_forward(
            x, n, w, c, eps=eps, lower_precision=lp)) for lp in (False, True)}

    def layer(self, x, w: dict, li: int, lp: bool = False):
        """One decoder block over a whole sequence ``x`` [T, d] in float32."""
        names = ("norm_in", "q_norm", "k_norm", "wq", "wk", "wv", "wg", "wo", "norm_post_attn")
        x1 = self._attn[(self.layer_types[li], lp)](x, {k: w[k] for k in names})
        m = self._pre(x1, w["norm_pre_mlp"])
        if li < self.n_dense:
            f = self._ffn[lp](m, w["w_gate"], w["w_up"], w["w_down"])
        else:
            sel, wt = self._route(m, w["router"], w["router_bias"])
            f = self._ffn[lp](m, w["s_gate"], w["s_up"], w["s_down"])
            for e in range(w["e_gate"].shape[0]):  # the experts held, one at a time
                f = f + self._expert[lp](m, sel, wt, self.first_expert + e,
                                         w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        return self._close(x1, f, w["norm_post_mlp"])

    def logits_of(self, params: dict, tokens: list[int], chosen: list[int],
                  *, lower_precision: bool = False):
        """→ (top, argmax, logit of ``chosen[p]``) per position p of
        ``tokens``, as numpy arrays of len(tokens)."""
        import numpy as np

        n = len(tokens)
        if n > self.pad_to or len(chosen) != n:
            raise ValueError((n, len(chosen), self.pad_to))
        pad = next(b for b in BUCKETS if b >= n)
        toks = np.zeros((pad,), np.int32)
        toks[:n] = tokens
        cho = np.zeros((pad,), np.int32)
        cho[:n] = chosen
        x = self._embed(params["embed"], jnp.asarray(toks))
        for li, w in enumerate(params["layers"]):
            x = self.layer(x, w, li, lower_precision)
        top, arg, got = self._head[lower_precision](
            x, params["final_norm"], params["lm_head"], jnp.asarray(cho))
        return np.asarray(top)[:n], np.asarray(arg)[:n], np.asarray(got)[:n]


def head_forward(x, final_norm, lm_head, chosen, *, eps: float, lower_precision: bool = False):
    """Logits of every position, reduced at once to what the check reads:
    the best logit, its token, and the logit of ``chosen`` [T]."""
    logits = _mm(_rms_norm(x, final_norm, eps), lm_head, lower_precision)
    top = jnp.max(logits, axis=-1)
    arg = jnp.argmax(logits, axis=-1)
    got = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return top, arg, got
