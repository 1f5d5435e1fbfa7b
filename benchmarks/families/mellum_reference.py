"""Plain reference of the Mellum 2 decoder (JetBrains, ``model_type: mellum``,
the Qwen3-MoE line of keys): a plain pre-norm block, per-head RMSNorm of q and
k, window and full attention layers that BOTH rotate q and k, each kind under
its own table, and 64 small experts in every layer behind a softmax router
with no bias, no scale and no shared expert.

One layer over a whole sequence (``x`` float32 [T, d], ``eps`` 1e-6)::

    a   = rms_norm(x, g_in)
    q   = rms_norm_per_head((a Wq).reshape(T, h, hd), g_q)
    k   = rms_norm_per_head((a Wk).reshape(T, kvh, hd), g_k)
    v   =                   (a Wv).reshape(T, kvh, hd)
    q,k = rotate(q, k, positions, table[kind])       # half-split (rotate_half)
          sliding: inv_freq_j = theta^(-2j/hd), cos and sin as they are
          full:    YaRN: extrap_j = theta^(-2j/hd), interp_j = extrap_j / factor,
                   low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)) with
                   dim(r) = hd ln(original / (2 pi r)) / (2 ln theta), clipped to
                   [0, hd - 1]; ramp_j = clip((j - low) / (high - low), 0, 1);
                   inv_freq_j = interp_j ramp_j + extrap_j (1 - ramp_j);
                   cos and sin multiplied by ``attention_factor``
    o   = softmax(q k^T / sqrt(hd) + mask) v         # causal; a sliding layer
          sees keys with p - k < window, a full layer the whole row
    x   = x + o.reshape(T, h hd) Wo
    m   = rms_norm(x, g_post)
    r   = softmax(m Wr) over all experts, float32
    S   = top k of r;  w_e = r_e / sum_{e in S} r_e  # norm_topk_prob
    x   = x + sum_{e in S} w_e (silu(m Wg_e) * (m Wu_e)) Wd_e

then ``rms_norm(x, g_final)`` and the untied head.

Straightforward ``jax.numpy`` in float32 under matmul precision "highest", the
whole forward pass over a whole sequence, no cache, no pages, no kernel, no
batching; it imports nothing of the program.  Both rotations are made here
from the formulas above (``axk1_reference.yarn_inv_freq``: python floats from
``rope_parameters``), never from the program's tables.  Its generic pieces (the
int8 control's product, the norm, the half-split rotation, row blocks, one
expert's masked term, the blocked head) are ``axk1_reference``'s, imported:
plain functions of arrays that know no model.  Departures from the equations:
none; of scale: attention a block of queries at a time against all keys under
the mask (all 32 heads' scores over 20480 positions would be 54 GB), an
expert's products and the head a block of tokens at a time (the logits of
20480 positions over 98304 tokens would be 8 GB), the weights upcast a matrix
and ONE EXPERT at a time.  The points taken on trust are under ``assumed`` in
the configuration file.

It honours a share as the program does (``first_expert``, and the experts
held are the leading dimension of the expert weights); the committed file
holds all 64.

``lower_precision=True`` is the CONTROL, not a reference: every matrix
multiplication but the router's in int8 x int8.  ``correct`` must come out
false on it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .axk1_reference import (HI, Q_BLOCK, _mm, _rms_norm, _rope, bucket_of, expert_term,
                             head_forward, yarn_inv_freq)

KINDS = ("sliding_attention", "full_attention")


def rotation_of(doc: dict, kind: str) -> tuple[tuple[float, ...], float]:
    """``(inverse frequencies, factor on cos and sin)`` of one kind of layer,
    from ``rope_parameters[kind]``: plain where ``rope_type`` is default,
    YaRN with the file's ``attention_factor``."""
    rp = doc["rope_parameters"][kind]
    theta, hd = float(rp["rope_theta"]), doc["head_dim"]
    if rp.get("rope_type", "default") == "default":
        return tuple(yarn_inv_freq(hd, theta, None)), 1.0
    return tuple(yarn_inv_freq(hd, theta, rp)), float(rp["attention_factor"])


def attention_part(x, w, *, n_heads, n_kv_heads, head_dim, window, inv_freq, ratio, eps,
                   lower_precision=False):
    """``x -> x + Attn(rms_norm(x)) Wo`` over a whole sequence ``x`` [T, d];
    ``window`` None makes it a full layer, a number a window layer (position
    p sees keys p - window + 1 .. p)."""
    lp = lower_precision
    t, h, kvh, hd = x.shape[0], n_heads, n_kv_heads, head_dim
    a = _rms_norm(x, w["norm_in"], eps)
    q = _rms_norm(_mm(a, w["wq"], lp).reshape(t, h, hd), w["q_norm"], eps)
    k = _rms_norm(_mm(a, w["wk"], lp).reshape(t, kvh, hd), w["k_norm"], eps)
    v = _mm(a, w["wv"], lp).reshape(t, kvh, hd)
    q, k = _rope(q, inv_freq, ratio), _rope(k, inv_freq, ratio)
    qb = t if t % Q_BLOCK else Q_BLOCK  # an unpadded sequence is one block
    k_pos = jnp.arange(t)

    def one_block(args):
        qs, q0 = args  # [qb, kvh, rep, hd], first position of the block
        scores = jnp.einsum("qkgd,skd->kgqs", qs, k, precision=HI) / math.sqrt(hd)
        q_pos = q0 + jnp.arange(qb)
        seen = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            seen &= q_pos[:, None] - k_pos[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs, v, precision=HI)

    o = jax.lax.map(one_block, (q.reshape(t // qb, qb, kvh, h // kvh, hd),
                                jnp.arange(t // qb) * qb)).reshape(t, h * hd)
    return x + _mm(o, w["wo"], lp)


def route(m, router, *, top_k, norm_topk_prob):
    """Softmax scores of every token over all experts, in float32 whatever
    the control does elsewhere: ``(sel [T, k], w [T, k])``; the selected
    scores divided by their sum where ``norm_topk_prob``."""
    r = jax.nn.softmax(jnp.matmul(m, router.astype(jnp.float32), precision=HI), axis=-1)
    w, sel = jax.lax.top_k(r, top_k)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return sel, w


class Reference:
    """Teacher-forced forward over one padded sequence at a time.  The jitted
    pieces (embedding, an attention part per kind of layer, the norm, the
    router, one expert, the head) serve every layer and every sequence of a
    run; a sequence is right-padded to its own bucket (causal attention makes
    the padding inert); ``pad_to`` only bounds its length."""

    def __init__(self, doc: dict, pad_to: int) -> None:
        self.pad_to = int(pad_to)
        self.doc = doc
        eps = float(doc["rms_norm_eps"])
        self.first_expert = int(doc.get("first_expert", 0))
        self.layer_types = list(doc["layer_types"])
        akw = dict(n_heads=doc["num_attention_heads"], n_kv_heads=doc["num_key_value_heads"],
                   head_dim=doc["head_dim"], eps=eps)
        windows = {KINDS[0]: int(doc["sliding_window"]), KINDS[1]: None}
        rots = {kind: rotation_of(doc, kind) for kind in KINDS}
        rkw = dict(top_k=doc["num_experts_per_tok"], norm_topk_prob=bool(doc["norm_topk_prob"]))
        self._embed = jax.jit(lambda e, toks: e[toks].astype(jnp.float32))
        self._attn = {
            (kind, lp): jax.jit(lambda x, w, kind=kind, lp=lp: attention_part(
                x, w, window=windows[kind], inv_freq=rots[kind][0], ratio=rots[kind][1],
                lower_precision=lp, **akw))
            for kind in KINDS for lp in (False, True)}
        self._pre = jax.jit(lambda x, n: _rms_norm(x, n, eps))
        self._route = jax.jit(lambda m, r: route(m, r, **rkw))
        self._expert = {lp: jax.jit(lambda m, sel, w, e, g, u, dn, lp=lp: expert_term(
            m, sel, w, e, g, u, dn, lp)) for lp in (False, True)}
        self._head = {lp: jax.jit(lambda x, n, w, c, lp=lp: head_forward(
            x, n, w, c, eps=eps, lower_precision=lp)) for lp in (False, True)}

    def layer(self, x, w: dict, li: int, lp: bool = False):
        """One decoder block over a whole sequence ``x`` [T, d] in float32."""
        names = ("norm_in", "q_norm", "k_norm", "wq", "wk", "wv", "wo")
        x = self._attn[(self.layer_types[li], lp)](x, {k: w[k] for k in names})
        m = self._pre(x, w["norm_post"])
        sel, wt = self._route(m, w["router"])
        for e in range(w["e_gate"].shape[0]):  # the experts held, one at a time
            x = x + self._expert[lp](m, sel, wt, self.first_expert + e,
                                     w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        return x

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
        x = self._embed(params["embed"], jnp.asarray(toks))
        for li, w in enumerate(params["layers"]):
            x = self.layer(x, w, li, lower_precision)
        top, arg, got = self._head[lower_precision](
            x, params["final_norm"], params["lm_head"], jnp.asarray(cho))
        return np.asarray(top)[:n], np.asarray(arg)[:n], np.asarray(got)[:n]
