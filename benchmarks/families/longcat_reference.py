"""Plain reference of the LongCat-Flash decoder (``LongCat-Flash-Chat``): the
shortcut-connected block in its PUBLISHED form.

A layer (pre-norm, no bias, ``N`` = RMSNorm; j = 0, 1)::

    x1 = x  + MLA_0(N_in0(x));   m0 = N_post0(x1);   e = MoE(m0)
    x2 = x1 + FFN_0(m0)
    x3 = x2 + MLA_1(N_in1(x2));  m1 = N_post1(x3)
    x_out = x3 + FFN_1(m1) + e

``MLA_j(a)``: ``cq = N(a Wqa)``; ``q = s_q (cq Wqb)`` by head as ``(nope |
rope)``, the rope part rotated; ``(c_raw | kr_raw) = a Wkva``; ``c = s_kv
N(c_raw)``; ``kr = RoPE(kr_raw)``, one for all heads, not normed, not scaled;
``(k_nope_h | v_h) = (c Wkvb)_h``; scores ``(nope + rope)^-0.5 (q_nope_h .
k_nope_h + q_rope_h . kr)``, causal softmax, ``concat_h(P_h v_h) Wo``; ``s_q =
sqrt(hidden / q_lora_rank)`` and ``s_kv = sqrt(hidden / kv_lora_rank)`` where
``mla_scale_q_lora`` / ``mla_scale_kv_lora`` say so.  ``MoE(m)``: ``p =
softmax(m Wr)`` over the router's whole width (real experts, then
``zero_expert_num`` identity experts), ``sel`` = the ``moe_topk`` largest of
``p + b``, ``w_i = routed_scaling_factor x p_i`` (raw scores, not
normalised), ``MoE = sum over real i in sel of w_i E_i(m) + (sum over
identity i in sel of w_i) m``.

Straightforward ``jax.numpy`` in float32 under matmul precision "highest",
no cache, no batching, no paging, no kernels, **keys and values expanded by
head** (the program under test never forms them: it folds ``Wkvb`` into the
query and the output); it imports nothing of the program.  The equations are
ISSUE 32's; the points taken on trust are under ``assumed`` in the
configuration file.  Its generic pieces (the int8 control's product, the
norm, the half-split rotation, row blocks, one expert's term, the head) are
``axk1_reference``'s, imported: plain functions of arrays that know no model.
Departures: none from those equations; three of scale, as there: attention a
group of heads and a block of queries at a time, feed-forward parts a block
of tokens at a time, the bf16 weights upcast a piece at a time, ONE EXPERT at
a time.

It is given the chip's share as the program is: ``first_expert`` and the
number of REAL experts held (the leading dimension of the expert weights).
The router scores its whole width and only the held experts' terms are
summed; the identity part is whole (every chip computes it for its own
tokens).  With all real experts held that is the whole layer.

``lower_precision=True`` is the CONTROL, not a reference: every matrix
multiplication in int8 x int8, the router in float32 as ever (the identity
part has no product).  ``correct`` must come out false on it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .axk1_reference import (HEAD_GROUP, HI, Q_BLOCK, TOKEN_BLOCK, _blocked, _mm, _rms_norm,
                             _rope, _swiglu, bucket_of, expert_term, head_forward)

SUB_KEYS = ("norm_in", "q_norm", "kv_norm", "wqa", "wqb", "wkva", "wkvb", "wo")


def attention_part(x, w, *, n_heads, nope, rope_dim, v_dim, kv_rank, inv_freq, q_scale,
                   kv_scale, eps, lower_precision=False):
    """``x -> x + MLA(N_in(x))`` over a whole sequence ``x`` [T, d], published
    form: per head ``k_h = (k_nope_h | kr)``, ``q_h = (q_nope_h | q_rope_h)``,
    ``o_h = softmax((nope + rope)^-0.5 q_h . k_h) v_h``."""
    lp = lower_precision
    t, h = x.shape[0], n_heads
    hg = min(HEAD_GROUP, h)
    a = _rms_norm(x, w["norm_in"], eps)
    cq = _rms_norm(_mm(a, w["wqa"], lp), w["q_norm"], eps)  # [T, q_rank]
    ckr = _mm(a, w["wkva"], lp)  # [T, kv_rank + rope_dim]
    c = kv_scale * _rms_norm(ckr[:, :kv_rank], w["kv_norm"], eps)
    kr = _rope(ckr[:, None, kv_rank:], inv_freq, 1.0)  # [T, 1, rope]: ONE key head
    wqb = w["wqb"].reshape(-1, h // hg, hg * (nope + rope_dim)).transpose(1, 0, 2)
    wkvb = w["wkvb"].reshape(-1, h // hg, hg * (nope + v_dim)).transpose(1, 0, 2)
    qb = min(Q_BLOCK, t)
    k_pos = jnp.arange(t)
    scale = (nope + rope_dim) ** -0.5

    def one_group(ws):
        wq, wkv = ws
        q = q_scale * _mm(cq, wq, lp).reshape(t, hg, nope + rope_dim)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq, 1.0)], axis=-1)
        kv = _mm(c, wkv, lp).reshape(t, hg, nope + v_dim)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kr, (t, hg, rope_dim))], axis=-1)
        v = kv[..., nope:]

        def one_block(args):
            qs, q0 = args  # [qb, hg, nope + rope], first position of the block
            scores = jnp.einsum("qhd,shd->hqs", qs, k, precision=HI) * scale
            seen = (q0 + jnp.arange(qb))[:, None] >= k_pos[None, :]
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqs,shv->qhv", probs, v, precision=HI)

        return jax.lax.map(one_block, (q.reshape(t // qb, qb, hg, -1),
                                       jnp.arange(t // qb) * qb)).reshape(t, hg * v_dim)

    o = jax.lax.map(one_group, (wqb, wkvb))  # [groups, T, hg * v]
    o = o.transpose(1, 0, 2).reshape(t, h * v_dim)
    return x + _blocked(lambda b: _mm(b, w["wo"], lp), o, TOKEN_BLOCK)


def route(m, router, bias, *, top_k, route_scale):
    """Softmax scores of every token over the router's whole width, in
    float32 whatever the control does elsewhere: ``(sel [T, k], w [T, k])``.
    The bias takes part in the choice only; the weights are the chosen raw
    scores times the factor, not normalised."""
    p = jax.nn.softmax(jnp.matmul(m, router.astype(jnp.float32), precision=HI), axis=-1)
    _, sel = jax.lax.top_k(p + bias, top_k)
    return sel, jnp.take_along_axis(p, sel, axis=1) * route_scale


def identity_term(m, sel, w, n_real):
    """``(sum of w_i over the identity experts in sel) x m``."""
    return jnp.sum(jnp.where(sel >= n_real, w, 0.0), axis=1, keepdims=True) * m


class Reference:
    """Teacher-forced forward over one padded sequence at a time.  The
    jitted pieces (embedding, the attention part, a dense FFN, the router,
    one expert, the identity term, the head) serve every layer and every
    sequence of a run; a sequence is right-padded to its own bucket;
    ``pad_to`` only bounds its length."""

    def __init__(self, doc: dict, pad_to: int) -> None:
        self.pad_to = int(pad_to)
        self.doc = doc
        eps = float(doc["rms_norm_eps"])
        self.first_expert = int(doc.get("first_expert", 0))
        self.n_real = int(doc["num_experts_routed"]) - int(doc["zero_expert_num"])
        d, rd = doc["hidden_size"], doc["qk_rope_head_dim"]
        akw = dict(
            n_heads=doc["num_attention_heads"], nope=doc["qk_nope_head_dim"], rope_dim=rd,
            v_dim=doc["v_head_dim"], kv_rank=doc["kv_lora_rank"],
            inv_freq=tuple(float(doc["rope_theta"]) ** (-2.0 * i / rd) for i in range(rd // 2)),
            q_scale=math.sqrt(d / doc["q_lora_rank"]) if doc["mla_scale_q_lora"] else 1.0,
            kv_scale=math.sqrt(d / doc["kv_lora_rank"]) if doc["mla_scale_kv_lora"] else 1.0,
            eps=eps)
        rkw = dict(top_k=doc["moe_topk"], route_scale=float(doc["routed_scaling_factor"]))
        self._embed = jax.jit(lambda e, toks: e[toks].astype(jnp.float32))
        self._attn = {lp: jax.jit(lambda x, w, lp=lp: attention_part(
            x, w, lower_precision=lp, **akw)) for lp in (False, True)}
        self._pre = jax.jit(lambda x, n: _rms_norm(x, n, eps))
        self._route = jax.jit(lambda m, r, b: route(m, r, b, **rkw))
        self._identity = jax.jit(lambda m, sel, w: identity_term(m, sel, w, self.n_real))
        self._ffn = {lp: jax.jit(lambda m, g, u, dn, lp=lp: _swiglu(m, g, u, dn, lp))
                     for lp in (False, True)}
        self._expert = {lp: jax.jit(lambda m, sel, w, e, g, u, dn, lp=lp: expert_term(
            m, sel, w, e, g, u, dn, lp)) for lp in (False, True)}
        self._head = {lp: jax.jit(lambda x, n, w, c, lp=lp: head_forward(
            x, n, w, c, eps=eps, lower_precision=lp)) for lp in (False, True)}

    def expert_branch(self, m, w: dict, lp: bool = False):
        """``MoE(m)`` as this share gives it: the identity part whole, the
        held real experts' terms, one expert at a time."""
        sel, wt = self._route(m, w["router"], w["router_bias"])
        e = self._identity(m, sel, wt)
        for i in range(w["e_gate"].shape[0]):  # the real experts held
            e = e + self._expert[lp](m, sel, wt, self.first_expert + i,
                                     w["e_gate"][i], w["e_up"][i], w["e_down"][i])
        return e

    def layer(self, x, w: dict, lp: bool = False):
        """One shortcut-connected layer over a whole sequence ``x`` [T, d]."""
        e = None
        for j, sub in enumerate(w["sub"]):
            x = self._attn[lp](x, {k: sub[k] for k in SUB_KEYS})
            m = self._pre(x, sub["norm_post"])
            if j == 0:
                e = self.expert_branch(m, w, lp)
            x = x + self._ffn[lp](m, sub["w_gate"], sub["w_up"], sub["w_down"])
        return x + e

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
        for w in params["layers"]:
            x = self.layer(x, w, lower_precision)
        top, arg, got = self._head[lower_precision](
            x, params["final_norm"], params["lm_head"], jnp.asarray(cho))
        return np.asarray(top)[:n], np.asarray(arg)[:n], np.asarray(got)[:n]
