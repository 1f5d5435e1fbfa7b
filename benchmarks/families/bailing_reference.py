"""Plain reference of the Bailing-hybrid decoder (``Ling-3.0-flash``,
``model_type: bailing_hybrid``): layers of Kimi Delta Attention with every
``layer_group_size``-th a latent-attention layer, in their PUBLISHED forms.

A layer (pre-norm, no bias, ``N`` = RMSNorm)::

    x1 = x + Attn(N_in(x));   m = N_post(x1);   x_out = x1 + FFN_or_MoE(m)

``Attn`` of published layer ``i`` is latent attention where ``(i + 1) %
layer_group_size == 0`` and KDA otherwise.

**KDA** (``h`` heads, ``d_k = d_v = head_dim``, no positional encoding), from
the normed input ``a`` of a sequence: ``(q~ | k~ | v~) = a Wqkv``; each
channel convolved causally over the sequence with its own ``width`` taps (tap
``width - 1`` on the token itself, zeros before position 0), then SiLU; ``q``
and ``k`` L2-normalised by head, ``q`` times ``d_k^-1/2``; ``g_t =
kda_lower_bound x sigmoid(exp(A_h) x (a Wa + b))`` a channel; ``beta_t =
sigmoid(a Wb)`` a head; the recurrence TOKEN BY TOKEN from ``S_0 = 0``
(``jax.lax.scan``, elementwise float32, no product the matmul unit could
round)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

then ``concat_h(N_head(o_t) x sigmoid(a Wg)_h) Wo``.  **Latent attention**:
``q = a Wq`` by head as ``(nope | rope)`` (ONE matrix: ``q_lora_rank`` is
null), the rope part rotated; ``(c_raw | kr_raw) = a Wkva``; ``c = N(c_raw)``;
``kr = RoPE(kr_raw)``, one for all heads; ``(k_nope_h | v_h) = (c Wkvb)_h``;
scores ``(nope + rope)^-0.5 (q_nope_h . k_nope_h + q_rope_h . kr)``, causal
softmax, ``concat_h(P_h v_h x sigmoid(a Wg)_h) Wo`` — keys and values
EXPANDED by head (the program under test never forms them).  **MoE**: sigmoid
scores over the router's whole width, the selection bias in the CHOICE only,
``n_group`` groups scored by their two best (biased) experts of which
``topk_group`` are kept, ``top_k`` picks, weights the chosen raw scores
normalised over the picks and scaled, plus one shared expert.

Straightforward ``jax.numpy`` in float32 under matmul precision "highest",
no cache, no slots, no pages, no kernels; it imports nothing of the program.
Its generic pieces (the int8 control's product, the norm, the half-split
rotation, row blocks, one expert's term, the head) are ``axk1_reference``'s,
imported: plain functions of arrays that know no model.  Departures from the
equations: none; of scale, as there: attention a group of heads and a block
of queries at a time, products a block of tokens at a time, ONE EXPERT at a
time.  The points taken on trust are under ``assumed`` in the configuration
file.

It is given the chip's share as the program is: ``first_expert`` and the
number of experts held (the leading dimension of the expert weights).

``lower_precision=True`` is the CONTROL, not a reference: every matrix
multiplication in int8 x int8; the router, the convolution and the recurrence
in float32 as ever.  ``correct`` must come out false on it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .axk1_reference import (HEAD_GROUP, HI, Q_BLOCK, TOKEN_BLOCK, _blocked, _mm, _rms_norm,
                             _rope, _swiglu, bucket_of, expert_term, head_forward)

KDA_KEYS = ("norm_in", "w_qkv", "conv_w", "w_a", "a_log", "a_bias", "w_beta", "w_g", "o_norm",
            "wo")
MLA_KEYS = ("norm_in", "kv_norm", "wq", "wkva", "wkvb", "wg", "wo")


def layer_kinds(doc: dict) -> list[tuple[str, bool]]:
    """``(attention kind, dense FFN?)`` of every layer the file keeps, from
    their PUBLISHED indices (``kept_layers``)."""
    return [("mla" if (i + 1) % doc["layer_group_size"] == 0 else "kda",
             i < doc["first_k_dense_replace"]) for i in doc["kept_layers"]]


def kda_scan(q, k, v, g, beta):
    """The recurrence over one sequence from a zero state: q, k, g [T, h,
    d_k], v [T, h, d_v], beta [T, h] -> (the state behind the last token [h,
    d_k, d_v], o [T, h, d_v]).  Elementwise float32."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        sd = s * jnp.exp(gt)[:, :, None]  # Diag(exp g) S
        u = jnp.sum(kt[:, :, None] * sd, axis=1)  # S_d^T k  [h, dv]
        s = sd + (bt[:, None] * kt)[:, :, None] * (vt - u)[:, None, :]
        return s, jnp.sum(qt[:, :, None] * s, axis=1)

    return jax.lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32), (q, k, v, g, beta))


def kda_part(x, w, *, n_heads, dk, dv, lower_bound, eps, lower_precision=False,
             with_state=False):
    """``x -> x + KDA(N_in(x))`` over a whole sequence ``x`` [T, d]; with
    ``with_state`` also the state behind the last token, [h, d_k, d_v]."""
    lp = lower_precision
    t, h = x.shape[0], n_heads
    a = _rms_norm(x, w["norm_in"], eps)
    mm = lambda m, name: _blocked(lambda b: _mm(b, w[name], lp), m, TOKEN_BLOCK)  # noqa: E731
    xs = mm(a, "w_qkv")  # [T, 3 x h x dk]
    taps = w["conv_w"].astype(jnp.float32)
    width = taps.shape[0]
    y = xs * taps[width - 1]
    for d in range(1, width):  # d positions back; zeros before position 0
        y = y + jnp.pad(xs, ((d, 0), (0, 0)))[:t] * taps[width - 1 - d]
    y = jax.nn.silu(y).reshape(t, 3, h, dk)
    unit = lambda m: m / jnp.sqrt(jnp.sum(m * m, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k, v = unit(y[:, 0]) * dk ** -0.5, unit(y[:, 1]), y[:, 2]
    z = (mm(a, "w_a") + w["a_bias"].astype(jnp.float32)).reshape(t, h, dk)
    g = lower_bound * jax.nn.sigmoid(jnp.exp(w["a_log"].astype(jnp.float32))[None, :, None] * z)
    beta = jax.nn.sigmoid(mm(a, "w_beta"))  # [T, h]
    state, o = kda_scan(q, k, v, g, beta)  # o [T, h, dv]
    o = _rms_norm(o, w["o_norm"], eps) * jax.nn.sigmoid(mm(a, "w_g"))[:, :, None]
    out = x + mm(o.reshape(t, h * dv), "wo")
    return (out, state) if with_state else out


def mla_part(x, w, *, n_heads, nope, rope_dim, v_dim, kv_rank, inv_freq, eps,
             lower_precision=False):
    """``x -> x + MLA(N_in(x))`` over a whole sequence ``x`` [T, d], published
    form with ONE query matrix and the head-wise output gate."""
    lp = lower_precision
    t, h = x.shape[0], n_heads
    hg = min(HEAD_GROUP, h)
    a = _rms_norm(x, w["norm_in"], eps)
    ckr = _mm(a, w["wkva"], lp)  # [T, kv_rank + rope_dim]
    c = _rms_norm(ckr[:, :kv_rank], w["kv_norm"], eps)
    kr = _rope(ckr[:, None, kv_rank:], inv_freq, 1.0)  # [T, 1, rope]: ONE key head
    gate = jax.nn.sigmoid(_mm(a, w["wg"], lp))  # [T, h]
    wq = w["wq"].reshape(-1, h // hg, hg * (nope + rope_dim)).transpose(1, 0, 2)
    wkvb = w["wkvb"].reshape(-1, h // hg, hg * (nope + v_dim)).transpose(1, 0, 2)
    qb = min(Q_BLOCK, t)
    k_pos = jnp.arange(t)
    scale = (nope + rope_dim) ** -0.5

    def one_group(ws):
        wq_g, wkv = ws
        q = _mm(a, wq_g, lp).reshape(t, hg, nope + rope_dim)
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
                                       jnp.arange(t // qb) * qb)).reshape(t, hg, v_dim)

    o = jax.lax.map(one_group, (wq, wkvb))  # [groups, T, hg, v]
    o = o.transpose(1, 0, 2, 3).reshape(t, h, v_dim) * gate[:, :, None]
    return x + _blocked(lambda b: _mm(b, w["wo"], lp), o.reshape(t, h * v_dim), TOKEN_BLOCK)


def route(m, router, bias, *, top_k, n_group, topk_group, route_scale, route_norm):
    """Sigmoid scores of every token over all routed experts, in float32
    whatever the control does elsewhere: ``(sel [T, k], w [T, k])``.  The
    bias takes part in the choice only (``noaux_tc``): a group's score is the
    sum of its two best biased scores, a token picks among the experts of its
    ``topk_group`` best groups; the weights are the chosen raw scores."""
    s = jax.nn.sigmoid(jnp.matmul(m, router.astype(jnp.float32), precision=HI))
    t, n = s.shape
    pick = s + bias
    if n_group > 1:
        by_group = pick.reshape(t, n_group, n // n_group)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)  # [T, n_group]
        _, kept = jax.lax.top_k(group_score, topk_group)
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        pick = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(t, n)
    _, sel = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    if route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel, w * route_scale


class Reference:
    """Teacher-forced forward over one padded sequence at a time (right
    padding is inert: both kinds of attention are causal).  The jitted pieces
    serve every layer of a kind and every sequence of a run; ``pad_to`` only
    bounds a sequence's length."""

    def __init__(self, doc: dict, pad_to: int) -> None:
        self.pad_to = int(pad_to)
        self.doc = doc
        eps = float(doc["rms_norm_eps"])
        self.first_expert = int(doc.get("first_expert", 0))
        self.kinds = layer_kinds(doc)
        rd = doc["qk_rope_head_dim"]
        kkw = dict(n_heads=doc["num_attention_heads"], dk=doc["head_dim"], dv=doc["head_dim"],
                   lower_bound=float(doc["kda_lower_bound"]), eps=eps)
        akw = dict(
            n_heads=doc["num_attention_heads"], nope=doc["qk_nope_head_dim"], rope_dim=rd,
            v_dim=doc["v_head_dim"], kv_rank=doc["kv_lora_rank"],
            inv_freq=tuple(float(doc["rope_theta"]) ** (-2.0 * i / rd) for i in range(rd // 2)),
            eps=eps)
        rkw = dict(top_k=doc["num_experts_per_tok"], n_group=doc["n_group"],
                   topk_group=doc["topk_group"], route_scale=float(doc["routed_scaling_factor"]),
                   route_norm=bool(doc["norm_topk_prob"]))
        self.route_kw = rkw
        self._embed = jax.jit(lambda e, toks: e[toks].astype(jnp.float32))
        self._kda = {lp: jax.jit(lambda x, w, lp=lp: kda_part(
            x, w, lower_precision=lp, **kkw)) for lp in (False, True)}
        self._kda_state = jax.jit(lambda x, w: kda_part(x, w, with_state=True, **kkw))
        self._mla = {lp: jax.jit(lambda x, w, lp=lp: mla_part(
            x, w, lower_precision=lp, **akw)) for lp in (False, True)}
        self._pre = jax.jit(lambda x, n: _rms_norm(x, n, eps))
        self._route = jax.jit(lambda m, r, b: route(m, r, b, **rkw))
        self._ffn = {lp: jax.jit(lambda m, g, u, dn, lp=lp: _swiglu(m, g, u, dn, lp))
                     for lp in (False, True)}
        self._expert = {lp: jax.jit(lambda m, sel, w, e, g, u, dn, lp=lp: expert_term(
            m, sel, w, e, g, u, dn, lp)) for lp in (False, True)}
        self._head = {lp: jax.jit(lambda x, n, w, c, lp=lp: head_forward(
            x, n, w, c, eps=eps, lower_precision=lp)) for lp in (False, True)}

    def embed(self, params: dict, tokens):
        """The stream a sequence of token ids enters the first block with."""
        return self._embed(params["embed"], jnp.asarray(tokens))

    def expert_part(self, m, w: dict, lp: bool = False):
        """The shared expert plus the held experts' terms, one at a time."""
        sel, wt = self._route(m, w["router"], w["router_bias"])
        f = self._ffn[lp](m, w["s_gate"], w["s_up"], w["s_down"])
        for e in range(w["e_gate"].shape[0]):
            f = f + self._expert[lp](m, sel, wt, self.first_expert + e,
                                     w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        return f

    def attention_part(self, x, w: dict, li: int, lp: bool = False, states: list | None = None):
        """``(x1, m)`` of one block: the stream behind the attention sublayer
        and the normed input of the layer's FFN or expert layer (what its
        router scores)."""
        kind, _ = self.kinds[li]
        if kind == "kda" and states is not None:
            x1, state = self._kda_state(x, {k: w[k] for k in KDA_KEYS})
            states.append(state)
        elif kind == "kda":
            x1 = self._kda[lp](x, {k: w[k] for k in KDA_KEYS})
        else:
            x1 = self._mla[lp](x, {k: w[k] for k in MLA_KEYS})
        return x1, self._pre(x1, w["norm_post"])

    def layer(self, x, w: dict, li: int, lp: bool = False, states: list | None = None):
        """One decoder block over a whole sequence ``x`` [T, d] in float32;
        a KDA layer's state behind the last token is appended to ``states``."""
        dense = self.kinds[li][1]
        x1, m = self.attention_part(x, w, li, lp, states)
        if dense:
            return x1 + self._ffn[lp](m, w["w_gate"], w["w_up"], w["w_down"])
        return x1 + self.expert_part(m, w, lp)

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
        x = self.embed(params, toks)
        for li, w in enumerate(params["layers"]):
            x = self.layer(x, w, li, lower_precision)
        top, arg, got = self._head[lower_precision](
            x, params["final_norm"], params["lm_head"], jnp.asarray(cho))
        return np.asarray(top)[:n], np.asarray(arg)[:n], np.asarray(got)[:n]

    def kda_states(self, params: dict, tokens: list[int]) -> list:
        """The state ``S`` [h, d_k, d_v] of every KDA layer behind the last
        of ``tokens``, fed unpadded from a zero state (padding would advance
        it): what a served row's state slot holds once it has fed them."""
        import numpy as np

        if len(tokens) > self.pad_to:
            raise ValueError((len(tokens), self.pad_to))
        states: list = []
        x = self.embed(params, np.asarray(tokens, np.int32))
        for li, w in enumerate(params["layers"]):
            x = self.layer(x, w, li, states=states)
        return [np.asarray(s) for s in states]
