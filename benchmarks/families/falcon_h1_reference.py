"""Plain reference of the Falcon-H1 decoder (``model_type: falcon_h1``): a
Mamba-2 state-space mixer and grouped-query attention side by side in EVERY
layer, in their PUBLISHED forms, with the config's multiplier on every branch.

A layer (pre-norm, ``N`` = RMSNorm, hidden ``d``; every multiplier a key of
the configuration file)::

    u  = N_in(h)
    h1 = h + Attn(u) + SSM(u)            # both read the SAME normed input
    h' = h1 + FFN(N_ff(h1))

**Attention**: ``q = (u x attention_in_multiplier) Wq``, ``k = ((u x
attention_in_multiplier) Wk) x key_multiplier``, ``v = (u x
attention_in_multiplier) Wv`` (``Wq | Wk | Wv`` the columns of ONE stored
matrix), RoPE over the whole head (half-split, ``rope_theta``, no scaling) on
q and k, causal ``softmax(q k^T / sqrt(head_dim)) v`` with query head ``i``
reading K/V head ``i // (heads / kv heads)``, ``(. Wo) x
attention_out_multiplier``.

**State-space mixer** (Mamba-2): ``p = ((u x ssm_in_multiplier) W_in) x m``
with ``W_in``: d -> ``[z | x | B | C | dt]`` and ``m`` the vector that holds
``ssm_multipliers[0..4]`` over those spans; ``(x | B | C) <- SiLU(conv(x | B |
C) + b_conv)``, depthwise, causal, ``mamba_d_conv`` taps, zeros before position
0; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; head ``j`` of group
``g`` from ``S = 0`` TOKEN BY TOKEN (``jax.lax.scan``, elementwise float32, no
product a matmul unit could round)::

    S_j <- exp(dt_j A_j) S_j + dt_j x_j B_g^T          # [mamba_d_head, mamba_d_state]
    y_j  = S_j C_g + D_j x_j

``y <- N_group(y x SiLU(z))`` (gate THEN norm, the mean square over each
group's channels), ``(y W_out) x ssm_out_multiplier``.

**Feed-forward**: ``((SiLU((u2 Wg) x mlp_multipliers[0]) x (u2 Wu)) Wd) x
mlp_multipliers[1]``.  Embedding rows ``x embedding_multiplier``; final
RMSNorm; logits ``(h W_head) x lm_head_multiplier``, head untied.

Straightforward ``jax.numpy`` in float32 under matmul precision "highest", the
whole forward pass over a whole sequence, no cache, no slots, no pages, no
kernel, no batching; it imports nothing of the program.  Its generic pieces
(the int8 control's product, the norm, the half-split rotation, row blocks)
are ``axk1_reference``'s, imported: plain functions of arrays that know no
model.  Departures from the equations: none; of scale: attention a block of
queries at a time, the head and the feed-forward a block of tokens at a time,
the weights upcast a matrix at a time.  It is given the same cut as the
program: the layers and the vocabulary slice the file keeps.  The points taken
on trust are under ``assumed`` in the configuration file.

``lower_precision=True`` is the CONTROL, not a reference: every matrix
multiplication in int8 x int8; the convolution, the recurrence and the norms
in float32 as ever.  ``correct`` must come out false on it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .axk1_reference import HI, Q_BLOCK, TOKEN_BLOCK, _blocked, _mm, _rms_norm, _rope, bucket_of


def span_multipliers(doc: dict):
    """``m``: ``ssm_multipliers[i]`` over span ``i`` of ``[z | x | B | C | dt]``."""
    d_ssm, gn = doc["mamba_d_ssm"], doc["mamba_n_groups"] * doc["mamba_d_state"]
    spans = (d_ssm, d_ssm, gn, gn, doc["mamba_n_heads"])
    return jnp.concatenate([jnp.full((n,), m, jnp.float32)
                            for n, m in zip(spans, doc["ssm_multipliers"])])


def attention_part(u, w, *, n_heads, n_kv_heads, head_dim, inv_freq, in_mult, key_mult,
                   out_mult, lower_precision=False):
    """``Attn(u)`` over a whole sequence ``u`` [T, d] (normed)."""
    lp = lower_precision
    t, h, kvh, hd = u.shape[0], n_heads, n_kv_heads, head_dim
    qkv = _mm(u * in_mult, w["w_qkv"], lp)
    q = _rope(qkv[:, :h * hd].reshape(t, h, hd), inv_freq, 1.0)
    k = _rope((qkv[:, h * hd:(h + kvh) * hd] * key_mult).reshape(t, kvh, hd), inv_freq, 1.0)
    v = qkv[:, (h + kvh) * hd:].reshape(t, kvh, hd)
    qb = t if t % Q_BLOCK else Q_BLOCK  # an unpadded sequence is one block
    k_pos = jnp.arange(t)

    def one_block(args):
        qs, q0 = args  # [qb, kvh, rep, hd], first position of the block
        scores = jnp.einsum("qkgd,skd->kgqs", qs, k, precision=HI) / math.sqrt(hd)
        seen = (q0 + jnp.arange(qb))[:, None] >= k_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs, v, precision=HI)

    o = jax.lax.map(one_block, (q.reshape(t // qb, qb, kvh, h // kvh, hd),
                                jnp.arange(t // qb) * qb)).reshape(t, h * hd)
    return _mm(o, w["wo"], lp) * out_mult


def ssd_scan(x, b, c, dt, a, d_skip):
    """The recurrence over one sequence from a zero state: x [T, heads,
    d_head], b and c [T, groups, d_state], dt [T, heads], a and d_skip
    [heads] -> (the state behind the last token [heads, d_head, d_state], y
    [T, heads, d_head]).  Elementwise float32."""
    h, p = x.shape[1], x.shape[2]
    per = h // b.shape[1]

    def step(s, xs):
        xt, bt, ct, dtt = xs
        bh, ch = jnp.repeat(bt, per, axis=0), jnp.repeat(ct, per, axis=0)  # [h, N]
        s = jnp.exp(dtt * a)[:, None, None] * s + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :]
        return s, jnp.sum(s * ch[:, None, :], axis=-1) + d_skip[:, None] * xt

    return jax.lax.scan(step, jnp.zeros((h, p, b.shape[2]), jnp.float32), (x, b, c, dt))


def mixer_part(u, w, *, heads, d_head, d_state, groups, in_mult, spans, out_mult, eps,
               lower_precision=False, with_state=False):
    """``SSM(u)`` over a whole sequence ``u`` [T, d] (normed); with
    ``with_state`` also the state behind the last token."""
    lp = lower_precision
    t, d_ssm, gn = u.shape[0], heads * d_head, groups * d_state
    p = _blocked(lambda blk: _mm(blk * in_mult, w["w_in"], lp), u, TOKEN_BLOCK) * spans
    z, xbc, dt = p[:, :d_ssm], p[:, d_ssm:2 * d_ssm + 2 * gn], p[:, 2 * d_ssm + 2 * gn:]
    taps = w["conv_w"].astype(jnp.float32)
    width = taps.shape[0]
    y = xbc * taps[width - 1]
    for back in range(1, width):  # ``back`` positions back; zeros before position 0
        y = y + jnp.pad(xbc, ((back, 0), (0, 0)))[:t] * taps[width - 1 - back]
    xbc = jax.nn.silu(y + w["conv_b"].astype(jnp.float32))
    x = xbc[:, :d_ssm].reshape(t, heads, d_head)
    b = xbc[:, d_ssm:d_ssm + gn].reshape(t, groups, d_state)
    c = xbc[:, d_ssm + gn:].reshape(t, groups, d_state)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
    state, y = ssd_scan(x, b, c, dt, -jnp.exp(w["a_log"].astype(jnp.float32)),
                        w["d_skip"].astype(jnp.float32))
    y = (y.reshape(t, d_ssm) * jax.nn.silu(z)).reshape(t, groups, d_ssm // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(t, d_ssm) * w["ssm_norm"].astype(jnp.float32)
    out = _blocked(lambda blk: _mm(blk, w["w_out"], lp), y, TOKEN_BLOCK) * out_mult
    return (out, state) if with_state else out


def feed_forward(m, w, *, gate_mult, out_mult, lower_precision=False):
    lp = lower_precision
    return _blocked(lambda blk: _mm(
        jax.nn.silu(_mm(blk, w["w_gate"], lp) * gate_mult) * _mm(blk, w["w_up"], lp),
        w["w_down"], lp), m, TOKEN_BLOCK) * out_mult


def head_forward(x, final_norm, lm_head, chosen, *, eps, mult, lower_precision=False):
    """Logits of every position a block of rows at a time, reduced at once to
    what the check reads: the best logit, its token, the logit of ``chosen``."""
    xn = _rms_norm(x, final_norm, eps)
    t = xn.shape[0]

    def rows(args):
        blk, cho = args
        logits = _mm(blk, lm_head, lower_precision) * mult
        return (jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1),
                jnp.take_along_axis(logits, cho[:, None], axis=-1)[:, 0])

    if t <= TOKEN_BLOCK or t % TOKEN_BLOCK:
        return rows((xn, chosen))
    top, arg, got = jax.lax.map(rows, (xn.reshape(t // TOKEN_BLOCK, TOKEN_BLOCK, -1),
                                       chosen.reshape(t // TOKEN_BLOCK, TOKEN_BLOCK)))
    return top.reshape(t), arg.reshape(t), got.reshape(t)


ATTN_KEYS = ("w_qkv", "wo")
SSM_KEYS = ("w_in", "conv_w", "conv_b", "a_log", "dt_bias", "d_skip", "ssm_norm", "w_out")
FFN_KEYS = ("w_gate", "w_up", "w_down")


class Reference:
    """Teacher-forced forward over one padded sequence at a time (right
    padding is inert: attention and the recurrence are causal).  The jitted
    pieces serve every layer and every sequence of a run; a sequence is
    right-padded to its own bucket; ``pad_to`` only bounds its length."""

    def __init__(self, doc: dict, pad_to: int) -> None:
        self.pad_to = int(pad_to)
        self.doc = doc
        eps = float(doc["rms_norm_eps"])
        hd = doc["head_dim"]
        akw = dict(
            n_heads=doc["num_attention_heads"], n_kv_heads=doc["num_key_value_heads"],
            head_dim=hd,
            inv_freq=tuple(float(doc["rope_theta"]) ** (-2.0 * i / hd) for i in range(hd // 2)),
            in_mult=float(doc["attention_in_multiplier"]), key_mult=float(doc["key_multiplier"]),
            out_mult=float(doc["attention_out_multiplier"]))
        skw = dict(
            heads=doc["mamba_n_heads"], d_head=doc["mamba_d_head"], d_state=doc["mamba_d_state"],
            groups=doc["mamba_n_groups"], in_mult=float(doc["ssm_in_multiplier"]),
            out_mult=float(doc["ssm_out_multiplier"]), eps=eps)
        fkw = dict(gate_mult=float(doc["mlp_multipliers"][0]),
                   out_mult=float(doc["mlp_multipliers"][1]))
        e_mult, h_mult = float(doc["embedding_multiplier"]), float(doc["lm_head_multiplier"])
        self._embed = jax.jit(lambda e, toks: e[toks].astype(jnp.float32) * e_mult)
        self._pre = jax.jit(lambda x, n: _rms_norm(x, n, eps))
        self._attn = {lp: jax.jit(lambda u, w, lp=lp: attention_part(
            u, w, lower_precision=lp, **akw)) for lp in (False, True)}
        self._ssm = {lp: jax.jit(lambda u, w, lp=lp: mixer_part(
            u, w, spans=span_multipliers(doc), lower_precision=lp, **skw)) for lp in (False, True)}
        self._ssm_state = jax.jit(lambda u, w: mixer_part(
            u, w, spans=span_multipliers(doc), with_state=True, **skw))
        self._ffn = {lp: jax.jit(lambda m, w, lp=lp: feed_forward(
            m, w, lower_precision=lp, **fkw)) for lp in (False, True)}
        self._head = {lp: jax.jit(lambda x, n, w, c, lp=lp: head_forward(
            x, n, w, c, eps=eps, mult=h_mult, lower_precision=lp)) for lp in (False, True)}

    def embed(self, params: dict, tokens):
        """The stream a sequence of token ids enters the first block with."""
        return self._embed(params["embed"], jnp.asarray(tokens))

    def layer(self, x, w: dict, lp: bool = False, states: list | None = None):
        """One decoder block over a whole sequence ``x`` [T, d] in float32;
        the mixer's state behind the last token is appended to ``states``."""
        u = self._pre(x, w["norm_in"])
        ssm_w = {k: w[k] for k in SSM_KEYS}
        if states is None:
            s = self._ssm[lp](u, ssm_w)
        else:
            s, state = self._ssm_state(u, ssm_w)
            states.append(state)
        x = x + self._attn[lp](u, {k: w[k] for k in ATTN_KEYS}) + s
        return x + self._ffn[lp](self._pre(x, w["norm_ff"]), {k: w[k] for k in FFN_KEYS})

    def logits_of(self, params: dict, tokens: list[int], chosen: list[int],
                  *, lower_precision: bool = False):
        """-> (top, argmax, logit of ``chosen[p]``) per position p of
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
        for w in params["layers"]:
            x = self.layer(x, w, lower_precision)
        top, arg, got = self._head[lower_precision](
            x, params["final_norm"], params["lm_head"], jnp.asarray(cho))
        return np.asarray(top)[:n], np.asarray(arg)[:n], np.asarray(got)[:n]

    def ssm_states(self, params: dict, tokens: list[int]) -> list:
        """The state ``S`` [heads, d_head, d_state] of every layer behind the
        last of ``tokens``, fed unpadded from a zero state (padding would
        advance it): what a served row's state slot holds once it has fed them."""
        import numpy as np

        if len(tokens) > self.pad_to:
            raise ValueError((len(tokens), self.pad_to))
        states: list = []
        x = self.embed(params, np.asarray(tokens, np.int32))
        for w in params["layers"]:
            x = self.layer(x, w, states=states)
        return [np.asarray(s) for s in states]
