"""Plain reference of the A.X-K1 decoder (``model_type: axk1``, the
DeepSeek-V3 line): pre-norm residual blocks, latent attention (MLA) in its
PUBLISHED form, a group-limited sigmoid router over routed experts with one
shared expert, one leading dense layer, untied output head.

Straightforward ``jax.numpy`` in float32 under matmul precision "highest",
no cache, no batching, no paging, no kernels; it imports nothing of the
program.  The equations are ISSUE 30's (from the catalog row and the family's
published modeling code as remembered; the points taken on trust are under
``assumed`` in the configuration file).  **Attention is the published,
unabsorbed form**: every position's latent is expanded by ``Wkvb`` to a key
part and a value PER HEAD, the one rotated key part is broadcast to the
heads, and scores are taken between 192-wide queries and keys — where the
program under test never forms K and V by head (it folds ``Wkvb`` into the
query and the output: identical in exact arithmetic only).  Departures: none
from those equations; three of scale, none changing a number that is
compared:

* attention runs a GROUP of heads at a time (``HEAD_GROUP``), each group a
  block of queries at a time (``Q_BLOCK``) against all keys under the causal
  mask, so 32768 positions fit beside the weights (all 64 heads' keys and
  values at once would be 2.7 GB, their whole score array 275 GB);
* the feed-forward parts run a block of tokens at a time (``TOKEN_BLOCK``:
  the dense layer's hidden rows are 18432 wide);
* the weights come in as the benchmark's bf16 pytree and are upcast a piece
  at a time, ONE EXPERT at a time.

It is given the chip's share as the program is: ``first_expert`` and the
number of experts held (the leading dimension of the expert weights).  The
router scores all ``num_experts_routed`` experts and limits by group over all
of them, the weights are normalised over all selected, and only the held
experts' terms are summed: with all experts held that is the whole layer.

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
HEAD_GROUP = 8
TOKEN_BLOCK = 2048
#: sequences are right-padded to a multiple of this (causal attention makes
#: the padding inert), so a run compiles a few shapes and not one per length
BUCKET = 4096
SMALL_BUCKETS = (128, 256, 512, 1024, 2048)
HI = jax.lax.Precision.HIGHEST


def bucket_of(n: int) -> int:
    for b in SMALL_BUCKETS:
        if n <= b:
            return b
    return -(-n // BUCKET) * BUCKET


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


def yarn_inv_freq(dim: int, theta: float, scaling: dict | None):
    """Inverse frequencies of the ``dim`` rotated dimensions under YaRN: the
    original ones where a dimension turns more than ``beta_fast`` times over
    the original context, the interpolated ones (``/ factor``) where it turns
    fewer than ``beta_slow`` times, a linear ramp between (python floats)."""
    extra = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if not scaling or float(scaling.get("factor", 1.0)) <= 1.0:
        return extra
    factor, orig = float(scaling["factor"]), float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))), dim - 1)
    out = []
    for i, f in enumerate(extra):
        ramp = min(1.0, max(0.0, (i - low) / max(high - low, 1e-3)))
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out


def yarn_mscale(scaling: dict | None, key: str) -> float:
    if not scaling or float(scaling.get("factor", 1.0)) <= 1.0:
        return 1.0
    return 0.1 * float(scaling.get(key, 1.0)) * math.log(float(scaling["factor"])) + 1.0


def _rope(x, inv_freq, ratio: float):
    """x: [T, H, D], positions 0..T-1; half-split rotation (dimension i with
    i + D/2: the layout the published weights permute to before rotating)."""
    t, _, d = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * ratio, jnp.sin(ang)[:, None, :] * ratio
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _blocked(fn, m, block: int):
    """``fn`` over ``m`` [T, d] a block of rows at a time."""
    t = m.shape[0]
    if t <= block or t % block:
        return fn(m)
    return jax.lax.map(fn, m.reshape(t // block, block, -1)).reshape(t, -1)


def _swiglu(m, gate, up, down, lp):
    return _blocked(
        lambda b: _mm(jax.nn.silu(_mm(b, gate, lp)) * _mm(b, up, lp), down, lp), m, TOKEN_BLOCK)


def attention_part(x, w, *, n_heads, nope, rope_dim, v_dim, kv_rank, inv_freq, rope_ratio,
                   scale, eps, lower_precision=False):
    """``x -> x1 = x + concat_h(o_h) Wo`` over a whole sequence ``x`` [T, d],
    published form: per head ``k_h = (k_nope_h | kr)``, ``q_h = (q_nope_h |
    q_rope_h)``, ``o_h = softmax(scale q_h . k_h) v_h``."""
    lp = lower_precision
    t, h = x.shape[0], n_heads
    hg = min(HEAD_GROUP, h)
    a = _rms_norm(x, w["norm_in"], eps)
    cq = _rms_norm(_mm(a, w["wqa"], lp), w["q_norm"], eps)  # [T, q_rank]
    ckr = _mm(a, w["wkva"], lp)  # [T, kv_rank + rope_dim]
    c = _rms_norm(ckr[:, :kv_rank], w["kv_norm"], eps)
    kr = _rope(ckr[:, None, kv_rank:], inv_freq, rope_ratio)  # [T, 1, rope]: ONE key head
    # a group of heads' columns of the two up-projections, stacked by group
    wqb = w["wqb"].reshape(-1, h // hg, hg * (nope + rope_dim)).transpose(1, 0, 2)
    wkvb = w["wkvb"].reshape(-1, h // hg, hg * (nope + v_dim)).transpose(1, 0, 2)
    qb = min(Q_BLOCK, t)
    k_pos = jnp.arange(t)

    def one_group(ws):
        wq, wkv = ws
        q = _mm(cq, wq, lp).reshape(t, hg, nope + rope_dim)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq, rope_ratio)], axis=-1)
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


def route(m, router, *, top_k, n_group, topk_group, route_scale, route_norm):
    """Sigmoid scores of every token over all routed experts, in float32
    whatever the control does elsewhere: ``(sel [T, k], w [T, k])``.  Group
    limit: a group's score is the sum of its two best experts', a token picks
    among the experts of its ``topk_group`` best groups; no selection bias."""
    s = jax.nn.sigmoid(jnp.matmul(m, router.astype(jnp.float32), precision=HI))
    t, n = s.shape
    pick = s
    if n_group > 1:
        by_group = s.reshape(t, n_group, n // n_group)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)  # [T, n_group]
        _, kept = jax.lax.top_k(group_score, topk_group)
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        pick = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(t, n)
    _, sel = jax.lax.top_k(pick, top_k)
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
    jitted pieces (embedding, the attention part, the dense feed-forward, the
    router, one expert, the head) serve every layer and every sequence of a
    run; a sequence is right-padded to its own bucket; ``pad_to`` only bounds
    its length."""

    def __init__(self, doc: dict, pad_to: int) -> None:
        self.pad_to = int(pad_to)
        self.doc = doc
        eps = float(doc["rms_norm_eps"])
        self.first_expert = int(doc.get("first_expert", 0))
        self.n_dense = int(doc["first_k_dense_replace"])
        scaling = doc.get("rope_scaling")
        nope, rd = doc["qk_nope_head_dim"], doc["qk_rope_head_dim"]
        m_all = yarn_mscale(scaling, "mscale_all_dim")
        akw = dict(
            n_heads=doc["num_attention_heads"], nope=nope, rope_dim=rd, v_dim=doc["v_head_dim"],
            kv_rank=doc["kv_lora_rank"],
            inv_freq=tuple(yarn_inv_freq(rd, float(doc["rope_theta"]), scaling)),
            rope_ratio=yarn_mscale(scaling, "mscale") / m_all,
            scale=(nope + rd) ** -0.5 * m_all * m_all, eps=eps)
        rkw = dict(top_k=doc["num_experts_per_tok"], n_group=doc["n_group"],
                   topk_group=doc["topk_group"], route_scale=float(doc["routed_scaling_factor"]),
                   route_norm=bool(doc["norm_topk_prob"]))
        self._embed = jax.jit(lambda e, toks: e[toks].astype(jnp.float32))
        self._attn = {lp: jax.jit(lambda x, w, lp=lp: attention_part(
            x, w, lower_precision=lp, **akw)) for lp in (False, True)}
        self._pre = jax.jit(lambda x, n: _rms_norm(x, n, eps))
        self._route = jax.jit(lambda m, r: route(m, r, **rkw))
        self._ffn = {lp: jax.jit(lambda m, g, u, dn, lp=lp: _swiglu(m, g, u, dn, lp))
                     for lp in (False, True)}
        self._expert = {lp: jax.jit(lambda m, sel, w, e, g, u, dn, lp=lp: expert_term(
            m, sel, w, e, g, u, dn, lp)) for lp in (False, True)}
        self._head = {lp: jax.jit(lambda x, n, w, c, lp=lp: head_forward(
            x, n, w, c, eps=eps, lower_precision=lp)) for lp in (False, True)}

    def layer(self, x, w: dict, li: int, lp: bool = False):
        """One decoder block over a whole sequence ``x`` [T, d] in float32."""
        names = ("norm_in", "q_norm", "kv_norm", "wqa", "wqb", "wkva", "wkvb", "wo")
        x1 = self._attn[lp](x, {k: w[k] for k in names})
        m = self._pre(x1, w["norm_post"])
        if li < self.n_dense:
            f = self._ffn[lp](m, w["w_gate"], w["w_up"], w["w_down"])
        else:
            sel, wt = self._route(m, w["router"])
            f = self._ffn[lp](m, w["s_gate"], w["s_up"], w["s_down"])
            for e in range(w["e_gate"].shape[0]):  # the experts held, one at a time
                f = f + self._expert[lp](m, sel, wt, self.first_expert + e,
                                         w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        return x1 + f

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


def head_forward(x, final_norm, lm_head, chosen, *, eps: float, lower_precision: bool = False):
    """Logits of every position a block of rows at a time, reduced at once
    to what the check reads: the best logit, its token, the logit of
    ``chosen`` [T]."""
    xn = _rms_norm(x, final_norm, eps)
    t = xn.shape[0]

    def rows(args):
        b, c = args
        logits = _mm(b, lm_head, lower_precision)
        return (jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1),
                jnp.take_along_axis(logits, c[:, None], axis=-1)[:, 0])

    if t <= TOKEN_BLOCK or t % TOKEN_BLOCK:
        return rows((xn, chosen))
    top, arg, got = jax.lax.map(rows, (xn.reshape(t // TOKEN_BLOCK, TOKEN_BLOCK, -1),
                                       chosen.reshape(t // TOKEN_BLOCK, TOKEN_BLOCK)))
    return top.reshape(t), arg.reshape(t), got.reshape(t)
