"""Kimi Delta Attention (KDA): a linear-attention layer whose cache is a
recurrent STATE, not positions (docs/SERVING.md §The state slot; the Kimi
Linear technical report's layer as ``model_type: bailing_hybrid`` configures
it, ``models/bailing.py``).

From the normed input ``x`` of a token: ``q~, k~, v~ = x Wq, x Wk, x Wv``;
a depthwise causal convolution of width ``conv_width`` over the row's own
last positions, then SiLU; ``q`` and ``k`` L2-normalised by head, ``q`` times
``d_k^-1/2``; a per-CHANNEL log-decay ``g = lower_bound x sigmoid(exp(A_h) x
(x Wa + b))`` in ``(lower_bound, 0)``; ``beta = sigmoid(x Wb)`` a head.  A
head's state ``S`` [d_k, d_v] then takes the delta rule

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

and the output is ``RMSNorm_head(o_t) x sigmoid(x Wg)_head`` through ``Wo``.

**What a row keeps between steps** lies in two arrays with no page axis and
no position, addressed by the row's STATE SLOT (``serving/backend.py``):

* ``state``  float32 ``[KDA layers, slots, d_k, heads, d_v]`` — ``S``, a key
  channel after the other, each a ``[heads, d_v]`` slab (the kernel's
  layout: a slab is whole vector registers, heads on sublanes);
* ``tail``   ``[KDA layers, slots, conv_width - 1, 3 x heads x d_k]`` in the
  weights' dtype — the last ``conv_width - 1`` positions' ``(q~ | k~ | v~)``,
  which the next step's convolution reads behind the row's new tokens.

Slot 0 is the null slot: padding rows and unused table rows name it.  A row
whose first fed position is 0 starts from zeros whatever its slot held (a
reused slot is never cleared by the host).

**One ragged step** feeds a row ``n >= 1`` tokens from its slot's ``S``:
decode rows (one token) and prefill chunks (up to the budget) side by side.
The recurrence is computed TOKEN BY TOKEN, in float32, so nothing of it can
overflow whatever the decay (the chunk form's factorised decays pass float32
after 18 tokens at ``g = -5``; a later ``perf_opt`` may bring the chunk form
to the MXU in sub-blocks of 16).  Written as ``S_d = Diag(exp g) S``, ``u =
S_d^T k``, ``S = S_d + (beta k)(v - u)^T``, ``o = S^T q``: two passes over the
state a token.  Two forms, chosen where the program is LOWERED
(``jax.lax.platform_dependent``, as ``models/latent_walk.py``'s walk):

* :func:`rows_kernel` — a Pallas TPU kernel, :data:`KERNEL_NAME` in the
  lowered program and in a device trace.  Only the token body is this
  module's (:func:`_kernel`); everything round it is
  ``models/row_pipeline.py``'s, shared with ``ssd_step``.  **The grid** is
  the table's rows (static); grid step ``j`` advances the ``j``-th FED row of
  the step's work list (:class:`StateRows` ``work`` / ``fed``, made in
  :func:`state_rows`), the steps behind the list do nothing.  **The
  buffers**: two state buffers in VMEM, 2 MiB each at the published widths
  (``row_pipeline.BUFFERS``, or as many as :data:`VMEM_BUDGET_BYTES` holds
  beside the operands) and a DMA semaphore a buffer and direction; while a
  row's tokens run in one buffer, the next fed row's ``S`` is on its way
  from the state array (which stays in HBM) into the other.  **Waited for**:
  a row's read before its first token (the step's first fed row's is the
  only read nothing hides; a ``fresh`` row fills its buffer with zeros and
  reads nothing), a buffer's write-back before that buffer is filled again,
  every write-back before the call returns.  **One slot, one row**: a row's
  read starts before the rows ahead of it are written back, so a step never
  holds one slot in two fed rows (:class:`StateRows`);
* :func:`rows_jnp` — ``jax.numpy``, every other platform (the CPU's tests
  and references): the rows side by side, a round a token of the longest.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .llama import rms_norm

#: the platform whose lowering of the recurrence holds the kernel
PLATFORM = "tpu"
#: the kernel's name in the lowered program (its custom call) and in a trace
KERNEL_NAME = "kda_step"
#: VMEM the kernel may ask for: the five operands and the output whole (2 MiB
#: each at 128 buffer slots, and Pallas' pipeline holds each twice: 24 MiB)
#: and the row pipeline's state buffers (two of 2 MiB: 28 MiB in all;
#: ``row_pipeline.depth_for`` gives fewer where fewer fit)
VMEM_BUDGET_BYTES = 48 * 1024 * 1024
#: accumulators the two reductions over the key channels are spread over
#: (a chain of 128 dependent adds would wait for each add's latency)
ACCUMULATORS = 4


def init_state(n_layers: int, slots: int, heads: int, dk: int, dv: int, conv_width: int,
               dtype: Any) -> tuple[jax.Array, jax.Array]:
    """``(state, tail)``, zeroed (see the module docstring for the shapes)."""
    return (jnp.zeros((n_layers, slots, dk, heads, dv), jnp.float32),
            jnp.zeros((n_layers, slots, conv_width - 1, 3 * heads * dk), dtype))


class StateRows(NamedTuple):
    """What every KDA layer of one step shares: where each table row's tokens
    lie in the buffer, which slot holds its state, and the kernel's work
    list.  **A step never holds one slot in two fed rows** (a slot is a
    session's, a session one table row; rows that feed nothing name the null
    slot 0, which no fed row may name): the kernel's pipeline starts a row's
    read before the rows ahead of it are written back
    (``models/row_pipeline.py``)."""
    token_seq: jax.Array  # [T] table row of each buffer slot
    offset: jax.Array  # [T] a slot's place in its row's run (0: the row's first fed token)
    lo: jax.Array  # [R] a row's first buffer slot (T for a row that feeds nothing)
    n: jax.Array  # [R] tokens the row feeds (0 for the padding row)
    fresh: jax.Array  # [R] the row's first fed position is 0: it starts from zeros
    slot: jax.Array  # [R] the row's state slot (0: the null slot)
    work: jax.Array  # [R] the table rows that feed, in table order; behind the ``fed``-th: 0
    fed: jax.Array  # [] how many rows feed


def state_rows(positions: jax.Array, token_seq: jax.Array, state_slot: jax.Array) -> StateRows:
    """The step's :class:`StateRows` from the feed: ``state_slot`` int32 ``[S
    + 1]`` (the last row is the padding row), rows packed one behind the
    other in the buffer."""
    t_buf, r = positions.shape[0], state_slot.shape[0]
    at = jnp.arange(t_buf, dtype=jnp.int32)
    live_row = jnp.arange(r) < r - 1
    lo = jnp.full((r,), t_buf, jnp.int32).at[token_seq].min(at)
    n = jnp.where(live_row, jnp.zeros((r,), jnp.int32).at[token_seq].add(1), 0)
    first = positions[jnp.minimum(lo, t_buf - 1)]  # [R] the row's first fed position
    feeds = n > 0
    (work,) = jnp.nonzero(feeds, size=r, fill_value=0)
    return StateRows(token_seq, at - lo[token_seq], lo, n, (first == 0) | ~feeds,
                     jnp.where(feeds, state_slot, 0), work.astype(jnp.int32),
                     jnp.sum(feeds, dtype=jnp.int32))


def rows_prefetched(rows: StateRows) -> jax.Array:
    """Fed rows whose state the kernel's pipeline reads ahead of their turn:
    the carried (not ``fresh``) fed rows behind the work list's first."""
    carried = (rows.n > 0) & ~rows.fresh
    return jnp.sum(carried, dtype=jnp.int32) - carried[rows.work[0]].astype(jnp.int32)


# ---------------------------------------------------------------------------
# the short convolution over a row's own last positions
# ---------------------------------------------------------------------------


def short_conv(x: jax.Array, conv_w: jax.Array, tail: jax.Array, layer: int,
               rows: StateRows) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution of ``x`` [T, C] (a row's tokens one
    behind the other) with ``conv_w`` [width, C], tap ``width - 1`` on the
    token itself: a token's earlier positions are its row's earlier buffer
    slots, behind them the slot's ``tail`` (zeros for a fresh row).  Returns
    ``(y [T, C] float32, tail)`` with every fed row's tail written."""
    t_buf, width = x.shape[0], conv_w.shape[0]
    keep = width - 1
    old = jnp.where(rows.fresh[:, None, None], 0, tail[layer, rows.slot])  # [R, keep, C]
    w = conv_w.astype(jnp.float32)
    y = x.astype(jnp.float32) * w[keep]
    for d in range(1, width):
        # d positions back: in the buffer where the run reaches that far,
        # else in the tail (its last entry is one position back)
        behind = old[rows.token_seq, jnp.clip(keep - d + rows.offset, 0, keep - 1)]
        prev = jnp.where((rows.offset >= d)[:, None], jnp.roll(x, d, axis=0), behind)
        y = y + prev.astype(jnp.float32) * w[keep - d]
    # the row's new tail: the last ``keep`` of (old tail | the row's tokens)
    e = rows.n[:, None] - keep + jnp.arange(keep)[None, :]  # [R, keep] place in the run
    from_run = x[jnp.clip(rows.lo[:, None] + e, 0, t_buf - 1)]  # [R, keep, C]
    from_old = jnp.take_along_axis(old, jnp.clip(keep + e, 0, keep - 1)[:, :, None], axis=1)
    new = jnp.where((e >= 0)[:, :, None], from_run, from_old)
    return y, tail.at[layer, rows.slot].set(new.astype(tail.dtype))


# ---------------------------------------------------------------------------
# the recurrence, two forms
# ---------------------------------------------------------------------------


def rows_jnp(q, k, kb, eg, v, state, layer, rows: StateRows):
    """The step's recurrences as ``jax.numpy``: every table row side by
    side, a round a token of the longest row.  q, k, kb (``beta x k``), eg
    (``exp g``): float32 ``[T, heads, d_k]``; v ``[T, heads, d_v]``; state
    ``[layers, slots, d_k, heads, d_v]``.  Returns ``(o [T, heads, d_v],
    state)``; buffer slots no row feeds read zeros."""
    t_buf = q.shape[0]
    hi = jax.lax.Precision.HIGHEST
    s0 = jnp.where(rows.fresh[:, None, None, None], 0.0, state[layer, rows.slot])  # [R, dk, h, dv]

    def one(j, carry):
        s, o = carry
        at = jnp.minimum(rows.lo + j, t_buf - 1)
        act = j < rows.n
        sd = s * eg[at].transpose(0, 2, 1)[..., None]
        u = jnp.einsum("rhk,rkhv->rhv", k[at], sd, precision=hi)
        s2 = sd + jnp.einsum("rhk,rhv->rkhv", kb[at], v[at] - u, precision=hi)
        ot = jnp.einsum("rhk,rkhv->rhv", q[at], s2, precision=hi)
        return (jnp.where(act[:, None, None, None], s2, s),
                o.at[jnp.where(act, at, t_buf)].set(ot, mode="drop"))

    s, o = jax.lax.fori_loop(0, jnp.max(rows.n), one, (s0, jnp.zeros(v.shape, jnp.float32)))
    return o, state.at[layer, rows.slot].set(s)


def _kernel(*refs, dk: int):
    """A token's two passes over its row's state: ``s_ref`` [d_k, heads,
    d_v], the row's ``S`` in one of the pipeline's VMEM buffers.  Everything
    round it (which row, which buffer, what is copied when) is
    ``row_pipeline.pipeline``'s."""
    from . import row_pipeline

    lists, (q_ref, k_ref, kb_ref, eg_ref, v_ref, state_in, o_ref, *rest) = (
        refs[:row_pipeline.N_LISTS], refs[row_pipeline.N_LISTS:])
    add, mul, column = jax.lax.add, jax.lax.mul, row_pipeline.column  # cheap to trace

    def token(s_ref, at):
        kt, kbt, qt, egt, vt = k_ref[at], kb_ref[at], q_ref[at], eg_ref[at], v_ref[at]
        acc = [jnp.zeros(vt.shape, jnp.float32) for _ in range(ACCUMULATORS)]
        for c in range(dk):  # S_d = Diag(exp g) S; u = S_d^T k
            sd = mul(s_ref[c], column(egt, c))
            s_ref[c] = sd
            acc[c % ACCUMULATORS] = add(acc[c % ACCUMULATORS], mul(column(kt, c), sd))
        d = vt - sum(acc[1:], acc[0])
        acc = [jnp.zeros(vt.shape, jnp.float32) for _ in range(ACCUMULATORS)]
        for c in range(dk):  # S = S_d + (beta k)(v - u)^T; o = S^T q
            s2 = add(s_ref[c], mul(column(kbt, c), d))
            s_ref[c] = s2
            acc[c % ACCUMULATORS] = add(acc[c % ACCUMULATORS], mul(column(qt, c), s2))
        o_ref[at] = sum(acc[1:], acc[0])

    row_pipeline.pipeline(*lists, state_in, o_ref, *rest, token)


def rows_kernel(q, k, kb, eg, v, state, layer, rows: StateRows):
    """The same as :func:`rows_jnp` through the Pallas kernel: the operands
    whole in VMEM, the state array in HBM, the fed rows' states through the
    pipeline's buffers (``row_pipeline.advance_rows``)."""
    from . import row_pipeline

    return row_pipeline.advance_rows(
        partial(_kernel, dk=q.shape[2]), (q, k, kb, eg, v), v.shape[2], state, layer, rows,
        name=KERNEL_NAME, vmem_budget=VMEM_BUDGET_BYTES)


# jitted, with the layer a traced operand (as ``ssd.recurrence``): the KDA
# layers of a step program trace and lower ONE recurrence (the kernel unrolls
# two passes over ``d_k`` slabs)
@jax.jit
def recurrence(q, k, kb, eg, v, state, layer, rows: StateRows):
    """The step's recurrences by the form the lowering platform holds."""
    with jax.named_scope(KERNEL_NAME):
        return jax.lax.platform_dependent(
            q, k, kb, eg, v, state, layer, rows,
            default=rows_jnp, **{PLATFORM: rows_kernel})


# ---------------------------------------------------------------------------
# the sublayer
# ---------------------------------------------------------------------------


def kda_sublayer(a: jax.Array, layer: dict, state: jax.Array, tail: jax.Array, row: int,
                 rows: StateRows, cfg: Any) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One KDA sublayer over row ``row`` of the state arrays: ``a`` [T, d]
    (normed, in the weights' dtype) -> ``(the sublayer's output [T, d],
    state, tail)``.  ``cfg`` gives ``n_heads``, ``kda_dk``, ``kda_dv``,
    ``kda_lower_bound`` and ``norm_eps``; ``layer`` holds ``w_qkv`` [d, 3 x
    h x d_k] (q | k | v), ``conv_w`` [width, 3 x h x d_k], ``w_a`` [d, h x
    d_k], ``a_log`` [h], ``a_bias`` [h x d_k], ``w_beta`` [d, h], ``w_g`` [d,
    h], ``o_norm`` [d_v] and ``wo`` [h x d_v, d]."""
    t_buf = a.shape[0]
    h, dk, dv = cfg.n_heads, cfg.kda_dk, cfg.kda_dv
    with jax.named_scope("kda_proj"):
        x = a @ layer["w_qkv"]  # [T, 3 x h x dk]
        z = (a @ layer["w_a"]).astype(jnp.float32) + layer["a_bias"].astype(jnp.float32)
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(layer["a_log"].astype(jnp.float32))[None, :, None] * z.reshape(t_buf, h, dk))
        beta = jax.nn.sigmoid((a @ layer["w_beta"]).astype(jnp.float32))  # [T, h]
    with jax.named_scope("kda_conv"):
        y, tail = short_conv(x, layer["conv_w"], tail, row, rows)
        y = jax.nn.silu(y).reshape(t_buf, 3, h, dk)
        unit = lambda m: m * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(m * m, axis=-1, keepdims=True) + 1e-6)
        q, k, v = unit(y[:, 0]) * dk ** -0.5, unit(y[:, 1]), y[:, 2]
    o, state = recurrence(q, k, beta[..., None] * k, jnp.exp(g), v, state, row, rows)
    with jax.named_scope("kda_out"):
        o = rms_norm(o, layer["o_norm"], cfg.norm_eps)  # float32, a head at a time
        o = o * jax.nn.sigmoid((a @ layer["w_g"]).astype(jnp.float32))[..., None]
        return o.reshape(t_buf, h * dv).astype(a.dtype) @ layer["wo"], state, tail


__all__ = ["KERNEL_NAME", "PLATFORM", "StateRows", "init_state", "kda_sublayer", "recurrence",
           "rows_jnp", "rows_kernel", "rows_prefetched", "short_conv", "state_rows"]
