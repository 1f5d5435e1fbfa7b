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
  lowered program and in a device trace: grid = the table's rows, one after
  the other; a live row's ``S`` (2 MiB at the published widths) is copied
  from the state array (which stays in HBM) into VMEM, advanced by the row's
  tokens there and copied back; rows that feed nothing cost a grid step;
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
#: each at 128 buffer slots), a row's state, and what the pipeline doubles
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
    lie in the buffer and which slot holds its state."""
    token_seq: jax.Array  # [T] table row of each buffer slot
    offset: jax.Array  # [T] a slot's place in its row's run (0: the row's first fed token)
    lo: jax.Array  # [R] a row's first buffer slot (T for a row that feeds nothing)
    n: jax.Array  # [R] tokens the row feeds (0 for the padding row)
    fresh: jax.Array  # [R] the row's first fed position is 0: it starts from zeros
    slot: jax.Array  # [R] the row's state slot (0: the null slot)


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
    fresh = (first == 0) | (n == 0)
    return StateRows(token_seq, at - lo[token_seq], lo, n, fresh,
                     jnp.where(n > 0, state_slot, 0))


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


def _kernel(lo_ref, n_ref, slot_ref, fresh_ref, layer_ref, q_ref, k_ref, kb_ref, eg_ref, v_ref,
            state_in, o_ref, state_out, s_ref, sem, *, dk: int):
    """One table row's tokens through its state, in VMEM.  ``s_ref`` [d_k,
    heads, d_v]: the row's ``S``; a token is two passes over its slabs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():  # buffer slots no row feeds read zeros, not what VMEM held
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    n = n_ref[i]

    @pl.when(n > 0)
    def _():
        layer, slot, lo = layer_ref[0], slot_ref[i], lo_ref[i]

        @pl.when(fresh_ref[i] == 0)
        def _():
            cp = pltpu.make_async_copy(state_in.at[layer, slot], s_ref, sem.at[0])
            cp.start()
            cp.wait()

        @pl.when(fresh_ref[i] != 0)
        def _():
            s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)

        def token(t, carry):
            at = lo + t
            kt, kbt, qt, egt, vt = k_ref[at], kb_ref[at], q_ref[at], eg_ref[at], v_ref[at]
            acc = [jnp.zeros(vt.shape, jnp.float32) for _ in range(ACCUMULATORS)]
            for c in range(dk):  # S_d = Diag(exp g) S; u = S_d^T k
                sd = s_ref[c] * egt[:, c:c + 1]
                s_ref[c] = sd
                acc[c % ACCUMULATORS] = acc[c % ACCUMULATORS] + kt[:, c:c + 1] * sd
            d = vt - sum(acc[1:], acc[0])
            acc = [jnp.zeros(vt.shape, jnp.float32) for _ in range(ACCUMULATORS)]
            for c in range(dk):  # S = S_d + (beta k)(v - u)^T; o = S^T q
                s2 = s_ref[c] + kbt[:, c:c + 1] * d
                s_ref[c] = s2
                acc[c % ACCUMULATORS] = acc[c % ACCUMULATORS] + qt[:, c:c + 1] * s2
            o_ref[at] = sum(acc[1:], acc[0])
            return carry

        jax.lax.fori_loop(0, n, token, 0)
        cp = pltpu.make_async_copy(s_ref, state_out.at[layer, slot], sem.at[0])
        cp.start()
        cp.wait()


def rows_kernel(q, k, kb, eg, v, state, layer, rows: StateRows):
    """The same as :func:`rows_jnp` through the Pallas kernel: the operands
    whole in VMEM (fetched once: their block does not move), the state array
    in HBM and updated in place (the result aliases it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_buf, h, dk = q.shape
    dv = v.shape[2]
    r = rows.n.shape[0]
    need = (2 * 5 * t_buf * h * max(dk, dv) + 2 * t_buf * h * dv + dk * h * dv) * 4
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(f"the recurrence's kernel needs {need} bytes of VMEM for a buffer of "
                         f"{t_buf} slots: over {VMEM_BUDGET_BYTES}")
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))  # noqa: E731
    o, state = pl.pallas_call(
        partial(_kernel, dk=dk),
        out_shape=(jax.ShapeDtypeStruct((t_buf, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(r,),
            in_specs=[whole(t_buf, h, dk), whole(t_buf, h, dk), whole(t_buf, h, dk),
                      whole(t_buf, h, dk), whole(t_buf, h, dv),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(whole(t_buf, h, dv), pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((dk, h, dv), jnp.float32),
                            pltpu.SemaphoreType.DMA((1,))]),
        # ``state`` (operand 10, behind the five prefetched and the five
        # blocked) is the second result
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_BUDGET_BYTES),
        name=KERNEL_NAME,
    )(jnp.minimum(rows.lo, t_buf - 1), rows.n, rows.slot, rows.fresh.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k, kb, eg, v, state)
    return o, state


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
           "rows_jnp", "rows_kernel", "short_conv", "state_rows"]
