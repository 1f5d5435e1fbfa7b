"""The Mamba-2 state-space mixer (SSD) on the serving path: a layer whose
cache is a recurrent STATE a row, as ``models/kda.py``'s, with another
recurrence, another state shape and another block round it (``model_type:
falcon_h1``, ``models/falcon_h1.py``; docs/SERVING.md §The state slot).

From the normed input ``u`` of a token: ``p = (u W_in) x m``, ``W_in``: d ->
``[z d_ssm | x d_ssm | B groups x d_state | C groups x d_state | dt heads]``
and ``m`` the vector of the five spans' multipliers; ``(x | B | C)`` through a
depthwise causal convolution of ``conv_width`` taps over the row's own last
positions, a bias, then SiLU; ``dt = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``, a SCALAR a head.  Head ``j`` of group ``g = j // (heads /
groups)`` keeps a state ``S_j`` [head_dim, d_state] and takes

    S_j <- exp(dt_j A_j) S_j + dt_j x_j B_g^T
    y_j  = S_j C_g + D_j x_j

then ``y <- RMSNorm_group(y x SiLU(z))`` (gate, THEN norm; the mean square
over each group's ``d_ssm / groups`` channels) through ``W_out``.

**What a row keeps between steps**, in two arrays with no page axis,
addressed by the row's STATE SLOT (``serving/backend.py``):

* ``state``  float32 ``[layers, slots, d_state, heads, head_dim]``: ``S``, a
  state channel after the other, each a ``[heads, head_dim]`` slab (the
  kernel's layout: a slab is whole vector registers, heads on sublanes, and
  the eight heads of a register share their group's ``B`` and ``C``): 4 MiB a
  row and layer at 32 heads of 128 over 256 channels, twice KDA's;
* ``tail``   ``[layers, slots, conv_width - 1, d_ssm + 2 x groups x d_state]``
  in the weights' dtype: the last ``conv_width - 1`` positions' ``(x | B | C)``
  before the convolution, which the next step's convolution reads behind the
  row's new tokens.

Slot 0 is the null slot; a row whose first fed position is 0 starts from
zeros whatever its slot held.  Where each row's tokens lie and which slot is
its own is ``kda.state_rows``'s record, and the convolution is
``kda.short_conv``: both are that module's, used as they are.

**One ragged step** feeds a row ``n >= 1`` tokens from its slot's ``S``,
decode rows and prefill chunks side by side, TOKEN BY TOKEN in float32: one
pass over the state a token (decay, rank-one update, read-out).  The scalar
decay would make the chunk form of ``mamba_chunk_size`` safe in float32; it
is a later change, and :func:`recurrence` is where it goes.  Two forms,
chosen where the program is LOWERED (``jax.lax.platform_dependent``):

* :func:`rows_kernel`: a Pallas TPU kernel, :data:`KERNEL_NAME` in the
  lowered program and in a device trace.  Only the token body is this
  module's (:func:`_kernel`); everything round it is
  ``models/row_pipeline.py``'s, shared with ``kda_step``.  **The grid** is
  the table's rows (static); grid step ``j`` advances the ``j``-th FED row of
  the step's work list (``StateRows.work`` / ``fed``), the steps behind the
  list do nothing.  **The buffers**: two 4 MiB state buffers in VMEM
  (``row_pipeline.BUFFERS``, or as many as :data:`VMEM_BUDGET_BYTES` holds
  beside the operands) and a DMA semaphore a buffer and direction; while a
  row's tokens run in one buffer, the next fed row's ``S`` is on its way
  from the state array (which stays in HBM) into the other.  **Waited for**:
  a row's read before its first token (the step's first fed row's is the
  only read nothing hides; a ``fresh`` row fills its buffer with zeros and
  reads nothing), a buffer's write-back before that buffer is filled again,
  every write-back before the call returns.  **One slot, one row**: a row's
  read starts before the rows ahead of it are written back, so a step never
  holds one slot in two fed rows (``kda.StateRows``);
* :func:`rows_jnp`: ``jax.numpy``, every other platform (the CPU's tests and
  references) and the kernel's reference in tests.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import kda
from .kda import StateRows

#: the platform whose lowering of the recurrence holds the kernel
PLATFORM = "tpu"
#: the kernel's name in the lowered program (its custom call) and in a trace
KERNEL_NAME = "ssd_step"
#: VMEM the kernel may ask for: the four operands and the output whole
#: (fetched once, but Pallas' pipeline holds each twice: 47.7 MB at a buffer
#: of 208 slots) and the row pipeline's state buffers (two of 4 MiB: 56.1
#: MB in all; ``row_pipeline.depth_for`` gives fewer where fewer fit)
VMEM_BUDGET_BYTES = 100 * 1024 * 1024
#: accumulators the read-out's sum over the state channels is spread over
ACCUMULATORS = 4


def holds_kernel(platform: str) -> bool:
    """Whether a program lowered for ``platform`` advances the state with the
    kernel.  Where it does, Pallas is imported here, a second or more that the
    first lowering would pay unseen (``startup.kernels`` stamps this call)."""
    if platform != PLATFORM:
        return False
    from jax.experimental.pallas import tpu  # noqa: F401
    return True


def init_state(n_layers: int, slots: int, heads: int, head_dim: int, d_state: int, groups: int,
               conv_width: int, dtype: Any) -> tuple[jax.Array, jax.Array]:
    """``(state, tail)``, zeroed (see the module docstring for the shapes)."""
    return (jnp.zeros((n_layers, slots, d_state, heads, head_dim), jnp.float32),
            jnp.zeros((n_layers, slots, conv_width - 1,
                       heads * head_dim + 2 * groups * d_state), dtype))


# ---------------------------------------------------------------------------
# the recurrence, two forms
# ---------------------------------------------------------------------------


def rows_jnp(dtx, da, b, c, state, layer, rows: StateRows):
    """The step's recurrences as ``jax.numpy``: every table row side by
    side, a round a token of the longest row, elementwise float32 (no
    product a matmul unit could round).  dtx (``dt x``) float32 ``[T, heads,
    head_dim]``; da (``exp(dt A)``) ``[T, heads]``; b, c ``[T, heads,
    d_state]`` (a group's, repeated to its heads); state ``[layers, slots,
    d_state, heads, head_dim]``.  Returns ``(S C [T, heads, head_dim],
    state)``; buffer slots no row feeds read zeros."""
    t_buf = dtx.shape[0]
    s0 = jnp.where(rows.fresh[:, None, None, None], 0.0, state[layer, rows.slot])  # [R, N, h, P]

    def one(j, carry):
        s, o = carry
        at = jnp.minimum(rows.lo + j, t_buf - 1)
        act = j < rows.n
        s2 = (s * da[at][:, None, :, None]
              + b[at].transpose(0, 2, 1)[..., None] * dtx[at][:, None])
        ot = jnp.sum(s2 * c[at].transpose(0, 2, 1)[..., None], axis=1)  # [R, h, P]
        return (jnp.where(act[:, None, None, None], s2, s),
                o.at[jnp.where(act, at, t_buf)].set(ot, mode="drop"))

    s, o = jax.lax.fori_loop(0, jnp.max(rows.n), one, (s0, jnp.zeros(dtx.shape, jnp.float32)))
    return o, state.at[layer, rows.slot].set(s)


def _kernel(*refs, d_state: int):
    """A token's pass over its row's state: ``s_ref`` [d_state, heads,
    head_dim], the row's ``S`` in one of the pipeline's VMEM buffers; a token
    is one pass over its slabs.  Everything round it (which row, which
    buffer, what is copied when) is ``row_pipeline.pipeline``'s."""
    from . import row_pipeline

    lists, (dtx_ref, da_ref, b_ref, c_ref, state_in, o_ref, *rest) = (
        refs[:row_pipeline.N_LISTS], refs[row_pipeline.N_LISTS:])
    add, mul, column = jax.lax.add, jax.lax.mul, row_pipeline.column  # cheap to trace

    def token(s_ref, at):
        xt, dat, bt, ct = dtx_ref[at], da_ref[at], b_ref[at], c_ref[at]
        acc = [jnp.zeros(xt.shape, jnp.float32) for _ in range(ACCUMULATORS)]
        for ch in range(d_state):  # S = exp(dt A) S + (dt x) B^T; y = S C
            s2 = add(mul(s_ref[ch], dat), mul(column(bt, ch), xt))
            s_ref[ch] = s2
            acc[ch % ACCUMULATORS] = add(acc[ch % ACCUMULATORS], mul(column(ct, ch), s2))
        o_ref[at] = sum(acc[1:], acc[0])

    row_pipeline.pipeline(*lists, state_in, o_ref, *rest, token)


def rows_kernel(dtx, da, b, c, state, layer, rows: StateRows):
    """The same as :func:`rows_jnp` through the Pallas kernel: the operands
    whole in VMEM (the decay spread over a head's lanes, as the kernel
    multiplies it), the state array in HBM, the fed rows' states through the
    pipeline's buffers (``row_pipeline.advance_rows``)."""
    from . import row_pipeline

    t_buf, h, p = dtx.shape
    return row_pipeline.advance_rows(
        partial(_kernel, d_state=b.shape[2]),
        (dtx, jnp.broadcast_to(da[:, :, None], (t_buf, h, p)), b, c), p, state, layer, rows,
        name=KERNEL_NAME, vmem_budget=VMEM_BUDGET_BYTES)


# jitted, with the layer a traced operand: the layers of a step program trace
# and lower ONE recurrence (the kernel unrolls a pass over ``d_state`` slabs)
@jax.jit
def recurrence(x, b, c, dt, a, state, layer, rows: StateRows):
    """The step's recurrences by the form the lowering platform holds: x
    float32 ``[T, heads, head_dim]``, b and c ``[T, groups, d_state]``, dt
    ``[T, heads]`` (after the softplus), a ``[heads]`` (negative).  Returns
    ``(S C [T, heads, head_dim], state)``."""
    per = x.shape[1] // b.shape[1]
    with jax.named_scope(KERNEL_NAME):
        return jax.lax.platform_dependent(
            dt[:, :, None] * x, jnp.exp(dt * a[None, :]), jnp.repeat(b, per, axis=1),
            jnp.repeat(c, per, axis=1), state, layer, rows,
            default=rows_jnp, **{PLATFORM: rows_kernel})


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------


def in_multipliers(cfg: Any) -> np.ndarray:
    """``m``: the multiplier each column of ``W_in``'s output meets, ``ssm_in``
    times its span's own (``z | x | B | C | dt``)."""
    gn = cfg.ssm_groups * cfg.ssm_state
    spans = (cfg.d_ssm, cfg.d_ssm, gn, gn, cfg.ssm_heads)
    return cfg.ssm_in_multiplier * np.concatenate(
        [np.full((n,), m, np.float32) for n, m in zip(spans, cfg.ssm_multipliers)])


def group_norm(y: jax.Array, w: jax.Array, groups: int, eps: float) -> jax.Array:
    """RMSNorm of ``y`` [T, C] float32 with the mean square taken over each of
    ``groups`` runs of channels, then the gain ``w`` [C]."""
    t, ch = y.shape
    yg = y.reshape(t, groups, ch // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return yg.reshape(t, ch) * w.astype(jnp.float32)


def mixer(u: jax.Array, layer: dict, state: jax.Array, tail: jax.Array, row: Any,
          rows: StateRows, cfg: Any) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One state-space branch over row ``row`` of the state arrays: ``u`` [T,
    d] (normed, in the weights' dtype) -> ``(the branch's output [T, d]
    float32, state, tail)``.  ``cfg`` gives ``ssm_heads``, ``ssm_head_dim``,
    ``d_ssm``, ``ssm_state``, ``ssm_groups``, the multipliers and
    ``norm_eps``; ``layer`` holds ``w_in`` [d, 2 d_ssm + 2 groups d_state +
    heads], ``conv_w`` [width, d_ssm + 2 groups d_state], ``conv_b``, ``a_log``
    / ``dt_bias`` / ``d_skip`` [heads] in float32, ``ssm_norm`` [d_ssm] and
    ``w_out`` [d_ssm, d]."""
    t_buf = u.shape[0]
    h, p, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    d_ssm, gn = cfg.d_ssm, cfg.ssm_groups * cfg.ssm_state
    with jax.named_scope("ssd_proj"):
        proj = jnp.dot(u, layer["w_in"], preferred_element_type=jnp.float32) * in_multipliers(cfg)
        z = proj[:, :d_ssm]
        xbc = proj[:, d_ssm:2 * d_ssm + 2 * gn].astype(u.dtype)  # what the tail keeps
        dt = jax.nn.softplus(proj[:, 2 * d_ssm + 2 * gn:] + layer["dt_bias"])  # [T, h]
    with jax.named_scope("ssd_conv"):
        y, tail = kda.short_conv(xbc, layer["conv_w"], tail, row, rows)
        xbc = jax.nn.silu(y + layer["conv_b"].astype(jnp.float32))
        x = xbc[:, :d_ssm].reshape(t_buf, h, p)
        b = xbc[:, d_ssm:d_ssm + gn].reshape(t_buf, g, n)
        c = xbc[:, d_ssm + gn:].reshape(t_buf, g, n)
    o, state = recurrence(x, b, c, dt, -jnp.exp(layer["a_log"]), state, row, rows)
    with jax.named_scope("ssd_out"):
        y = (o + layer["d_skip"][None, :, None] * x).reshape(t_buf, d_ssm) * jax.nn.silu(z)
        y = group_norm(y, layer["ssm_norm"], g, cfg.norm_eps)
        out = jnp.dot(y.astype(u.dtype), layer["w_out"], preferred_element_type=jnp.float32)
        return out * cfg.ssm_out_multiplier, state, tail


__all__ = ["KERNEL_NAME", "PLATFORM", "group_norm", "holds_kernel", "in_multipliers",
           "init_state", "mixer", "recurrence", "rows_jnp", "rows_kernel"]
