"""The grouped expert products of one expert layer as ONE Pallas TPU kernel
(docs/SERVING.md §The expert layer; ROADMAP S14).

``afmoe.expert_layer`` sorts a step's ``T x top_k`` assignment rows by held
expert and multiplies each group by its expert's three matrices: ``silu(x
Wg) * (x Wu)`` through ``Wd``.  As three ``jax.lax.ragged_dot`` calls that is
three passes which each read the rows again, write ``gate`` and ``up`` to HBM
and read them back, and stream the weights at a third of the chip's bandwidth
for the one to three rows an expert that a serving step brings (PERF.md
section 5, PR 41).  The work is a read of weights with next to no
arithmetic, so this kernel is built round that read:

* **a work list from ``counts``, made in the program** (:func:`work_list`,
  prefetched into scalar memory): one ITEM a (touched expert, run of its
  rows).  An expert nobody picked gets no item and no byte of its weights is
  read; the rows behind the last group (experts held elsewhere, identity
  picks, padding) are never visited.  The grid is static, ``(items, blocks
  of the expert width)``; the items past the last repeat the last item's
  block indices, so Pallas starts no copy for them, and do nothing;
* **each touched expert's weights once, in large blocks**: ``Wg[e][:, j]``
  and ``Wu[e][:, j]`` as ``[d, F]``, ``Wd[e][j, :]`` as ``[F, d]``, blocked
  operands indexed through the prefetched expert id, so Pallas' own pipeline
  fetches the next block, and the next EXPERT's first block, while this one
  is computed.  ``F`` (:func:`block_width`) follows ``d``, the dtype and
  :data:`VMEM_BUDGET_BYTES`, never a family;
* **gate, up and down fused**: ``h_j = silu(x Wg_j) * (x Wu_j)`` never
  leaves VMEM and ``acc += h_j Wd_j`` in float32, written once behind the
  last block.  Operands in the weights' dtype, float32 products, ``h``
  rounded to the weights' dtype ONCE (the ``ragged_dot`` form rounds
  ``gate`` and ``up`` before the ``silu`` and their product after it);
* **dropless**: any ``counts`` is legal.  An item spans at most
  :data:`ITEM_ROWS` rows from an :data:`ALIGN`-row boundary; a fatter group
  is several items of its expert, whose rows pass through the resident
  block :data:`CHUNK` at a time.

A group begins wherever the one before it ended, so an item's rows lie at no
boundary of the operand's tiling.  The operand and the result stay in HBM
and move in whole ``ALIGN``-row pieces: an item copies the pieces it spans
into VMEM, computes them whole (a piece's other rows are another expert's:
rows are independent, what they compute is never kept) and writes them back
whole; the piece it shares with the item before it is carried over in VMEM
and its earlier rows are put back before the write, so every visited row
ends with its own expert's product.  Rows no item visited hold nothing.

The module imports Pallas (a second or more of imports, PERF.md section 6,
PR 39), so nothing imports it at its own import: ``afmoe.expert_layer`` does
where it traces the products and a sparse family's ``ModelSpec.kernels``
(``afmoe.expert_label``, under a backend's ``startup.kernels`` phase);
``models/llama.py`` never does.  Which
form a program holds is decided where it is LOWERED
(``jax.lax.platform_dependent`` in ``afmoe.grouped_products``):
:data:`PLATFORM` gets this kernel, every other platform ``ragged_dot``;
:func:`holds_kernel` is the same rule for the host.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the platform whose lowering of the grouped products holds the kernel
PLATFORM = "tpu"
#: the kernel's name in the lowered program (its custom call) and in a trace
KERNEL_NAME = "expert_mlp"
#: VMEM the kernel may ask for (a v5e core has 128 MiB): half of it for the
#: double-buffered weight blocks (:func:`block_width`), the rest for an
#: item's rows, its float32 accumulator and a chunk's products.  Measured on
#: the chip at the four sparse cells' shapes (PERF.md section 6, PR 41):
#: blocks from 24 / 48 / 96 MiB read 80 / 76 / 76 % of the experts' roofline
#: at 91 small experts a call (the first block of a call is exposed, a
#: smaller one less) and 72 / 75 / 78 % at ONE large expert (a grid step
#: costs its 0.4 us): the middle of what that sweep could tell apart
VMEM_BUDGET_BYTES = 32 * 1024 * 1024
#: rows a piece: what the operand and the result move in between HBM and
#: VMEM (the sublane packing of a bfloat16 tile; two float32 tiles)
ALIGN = 16
#: rows a product: one pass of the resident weight block.  Two pieces, so
#: that a thin group (up to ``CHUNK - ALIGN + 1`` rows) is one pass wherever
#: it begins
CHUNK = 2 * ALIGN
#: rows an item spans at most, from the boundary at or before its first
ITEM_ROWS = 256


def block_width(d: int, fe: int, itemsize: int) -> int:
    """Columns of the expert width a block holds (``F``): the whole width
    where the three matrices fit twice in half the budget, else the widest
    multiple of 128 that divides it and fits; 0 where none does."""
    column = 2 * 3 * d * itemsize  # two buffers of gate, up and down, a column
    budget = VMEM_BUDGET_BYTES // 2
    if fe * column <= budget:
        return fe
    return max((f for f in range(128, fe, 128) if fe % f == 0 and f * column <= budget),
               default=0)


def vmem_bytes(d: int, f: int, itemsize: int) -> int:
    """What the kernel keeps in VMEM: the double-buffered weight blocks, an
    item's rows and accumulator, the carried piece, a chunk's products."""
    return (2 * 3 * d * f * itemsize + ITEM_ROWS * d * (itemsize + 4) + ALIGN * d * 4
            + CHUNK * (d + 3 * f) * 4)


def fits(d: int, fe: int, itemsize: int) -> bool:
    """Whether experts of these shapes have a block the budget holds."""
    f = block_width(d, fe, itemsize)
    return f > 0 and vmem_bytes(d, f, itemsize) <= VMEM_BUDGET_BYTES


def holds_kernel(platform: str, d: int, fe: int, itemsize: int) -> bool:
    """Whether a step program lowered for ``platform`` multiplies its groups
    with this kernel: the platform and the layer's shapes, nothing else."""
    return platform == PLATFORM and fits(d, fe, itemsize)


def max_items(held: int, rows: int) -> int:
    """The static grid: at most an item a touched expert, and one more for
    every ``ITEM_ROWS`` of a group's span (its rows and the up to ``ALIGN -
    1`` before them in its first piece)."""
    touched = min(held, rows)
    return max(1, touched + (rows + (ALIGN - 1) * touched) // ITEM_ROWS)


def item_counts(counts: Any) -> Any:
    """Items each held expert gets (numpy or jax int arrays, the experts on
    the last axis): the kernel's grid and the host's count alike."""
    xp = np if isinstance(counts, np.ndarray) else jnp
    ends = xp.cumsum(counts, axis=-1)
    span = ends - (ends - counts) // ALIGN * ALIGN  # from the boundary before its first row
    return xp.where(counts > 0, -(-span // ITEM_ROWS), 0)


def work_list(counts: jax.Array, rows: int) -> tuple[jax.Array, ...]:
    """``counts`` int32 ``[held]`` (each held expert's rows, the groups one
    behind the other from row 0) -> ``(expert [G], lo [G], hi [G], total
    [1])``: item ``i < total`` is rows ``[lo, hi)`` of the sorted operand
    under ``expert``; the items behind repeat the last one's."""
    held = counts.shape[0]
    g = max_items(held, rows)
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    per = item_counts(counts)
    item_ends = jnp.cumsum(per)
    total = item_ends[-1]
    at = jnp.minimum(jnp.arange(g, dtype=jnp.int32), jnp.maximum(total - 1, 0))
    # the expert of item ``at``: the experts whose items end at or before it
    # (one comparison of two small vectors: no loop in the program)
    e = jnp.minimum(jnp.sum(item_ends[None, :] <= at[:, None], axis=1, dtype=jnp.int32), held - 1)
    first = starts[e] // ALIGN * ALIGN + (at - (item_ends[e] - per[e])) * ITEM_ROWS
    lo = jnp.maximum(starts[e], first)
    hi = jnp.minimum(ends[e], first + ITEM_ROWS)
    return e, lo, hi, total.reshape(1)


def _kernel(e_ref, lo_ref, hi_ref, total_ref, x_hbm, wg_ref, wu_ref, wd_ref, out_hbm,
            xbuf, acc, carry, sems):
    del e_ref  # the weights' index maps read it
    i, j, nj = pl.program_id(0), pl.program_id(1), pl.num_programs(1)

    def piece(p):  # where piece p of an item lies in the scratch
        return pl.ds(pl.multiple_of(p * ALIGN, ALIGN), ALIGN)

    @pl.when(i < total_ref[0])
    def _():
        lo, hi = lo_ref[i], hi_ref[i]
        base = pl.multiple_of(lo // ALIGN * ALIGN, ALIGN)
        pieces = (hi - base + ALIGN - 1) // ALIGN

        def rows(p):  # the same piece in the operand and in the result
            return pl.ds(pl.multiple_of(base + p * ALIGN, ALIGN), ALIGN)

        def each_piece(copy):
            jax.lax.fori_loop(0, pieces, lambda p, _: copy(p).start(), None)
            jax.lax.fori_loop(0, pieces, lambda p, _: copy(p).wait(), None)

        @pl.when(j == 0)
        def _():
            # the piece this item begins in, as the item before left it
            @pl.when(lo > base)
            def _():
                before = lo_ref[i - 1] // ALIGN * ALIGN
                carry[...] = acc[piece((lo - 1 - before) // ALIGN)]

            each_piece(lambda p: pltpu.make_async_copy(
                x_hbm.at[rows(p)], xbuf.at[piece(p)], sems.at[0]))

        def chunk(c, _):
            at = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
            x = xbuf[at, :]
            gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
            up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
            h = (jax.nn.silu(gate) * up).astype(wd_ref.dtype)
            y = jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)

            @pl.when(j == 0)
            def _():
                acc[at, :] = y

            @pl.when(j > 0)
            def _():
                acc[at, :] += y

        jax.lax.fori_loop(0, (hi - base + CHUNK - 1) // CHUNK, chunk, None)

        @pl.when(j == nj - 1)
        def _():
            @pl.when(lo > base)
            def _():
                row = jax.lax.broadcasted_iota(jnp.int32, (ALIGN, 1), 0)
                acc[piece(0)] = jnp.where(row < lo - base, carry[...], acc[piece(0)])

            each_piece(lambda p: pltpu.make_async_copy(
                acc.at[piece(p)], out_hbm.at[rows(p)], sems.at[1]))


def expert_mlp(xs: jax.Array, e_gate: jax.Array, e_up: jax.Array, e_down: jax.Array,
               counts: jax.Array) -> jax.Array:
    """``xs`` ``[R, d]`` (the assignment rows sorted by held expert, in the
    weights' dtype), ``e_gate`` / ``e_up`` ``[held, d, fe]``, ``e_down``
    ``[held, fe, d]``, ``counts`` int32 ``[held]`` -> float32 ``[R, d]``:
    row ``r`` of group ``e`` holds ``(silu(x Wg[e]) * (x Wu[e])) Wd[e]``;
    the rows behind the last group hold nothing."""
    n_rows, d = xs.shape
    held, _, fe = e_gate.shape
    itemsize = e_gate.dtype.itemsize
    if not fits(d, fe, itemsize):
        raise ValueError(f"experts of {d} x {fe} in {itemsize}-byte elements: no block of them "
                         f"fits the kernel's {VMEM_BUDGET_BYTES} bytes of VMEM")
    f = block_width(d, fe, itemsize)
    nj = fe // f
    rows = -(-n_rows // ALIGN) * ALIGN  # whole pieces (the cells' operands are)
    xs = jnp.pad(xs.astype(e_gate.dtype), ((0, rows - n_rows), (0, 0)))

    def block(i, j, e, lo, hi, total):  # an idle item stays on the last item's last block
        return e[i], jnp.where(i < total[0], j, nj - 1)

    def gate_up(i, j, *lists):
        e, at = block(i, j, *lists)
        return e, 0, at

    def down(i, j, *lists):
        e, at = block(i, j, *lists)
        return e, at, 0

    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(max_items(held, rows), nj),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((None, d, f), gate_up),
                      pl.BlockSpec((None, d, f), gate_up),
                      pl.BlockSpec((None, f, d), down)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((ITEM_ROWS, d), e_gate.dtype),
                            pltpu.VMEM((ITEM_ROWS, d), jnp.float32),
                            pltpu.VMEM((ALIGN, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET_BYTES),
        name=KERNEL_NAME,
    )(*work_list(counts, rows), xs, e_gate, e_up, e_down)
    return out[:n_rows]


__all__ = ["ALIGN", "CHUNK", "ITEM_ROWS", "KERNEL_NAME", "PLATFORM", "VMEM_BUDGET_BYTES",
           "block_width", "expert_mlp", "fits", "holds_kernel", "item_counts", "max_items",
           "vmem_bytes", "work_list"]
