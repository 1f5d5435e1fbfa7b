"""The latent walk of one group of tiles as ONE Pallas TPU kernel
(docs/SERVING.md §The ragged entry point; ROADMAP S2 step 2, the latent form).

``attention.paged_attention`` over a latent arena (``v_pages is None``: one
arena ``[rows, N, ps, width]``, one shared key head under all the query
heads, a key's leading ``v_dim`` columns its value) walks a group of
``ATTN_GROUP_TILES`` tiles block by block.  As ``jax.numpy`` that walk
sends a trip's intermediates through HBM: the gathered block written and
read again, five passes over the float32 scores, the 4 MiB accumulator of
the group read and rewritten whatever the block's length — 36 us a trip for
12 us of products (PERF.md section 6, PR 31).  XLA does not fuse product,
softmax and product; this kernel does:

* **grid = the tiles of the group**, one after the other on the chip's one
  core.  A tile's queries ``[slots x heads, width]`` are resident for its
  whole walk (a blocked operand, fetched once a tile while the tile before
  computes; an idle tile's index does not move, so nothing is fetched for
  it), its slots' positions are scalars (prefetched) from which the kernel
  builds the product rows' column once a tile;
* **the arena stays in HBM** (``memory_space=pl.ANY``) and is never gathered
  into a block that goes back there: a block's pages are copied page by page
  (``[ps, width]`` each, by the table row prefetched into scalar memory)
  straight into one half of a double-buffered VMEM block, the next block's
  copies in flight while this one is computed — across the end of a tile
  too: a tile's last trip starts the next tile's first block;
* **the scores (float32), the causal mask from the positions, the running
  maximum, sum and accumulator live in VMEM** across a tile's blocks, and
  the tile's output is written once, behind its last block;
* **each tile ends at ITS OWN newest block**: the block axis is a loop
  inside the kernel whose bound is the tile's own trip count (prefetched),
  so no copy is started and no product made past it.  A grid axis over a
  row's blocks would pay a grid step for every block of the TABLE (64 of
  them at 32768 positions) where a tile walks 18: the loop pays none.  An
  idle tile (on the padding row, no trips) writes zeros nobody reads.

Same numerics as the ``jax.numpy`` walk: operands in the arena's dtype,
float32 scores and state, probabilities cast to the arena's dtype for the
value product, a masked key scores ``-1e30``, ``scale`` as given.

The module imports Pallas, a second or more of imports (1.45 s on the
chip's host, PERF.md section 6, PR 39), so nothing imports it at its own
import: ``attention.walk_kernel`` does, for ``attention.paged_attention``
where it traces a form that has a kernel and for a family's
``ModelSpec.kernels`` where a backend holds such an arena
(``models/head_walk.py``, the kernel for K and V by head, imports what the two
kernels share in the kernel body: :data:`PLATFORM`, :func:`page_loop`, the
VMEM budget).  The loops over a
block's pages are ``fori_loop``s of
:data:`PAGE_UNROLL` copies a pass, not Python's over all 32: unrolled in
Python they made the step program's trace 1.3 s and its executable's load
1.1 s longer on that host, and ``setup_s`` is judged.

Which of the two walks a program holds is decided where the program is
LOWERED (``jax.lax.platform_dependent`` in ``attention.paged_attention``):
:data:`PLATFORM` gets this kernel, every other platform the ``jax.numpy``
walk; :func:`holds_kernel` is that rule, which ``attention.walk_kernel`` asks
for the trace and for the host alike (the host counts the walk as its program
makes it, each tile to its own end: ``attention.tile_trips``,
``attention.count_walk``).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the platform whose lowering of the latent walk holds the kernel
PLATFORM = "tpu"
#: the kernel's name in the lowered program (its custom call) and in a trace
KERNEL_NAME = "latent_walk"
#: VMEM the kernel may ask for: a v5e core has 128 MiB of which the compiler
#: grants 16 by default; the latent cells' kernel needs 4.5 by :func:`vmem_bytes`
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
#: page copies a pass of the kernel's loop over a block's pages.  Measured on
#: the chip (PERF.md section 6, PR 39), the walks of a step at 16k: all 32
#: unrolled 11.3 ms but a trace 1.3 s and an executable's load 1.1 s longer,
#: 8 a pass 11.8, 4 a pass 12.0, one a pass 14.5 (about 10 cycles a pass that
#: unrolled text hides among the vector bundles)
PAGE_UNROLL = 8


def holds_kernel(platform: str, latent: bool) -> bool:
    """Whether a step program lowered for ``platform`` walks its pages with
    this kernel: the arena's form and the platform, nothing else."""
    return latent and platform == PLATFORM


def page_loop(block_pages: int, copy: Any) -> None:
    """``copy(p)`` for every page of a block, inside a kernel: a loop in the
    kernel (the step program's trace and the executable do not grow with the
    block) of :data:`PAGE_UNROLL` pages a pass."""
    k = math.gcd(block_pages, PAGE_UNROLL)

    def some(c, _):
        for u in range(k):
            copy(c * k + u)

    jax.lax.fori_loop(0, block_pages // k, some, None)


def vmem_bytes(rows: int, width: int, v_dim: int, block_tokens: int, itemsize: int) -> int:
    """What the kernel keeps in VMEM: the double-buffered block, the blocked
    queries and output (two buffers each), the float32 state (a ``[rows,
    1]`` column takes whole 128-lane tiles: positions, maximum, sum), and the
    scores with their probabilities."""
    column = rows * 128 * 4
    return (2 * block_tokens * width * itemsize + 2 * rows * width * itemsize
            + 2 * rows * v_dim * itemsize + 3 * column + rows * v_dim * 4
            + rows * block_tokens * (2 * 4 + itemsize))


def _kernel(at_ref, trips_ref, src_ref, pos_ref, tab_ref, q_ref, arena_ref, _, out_ref,
            kbuf, sems, m_ref, l_ref, acc_ref, *,
            block_pages: int, page_size: int, slots: int, v_dim: int, scale: float,
            tab_width: int):
    del src_ref  # the queries' index map reads it
    i, g = pl.program_id(0), pl.num_programs(0)
    n = trips_ref[i]
    row = at_ref[0]
    bp, ps = block_pages, page_size
    bt = bp * ps
    rows = q_ref.shape[1]
    # each product row's position: its slot's, ``rows // slots`` heads a slot
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    pos = jnp.zeros((rows, 1), jnp.int32)
    for slot in range(slots):
        pos = jnp.where(at >= slot * (rows // slots), pos_ref[i * slots + slot], pos)

    def page(p):  # where page p of a block lands in a half
        return pl.ds(pl.multiple_of(p * ps, ps), ps)

    pages = partial(page_loop, bp)

    def start(tile, j, half):
        # block j of a tile: its pages, one copy each, into one half
        base = tile * tab_width + j * bp
        pages(lambda p: pltpu.make_async_copy(
            arena_ref.at[row, tab_ref[base + p]], kbuf.at[half, page(p)],
            sems.at[half]).start())

    def wait(half):
        pages(lambda p: pltpu.make_async_copy(
            arena_ref.at[0, 0], kbuf.at[half, page(p)], sems.at[half]).wait())

    # the halves alternate over the GROUP's trips, so that a tile's last
    # trip can start the next tile's first block behind its own
    half0 = jax.lax.fori_loop(0, i, lambda k, s: s + trips_ref[k], 0) % 2
    ahead = trips_ref[jnp.maximum(i - 1, 0)]
    behind = trips_ref[jnp.minimum(i + 1, g - 1)]

    @pl.when((n > 0) & ((i == 0) | (ahead == 0)))
    def _():
        start(i, 0, half0)

    @pl.when(n > 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(j, _):
        half = (half0 + j) % 2
        wait(half)

        @pl.when(j + 1 < n)
        def _():
            start(i, j + 1, 1 - half)

        @pl.when((j + 1 == n) & (i + 1 < g) & (behind > 0))
        def _():
            start(i + 1, 0, 1 - half)

        kb = kbuf[half]  # [bt, width]
        s = jax.lax.dot_general(q_ref[0], kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, -1e30)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(kb.dtype), kb[:, :v_dim], preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    jax.lax.fori_loop(0, n, block, None)

    @pl.when(n > 0)
    def _():
        out_ref[0] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def walk_group(q: jax.Array, q_pos: jax.Array, arena: jax.Array, row: Any, tab: jax.Array,
               trips: jax.Array, out: jax.Array, first: Any = 0, *,
               block_pages: int, v_dim: int, scale: float) -> jax.Array:
    """The walk of one group of ``G`` tiles, tiles ``first`` to ``first + G``
    of a step's.  q: ``[tiles, rows, width]``, every tile's queries (a tile's
    slots x heads, in the arena's dtype: the group's are read in place);
    q_pos: int32 ``[G, slots]``, each slot's position (``rows // slots``
    product rows a slot); arena: ``[arena rows, N, ps, width]``; row: the
    arena row (a traced int); tab: int32 ``[G, P]``, each tile's table row,
    ``P`` a whole number of blocks; trips: int32 ``[G]``
    (``attention.tile_trips``); out: ``[tiles, rows, v_dim]`` in q's dtype.
    Returns ``out`` with the group's tiles written (in place: the result
    aliases it), the others as they were."""
    n_tiles, rows, width = q.shape
    (g, slots), ps = q_pos.shape, arena.shape[2]
    bt = block_pages * ps
    if tab.shape[1] % block_pages or rows % slots:
        raise ValueError(f"a table {tab.shape[1]} pages wide in blocks of {block_pages}, tiles "
                         f"of {rows} rows for {slots} slots: neither may leave a rest")
    if out.shape != (n_tiles, rows, v_dim) or out.dtype != q.dtype:
        raise ValueError(f"outputs {out.shape} {out.dtype} for queries {q.shape} {q.dtype}")
    need = vmem_bytes(rows, width, v_dim, bt, arena.dtype.itemsize)
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(f"the walk's kernel needs {need} bytes of VMEM for tiles of {rows} "
                         f"rows and blocks of {bt} positions: over {VMEM_BUDGET_BYTES}")
    trips = trips.astype(jnp.int32)
    # where a tile's queries are fetched from: its own block, or for an idle
    # tile the block of the last live one before it (an index that does not
    # move fetches nothing)
    src = jax.lax.cummax(jnp.where(trips > 0, jnp.arange(g, dtype=jnp.int32), 0))
    at = jnp.stack([jnp.asarray(row, jnp.int32), jnp.asarray(first, jnp.int32)])
    return pl.pallas_call(
        partial(_kernel, block_pages=block_pages, page_size=ps, slots=slots, v_dim=v_dim,
                scale=scale, tab_width=tab.shape[1]),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(g,),
            in_specs=[pl.BlockSpec((1, rows, width),
                                   lambda i, at, trips, src, *_: (at[1] + src[i], 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, rows, v_dim), lambda i, at, *_: (at[1] + i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, bt, width), arena.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, v_dim), jnp.float32)]),
        # ``out`` (operand 7, behind the five prefetched) is the result
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_BUDGET_BYTES),
        name=KERNEL_NAME,
    )(at, trips, src, q_pos.astype(jnp.int32).reshape(-1), tab.astype(jnp.int32).reshape(-1),
      q, arena, out)
