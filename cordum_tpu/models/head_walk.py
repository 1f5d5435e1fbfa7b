"""The walk over K and V pages by head of one group of tiles as ONE Pallas TPU
kernel (docs/SERVING.md §The ragged entry point; ROADMAP S2 step 1).

``attention.paged_attention`` over two arenas ``[rows, N, ps, kvh, hd]`` (K and V
by head: whole rows, or under a window each row's ring) walks a group of
``ATTN_GROUP_TILES`` tiles block by block.  As ``jax.numpy`` that walk gathers
a trip's pages into a new HBM array and reads them back, makes five passes
over the float32 scores through HBM, reads and rewrites the group's
accumulator whatever the block's length and drags all eight tiles to the
group's longest row: 3.8 ms of a 36 ms step for 0.36 ms of bytes in the one
cell the device binds, 1.4 ms under this kernel (PERF.md section 6, PR 44).
This kernel is ``models/latent_walk.py``'s for the other form of arena, and
shares by import what the two have in common in the kernel body (the
platform, the page loop, the VMEM budget):

* **ONE program a group, its sequence of copies run after run and block
  after block** (ISSUE 50).  A RUN is the group's consecutive live tiles that
  sit on the same table row (``attention.tile_runs``, prefetched: at the tile
  that heads a run its tiles, its blocks and the first of them): a prefill
  chunk's tiles lie side by side in the walk's order, 28 of them for a
  224-token chunk, and each used to copy and take apart the same blocks of
  their one row.  A block of a run is copied ONCE, its K and V heads are taken
  apart ONCE, and then every tile of the run that walks this block makes its
  products against it.  **A run of one tile is the walk it was**: a decode
  row, a draft row, a chunk of up to eight slots;
* **the group's queries ``[G, kvh, slots x rep, hd]`` are resident** (one
  blocked operand, fetched once a group, read in place from the step's whole
  array), and so are every tile's positions, maxima, sums and accumulators for
  its run's walk: the tiles of a run are a loop in the kernel, as a block's
  pages are, so the kernel's text does not grow with the run.  The slots'
  positions and the table rows are scalars (prefetched);
* **both arenas stay in HBM** (``memory_space=pl.ANY``): a block's K pages
  and V pages (``[ps, kvh, hd]`` each) are copied page by page into one of
  :data:`BUFFERS` VMEM blocks an arena.  The group's blocks are ONE sequence,
  walked through those buffers with ``BUFFERS - 1`` blocks on their way in
  behind the one in the products, across the end of a run too; a block's
  copies are waited for with one wait an arena;
* **a K/V head's keys are every ``kvh``-th row of the block** read as ``[bt x
  kvh, hd]``: a strided load from VMEM (bfloat16 arenas: of the 32-bit words
  that hold two neighbouring heads' rows, taken apart by a shift and a mask,
  which is exact) into ``[kvh, bt, hd]`` for the run's tiles, so ``kvh``
  stays a batch dimension of both products, K and V are never repeated to
  ``h`` heads and nothing is transposed;
* **the float32 scores, the causal mask from the positions, the running
  maximum, sum and accumulator live in VMEM**: every K/V head's scores are
  made first, ONE softmax runs over ``[kvh x rows, bt]``, then every head's
  value product, a tile at a time with the operations the tile alone had; a
  tile's output is written once, behind its run's last block.  The positions,
  maxima and sums are kept on every lane of their rows (:data:`LANES`), so a
  column meets a block's scores or the accumulator without a lane
  permutation: with the copies shared a tile-trip is the XLU's, and the
  permutations were half of its work there;
* **a tile that feeds few slots computes their rows alone**: a decode row's
  tile feeds ONE of its eight slots, and its trip was the products and the
  softmax of ``slots x rep`` rows for ``rep`` live ones.  The slots a tile does
  not feed carry the position -1; a tile whose fed slots fit the first
  :data:`SUBLANES`-row group of a K/V head's product rows (one slot at 5 to 8
  query heads a K/V head, two at 4) enters, walks and leaves with those rows
  alone, and writes zeros for the others, which nobody reads.  The same rows
  get the same operations, so a fed slot's output is bit for bit what the
  whole tile's walk gave it;
* **each tile starts and ends at ITS OWN blocks**: a run's blocks go from the
  least of its tiles' first blocks to the greatest of their last, the tiles
  of a run are asked block by block whether they walk it (their own
  prefetched trip count, under a window from their own first block), and no
  copy is started for a block no tile of the run walks.  An idle tile writes
  zeros nobody reads;
* **a window's ring is the same walk from the tile's OWN first block** (a
  static ``window``).  One more prefetched array gives each tile the block of
  the oldest key its oldest slot sees; page ``p`` of block ``b`` is slot ``(b
  x block_pages + p) % ring`` of the run's table row (scalar arithmetic where
  the copy is started: the ring need be no whole number of blocks wide); the
  block's key positions are its logical ones, and the mask gains the window's
  lower bound, ``pos - W < k_pos <= pos``, so a ring slot read twice (a run's
  blocks may be a page more than the ring) is masked by its logical position.
  As ``jax.numpy`` the rings were the heaviest device operation of both cells
  with window layers, about ten times a block what this kernel costs (PERF.md
  section 6, PR 47).

Same numerics as the ``jax.numpy`` walk: operands in the arena's dtype,
float32 scores and state, probabilities cast to the arena's dtype for the
value product, a masked key scores ``-1e30``, ``scale`` as given.

Which walk a program holds is decided where it is LOWERED
(``jax.lax.platform_dependent`` in ``attention.paged_attention``), by
:func:`holds_kernel`: the arena's form, the lowering platform, and that the
program is not partitioned over a mesh (a Pallas call is one device's; the
tensor-parallel gang shards the arenas by head and keeps the ``jax.numpy``
walk) — for whole rows and rings alike.  ``attention.walk_kernel`` asks it
for the trace and for the host alike, and the host counts the walk by the
same rule (``attention.count_walk``).  The module imports Pallas, so nothing imports it
at its own import (``models/latent_walk.py`` says why).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .latent_walk import PLATFORM, VMEM_BUDGET_BYTES, page_loop

#: the kernel's name in the lowered program (its custom call) and in a trace
KERNEL_NAME = "head_walk"
#: blocks of each arena the kernel holds in VMEM: one in the products and
#: ``BUFFERS - 1`` on their way in.  Measured on the chip (PERF.md section 5,
#: PR 44; the walks of a step alone, ms): with the copies alone in the kernel
#: two buffers read 2.62 and four 1.84 at Falcon-H1's shapes, so two leave the
#: copies' latency in the open; with the products in, 2 / 3 / 4 buffers read
#: 2.74 / 2.74 / 2.67 there and 2.61 / 2.28 / 2.20 at Mistral's
BUFFERS = 4
#: a tile's positions, maxima and sums are kept on every lane of their rows
#: (``[rows, LANES]``, the bytes a ``[rows, 1]`` column takes anyway): a column
#: read back from VMEM sits in lane 0, and each use of it against a block's
#: scores or the accumulator was a lane permutation on the XLU, 72 a tile-trip
#: beside the softmax's 64 lane reductions, in a trip the XLU bounds
LANES = 128
#: a tile that feeds few slots (a decode row: ONE of its eight) computes the
#: first product rows a K/V head that hold them, in whole groups of this many
#: (a float32 register's rows), and not all ``slots x rep``: a decode tile's
#: trip was the products and the softmax of 64 rows for 8 live ones
SUBLANES = 8


def holds_kernel(platform: str, by_head: bool, mesh_devices: int) -> bool:
    """Whether a step program lowered for ``platform`` walks one kind of page
    with this kernel, whole rows and a window's rings alike: K and V by head
    (``by_head``), the platform, and a program that is one device's
    (``mesh_devices``: the devices of the mesh the arenas are laid out over,
    0 or 1 where there is none) — nothing else."""
    return by_head and mesh_devices <= 1 and platform == PLATFORM


def vmem_bytes(tiles: int, kvh: int, rows: int, hd: int, block_tokens: int,
               itemsize: int) -> int:
    """What the kernel keeps in VMEM: both arenas' :data:`BUFFERS` blocks,
    the group's ``tiles`` blocked queries and outputs (two buffers each), the
    float32 state of every tile and K/V head, resident for a run's walk (a
    column is kept on all :data:`LANES` lanes, the bytes ``[rows, 1]`` would
    take anyway: maximum and sum, and the positions once; the accumulator), one tile's scores with their
    probabilities, and a block's keys and values taken apart (the float32
    halves on their way, the heads in the arena's dtype for the run's tiles)."""
    column = rows * LANES * 4
    return (2 * BUFFERS * block_tokens * kvh * hd * itemsize
            + tiles * (2 * 2 * kvh * rows * hd * itemsize
                       + (2 * kvh + 1) * column + kvh * rows * hd * 4)
            + kvh * rows * block_tokens * (2 * 4 + itemsize)
            + 2 * kvh * block_tokens * hd * (4 + itemsize))


def _wide(x: jax.Array, width: int) -> jax.Array:
    """A column kept on all :data:`LANES` lanes, ``[rows, LANES]`` with a row's
    one value in every lane, as ``[rows, width]``: the broadcast of a ``[rows,
    1]`` column without a lane permutation."""
    whole, rest = divmod(width, LANES)
    return jnp.concatenate([x] * whole + [x[:, :rest]] * (rest > 0), axis=1)


def _heads(ref: Any, kvh: int, block_tokens: int) -> list:
    """A block ``[bt x kvh, hd]`` (a position's heads one behind the other)
    taken apart: each K/V head's rows ``[bt, hd]``, one strided load a head
    for a float32 arena, one a pair of heads for a bfloat16 one."""
    if kvh == 1:
        return [ref[...]]
    if ref.dtype == jnp.float32:
        return [ref[pl.ds(head, block_tokens, stride=kvh), :] for head in range(kvh)]
    # bfloat16: two neighbouring heads' rows share a 32-bit word a column, the
    # even head the low half.  A bfloat16 is the high half of the float32 of
    # its value, so both casts are exact
    words = ref.bitcast(jnp.uint32)
    heads = []
    for pair in range(kvh // 2):
        w = words[pl.ds(pair, block_tokens, stride=kvh // 2), :]
        heads += [pltpu.bitcast(jax.lax.shift_left(w, jnp.uint32(16)), jnp.float32),
                  pltpu.bitcast(jax.lax.bitwise_and(w, jnp.uint32(0xFFFF0000)), jnp.float32)]
    return [jax.lax.convert_element_type(x, ref.dtype) for x in heads]


def _kernel(at_ref, trips_ref, runs_ref, pos_ref, tab_ref, *refs,
            block_pages: int, page_size: int, slots: int, scale: float, tab_width: int,
            window: Optional[int]):
    # under a window one more prefetched array: each tile's first block
    first_ref, refs = (None, refs) if window is None else (refs[0], refs[1:])
    (q_ref, k_ref, v_ref, out_ref, kbuf, vbuf, sems, ahead_ref, fed_ref, kh_ref, vh_ref, pos_col,
     m_ref, l_ref, acc_ref) = refs
    row = at_ref[0]
    bp, ps = block_pages, page_size
    bt = bp * ps
    depth = kbuf.shape[0]
    g, kvh, rows, hd = q_ref.shape
    rep = rows // slots
    # the rows a K/V head of a tile that feeds few slots: one slot's heads in
    # whole sublane groups, and the most slots that fit them (0: every tile
    # computes all its rows)
    narrow = -(-rep // SUBLANES) * SUBLANES
    few_slots = narrow // rep if narrow < rows else 0

    def by_rows(tile, do):
        """``do(n)`` for the product rows a K/V head the tile computes: all of
        them, or its first ``narrow`` where it feeds few slots."""
        if not few_slots:
            return do(rows)
        few = fed_ref[tile] <= few_slots
        pl.when(few)(lambda: do(narrow))
        pl.when(jnp.logical_not(few))(lambda: do(rows))

    # what ``attention.tile_runs`` says of the tile that heads a run (zeros of
    # every other): the run's tiles, its blocks and the first of them
    def span(tile):
        return runs_ref[3 * tile]

    def blocks(tile):
        return runs_ref[3 * tile + 1]

    def lowest(tile):
        return runs_ref[3 * tile + 2]

    # the group's blocks are ONE sequence, run after run and block after
    # block (a run: consecutive live tiles of one table row, which share each
    # block's copy), walked through ``depth`` buffers: block ``s`` of the
    # sequence lands in buffer ``s % depth``, and ``ahead_ref`` says which
    # (run, block) is started next, ``depth - 1`` ahead of the one in the
    # products — across the end of a run too
    def head_after(tile):
        return jax.lax.fori_loop(
            0, g, lambda k, t: jnp.where((k > tile) & (span(k) > 0) & (t == g), k, t), g)

    def start_next(buf):
        tile, blk = ahead_ref[0], ahead_ref[1]

        @pl.when(tile < g)
        def _():
            # the run's table row is its first tile's (under a window a ring
            # ``tab_width`` pages wide, walked from the run's first block)
            base, lap = tile * tab_width, (lowest(tile) + blk) * bp

            def copy(p):  # a page of each arena, one copy each
                page = tab_ref[base + (lap + p if window is None
                                       else jax.lax.rem(lap + p, tab_width))]
                pltpu.make_async_copy(k_ref.at[row, page], kbuf.at[buf, p], sems.at[buf]).start()
                pltpu.make_async_copy(v_ref.at[row, page], vbuf.at[buf, p], sems.at[buf]).start()

            page_loop(bp, copy)
            last = blk + 1 == blocks(tile)
            ahead_ref[1] = jnp.where(last, 0, blk + 1)

            @pl.when(last)
            def _():
                ahead_ref[0] = head_after(tile)

    def wait(buf):
        # one wait an arena for a block's whole byte count: its pages' copies
        # signal the buffer's one semaphore
        pltpu.make_async_copy(k_ref.at[0, pl.ds(0, bp)], kbuf.at[buf], sems.at[buf]).wait()
        pltpu.make_async_copy(v_ref.at[0, pl.ds(0, bp)], vbuf.at[buf], sems.at[buf]).wait()

    ahead_ref[0] = head_after(-1)
    ahead_ref[1] = 0
    jax.lax.fori_loop(0, depth - 1, lambda buf, _: start_next(buf), None)

    def enter(tile, _):
        # the slots the tile feeds: the others' positions are -1
        fed_ref[tile] = sum((pos_ref[tile * slots + slot] >= 0).astype(jnp.int32)
                            for slot in range(slots))

        def start(n):
            # each product row's position: its slot's, ``rep`` heads a slot
            at = jax.lax.broadcasted_iota(jnp.int32, (n, LANES), 0)
            pos = jnp.zeros((n, LANES), jnp.int32)
            for slot in range(-(-n // rep)):
                pos = jnp.where(at >= slot * rep, pos_ref[tile * slots + slot], pos)
            pos_col[tile, :n] = pos
            m_ref[tile, :, :n] = jnp.full((kvh, n, LANES), -1e30, jnp.float32)
            l_ref[tile, :, :n] = jnp.zeros((kvh, n, LANES), jnp.float32)
            acc_ref[tile, :, :n] = jnp.zeros((kvh, n, hd), jnp.float32)

        by_rows(tile, start)

    def products(tile, blk, n):
        """One tile's trip over the block that lies taken apart, its first
        ``n`` product rows a K/V head: its scores, its softmax update and its
        value products, as the tile alone made them."""
        pos = _wide(pos_col[tile, :n], bt)
        k_pos = blk * bt + jax.lax.broadcasted_iota(jnp.int32, (n, bt), 1)
        seen = k_pos <= pos
        if window is not None:  # the window's lower bound
            seen &= k_pos > pos - window
        # every head's scores first, ONE softmax over [kvh x n, bt], then
        # the value products: the heads' products follow each other through
        # the MXU and the softmax's passes are long ones (a head at a time
        # the same work took a third longer: PERF.md section 5, PR 44)
        s = jnp.concatenate([jax.lax.dot_general(
            q_ref[tile, h, :n], kh_ref[h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) for h in range(kvh)], axis=0) * scale
        s = jnp.where(jnp.concatenate([seen] * kvh, axis=0), s, -1e30)
        m = m_ref[tile, :, :n].reshape(kvh * n, LANES)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - _wide(m_new, bt))
        l = l_ref[tile, :, :n].reshape(kvh * n, LANES) * alpha + jnp.sum(p, axis=-1, keepdims=True)
        l_ref[tile, :, :n] = l.reshape(kvh, n, LANES)
        m_ref[tile, :, :n] = m_new.reshape(kvh, n, LANES)
        for h in range(kvh):  # unrolled: ``jax.lax`` bindings trace in half the time (PR 43)
            mine = (h * n, (h + 1) * n)
            ph = jax.lax.convert_element_type(jax.lax.slice_in_dim(p, *mine), vh_ref.dtype)
            acc_ref[tile, h, :n] = jax.lax.add(
                jax.lax.mul(acc_ref[tile, h, :n], _wide(jax.lax.slice_in_dim(alpha, *mine), hd)),
                jax.lax.dot_general(ph, vh_ref[h], (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32))

    def leave(tile, _):
        def write(n):
            if n < rows:  # the rows nobody computed: zeros nobody reads
                out_ref[tile] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)
            l = _wide(l_ref[tile, :, :n].reshape(kvh * n, LANES), hd).reshape(kvh, n, hd)
            out_ref[tile, :, :n] = (acc_ref[tile, :, :n] / l).astype(out_ref.dtype)

        by_rows(tile, write)

    def run(head, seq0):
        """The walk of the run that tile ``head`` heads (none: nothing),
        whose first block is the group's ``seq0``-th: each block waited for
        and taken apart ONCE, then every tile of the run that walks it (from
        its OWN first block to its OWN last) makes its products against it."""
        tiles = (head, head + span(head))

        @pl.when(span(head) > 0)
        def _():
            jax.lax.fori_loop(*tiles, enter, None)

            def block(j, _):
                buf = (seq0 + j) % depth
                wait(buf)
                start_next((seq0 + j + depth - 1) % depth)  # the buffer the block before this one left
                for arena, apart in ((kbuf, kh_ref), (vbuf, vh_ref)):
                    for h, x in enumerate(_heads(arena.at[buf].reshape(bt * kvh, hd), kvh, bt)):
                        apart[h] = x
                blk = lowest(head) + j

                def tile(t, _):
                    first = 0 if window is None else first_ref[t]

                    @pl.when((blk >= first) & (blk < first + trips_ref[t]))
                    def _():
                        by_rows(t, partial(products, t, blk))

                jax.lax.fori_loop(*tiles, tile, None)

            jax.lax.fori_loop(0, blocks(head), block, None)
            jax.lax.fori_loop(*tiles, leave, None)

        return seq0 + blocks(head)

    jax.lax.fori_loop(0, g, run, 0)

    def idle(tile, _):  # an idle tile writes zeros nobody reads
        @pl.when(trips_ref[tile] == 0)
        def _():
            out_ref[tile] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    jax.lax.fori_loop(0, g, idle, None)


def walk_group(q: jax.Array, q_pos: jax.Array, k_arena: jax.Array, v_arena: jax.Array, row: Any,
               tab: jax.Array, trips: jax.Array, runs: jax.Array, first: Any = 0, *,
               block_pages: int, scale: float, window: Optional[int] = None,
               first_blocks: Optional[jax.Array] = None) -> jax.Array:
    """The walk of one group of ``G`` tiles, tiles ``first`` to ``first + G``
    of a step's (``first`` a whole number of groups).  q: ``[tiles, kvh, rows,
    hd]``, every tile's queries (a K/V head's ``slots x rep`` product rows, in
    the arenas' dtype: the group's are read in place); q_pos: int32 ``[G,
    slots]``, each slot's position, -1 for a slot the tile does not feed (a
    tile feeds its FIRST slots; one that feeds few computes those rows alone
    and writes zeros for the others); k_arena / v_arena: ``[arena rows, N, ps,
    kvh, hd]``; row: the arena row (a traced int); tab: int32 ``[G, P]``, each
    tile's table row, ``P`` a whole number of blocks; trips: int32 ``[G]``
    (``attention.tile_trips``); runs: int32 ``[G, 3]``
    (``attention.tile_runs``: at the tile that heads a run its tiles, its
    blocks and the first of them; the tiles of a run are on ONE table row).
    Under a ``window`` (static) ``tab`` is each tile's RING, any number of
    pages wide, ``first_blocks`` int32 ``[G]`` the block each tile's walk
    starts at (``attention.first_block``) and ``trips`` counts from there.
    Returns the group's outputs ``[G, kvh, rows, hd]`` in q's dtype, an idle
    tile's zeros."""
    n_tiles, kvh, rows, hd = q.shape
    (g, slots), ps = q_pos.shape, k_arena.shape[2]
    bt = block_pages * ps
    if (window is None and tab.shape[1] % block_pages) or rows % slots or n_tiles % g:
        raise ValueError(f"a table {tab.shape[1]} pages wide in blocks of {block_pages}, tiles "
                         f"of {rows} rows for {slots} slots, {n_tiles} tiles in groups of {g}: "
                         "none may leave a rest")
    if (window is None) != (first_blocks is None):
        raise ValueError("a window's walk takes each tile's first block, and no other does")
    if k_arena.shape != v_arena.shape or k_arena.shape[3:] != (kvh, hd) or (
            k_arena.dtype != v_arena.dtype):
        raise ValueError(f"arenas {k_arena.shape} {k_arena.dtype} and {v_arena.shape} "
                         f"{v_arena.dtype} for queries {q.shape}")
    if k_arena.dtype not in (jnp.float32, jnp.bfloat16) or (
            k_arena.dtype == jnp.bfloat16 and kvh > 1 and kvh % 2):
        raise ValueError(f"{kvh} K/V heads of {k_arena.dtype}: the kernel takes the heads of a "
                         "float32 arena apart by rows and of a bfloat16 one by pairs")
    need = vmem_bytes(g, kvh, rows, hd, bt, k_arena.dtype.itemsize)
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(f"the walk's kernel needs {need} bytes of VMEM for {g} tiles of {kvh} x "
                         f"{rows} rows and blocks of {bt} positions: over {VMEM_BUDGET_BYTES}")
    at = jnp.stack([jnp.asarray(row, jnp.int32), jnp.asarray(first, jnp.int32) // g])
    block = (BUFFERS, block_pages, ps, kvh, hd)
    scalars = (at, trips.astype(jnp.int32), runs.astype(jnp.int32).reshape(-1),
               q_pos.astype(jnp.int32).reshape(-1), tab.astype(jnp.int32).reshape(-1))
    if window is not None:
        scalars += (first_blocks.astype(jnp.int32),)
    column = (g, kvh, rows, LANES)
    return pl.pallas_call(
        partial(_kernel, block_pages=block_pages, page_size=ps, slots=slots, scale=scale,
                tab_width=tab.shape[1], window=window),
        out_shape=jax.ShapeDtypeStruct((g, kvh, rows, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            # ONE program a group: a run's tiles are a loop in the kernel, as
            # a block's pages are, and the group's queries are fetched once
            grid=(1,),
            in_specs=[pl.BlockSpec((g, kvh, rows, hd), lambda i, at, *_: (at[1], 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((g, kvh, rows, hd), lambda i, *_: (0, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM(block, k_arena.dtype),
                            pltpu.VMEM(block, v_arena.dtype),
                            pltpu.SemaphoreType.DMA((BUFFERS,)),
                            pltpu.SMEM((2,), jnp.int32),
                            pltpu.SMEM((g,), jnp.int32),
                            pltpu.VMEM((kvh, bt, hd), k_arena.dtype),
                            pltpu.VMEM((kvh, bt, hd), v_arena.dtype),
                            pltpu.VMEM((g, rows, LANES), jnp.int32),
                            pltpu.VMEM(column, jnp.float32),
                            pltpu.VMEM(column, jnp.float32),
                            pltpu.VMEM((g, kvh, rows, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_BUDGET_BYTES),
        name=KERNEL_NAME,
    )(*scalars, q, k_arena, v_arena)
