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

* **grid = the tiles of the group**; a tile's queries ``[kvh, slots x rep,
  hd]`` are resident for its whole walk, its slots' positions and its table
  row are scalars (prefetched);
* **both arenas stay in HBM** (``memory_space=pl.ANY``): a block's K pages
  and V pages (``[ps, kvh, hd]`` each) are copied page by page into one of
  :data:`BUFFERS` VMEM blocks an arena.  The group's blocks are ONE sequence,
  tile after tile and block after block, walked through those buffers with
  ``BUFFERS - 1`` blocks on their way in behind the one in the products,
  across the end of a tile too; a block's copies are waited for with one
  wait an arena;
* **a K/V head's keys are every ``kvh``-th row of the block** read as ``[bt x
  kvh, hd]``: a strided load from VMEM (bfloat16 arenas: of the 32-bit words
  that hold two neighbouring heads' rows, taken apart by a shift and a mask,
  which is exact), so ``kvh`` stays a batch dimension of both products, K and
  V are never repeated to ``h`` heads and nothing is transposed;
* **the float32 scores, the causal mask from the positions, the running
  maximum, sum and accumulator live in VMEM** for a tile's walk: every K/V
  head's scores are made first, ONE softmax runs over ``[kvh x rows, bt]``,
  then every head's value product; the tile's output is written once;
* **each tile ends at ITS OWN newest block**: the block axis is a loop in
  the kernel bounded by the tile's own prefetched trip count, and no copy is
  started for a block no tile walks.  An idle tile writes zeros nobody reads;
* **a window's ring is the same walk from the tile's OWN first block** (a
  static ``window``: with none the traced kernel is text for text the one it
  was).  One more prefetched array gives each tile the block of the oldest
  key its oldest slot sees; page ``p`` of its ``j``-th block is slot
  ``((first + j) x block_pages + p) % ring`` of its table row (scalar
  arithmetic where the copy is started: the ring need be no whole number of
  blocks wide); the block's key positions count from ``first``, and the mask
  gains the window's lower bound, ``pos - W < k_pos <= pos``, so a ring slot
  read twice is masked by its logical position.  As ``jax.numpy`` the rings
  were the heaviest device operation of both cells with window layers, about
  ten times a block what this kernel costs (PERF.md section 6, PR 47).

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


def holds_kernel(platform: str, by_head: bool, mesh_devices: int) -> bool:
    """Whether a step program lowered for ``platform`` walks one kind of page
    with this kernel, whole rows and a window's rings alike: K and V by head
    (``by_head``), the platform, and a program that is one device's
    (``mesh_devices``: the devices of the mesh the arenas are laid out over,
    0 or 1 where there is none) — nothing else."""
    return by_head and mesh_devices <= 1 and platform == PLATFORM


def vmem_bytes(kvh: int, rows: int, hd: int, block_tokens: int, itemsize: int) -> int:
    """What the kernel keeps in VMEM: both arenas' :data:`BUFFERS` blocks,
    the blocked queries and output (two buffers each), the float32 state of
    every K/V head (a ``[rows, 1]`` column takes whole 128-lane tiles:
    maximum and sum, and the positions once), every head's scores with their
    probabilities, and a block's keys and values taken apart."""
    column = rows * 128 * 4
    return (2 * BUFFERS * block_tokens * kvh * hd * itemsize + 2 * 2 * kvh * rows * hd * itemsize
            + (2 * kvh + 1) * column + kvh * rows * hd * 4
            + kvh * rows * block_tokens * (2 * 4 + itemsize)
            + 2 * kvh * block_tokens * hd * (4 + itemsize))


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


def _kernel(at_ref, trips_ref, src_ref, pos_ref, tab_ref, *refs,
            block_pages: int, page_size: int, slots: int, scale: float, tab_width: int,
            window: Optional[int]):
    # under a window one more prefetched array: each tile's first block
    first_ref, refs = (None, refs) if window is None else (refs[0], refs[1:])
    q_ref, k_ref, v_ref, out_ref, kbuf, vbuf, sems, ahead_ref, m_ref, l_ref, acc_ref = refs
    del src_ref  # the queries' index map reads it
    i, g = pl.program_id(0), pl.num_programs(0)
    n = trips_ref[i]
    row = at_ref[0]
    bp, ps = block_pages, page_size
    bt = bp * ps
    depth = kbuf.shape[0]
    _, kvh, rows, hd = q_ref.shape
    # each product row's position: its slot's, ``rows // slots`` heads a slot
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    pos = jnp.zeros((rows, 1), jnp.int32)
    for slot in range(slots):
        pos = jnp.where(at >= slot * (rows // slots), pos_ref[i * slots + slot], pos)

    # the group's blocks are ONE sequence, tile after tile and block after
    # block (idle tiles have none), walked through ``depth`` buffers: block
    # ``s`` of the sequence lands in buffer ``s % depth``, and ``ahead_ref``
    # says which (tile, block) is started next, ``depth - 1`` ahead of the one
    # in the products — across the end of a tile too
    def live_after(tile):
        return jax.lax.fori_loop(
            0, g, lambda k, t: jnp.where((k > tile) & (trips_ref[k] > 0) & (t == g), k, t), g)

    def start_next(buf):
        tile, blk = ahead_ref[0], ahead_ref[1]

        @pl.when(tile < g)
        def _():
            if window is None:
                base = tile * tab_width + blk * bp
            else:  # the tile's table row is a ring ``tab_width`` pages wide
                base, lap = tile * tab_width, (first_ref[tile] + blk) * bp

            def copy(p):  # a page of each arena, one copy each
                page = tab_ref[base + p if window is None
                               else base + jax.lax.rem(lap + p, tab_width)]
                pltpu.make_async_copy(k_ref.at[row, page], kbuf.at[buf, p], sems.at[buf]).start()
                pltpu.make_async_copy(v_ref.at[row, page], vbuf.at[buf, p], sems.at[buf]).start()

            page_loop(bp, copy)
            last = blk + 1 == trips_ref[tile]
            ahead_ref[1] = jnp.where(last, 0, blk + 1)

            @pl.when(last)
            def _():
                ahead_ref[0] = live_after(tile)

    def wait(buf):
        # one wait an arena for a block's whole byte count: its pages' copies
        # signal the buffer's one semaphore
        pltpu.make_async_copy(k_ref.at[0, pl.ds(0, bp)], kbuf.at[buf], sems.at[buf]).wait()
        pltpu.make_async_copy(v_ref.at[0, pl.ds(0, bp)], vbuf.at[buf], sems.at[buf]).wait()

    @pl.when(i == 0)
    def _():
        ahead_ref[0] = live_after(-1)
        ahead_ref[1] = 0
        jax.lax.fori_loop(0, depth - 1, lambda buf, _: start_next(buf), None)

    @pl.when(n > 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq0 = jax.lax.fori_loop(0, i, lambda k, s: s + trips_ref[k], 0)

    def block(j, _):
        buf = (seq0 + j) % depth
        wait(buf)
        start_next((seq0 + j + depth - 1) % depth)  # the buffer the block before this one left
        if window is None:
            seen = j * bt + jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 1) <= pos
        else:  # the tile's own block of this trip, and the window's lower bound
            k_pos = (first_ref[i] + j) * bt + jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 1)
            seen = (k_pos <= pos) & (k_pos > pos - window)
        ks = _heads(kbuf.at[buf].reshape(bt * kvh, hd), kvh, bt)
        vs = _heads(vbuf.at[buf].reshape(bt * kvh, hd), kvh, bt)
        # every head's scores first, ONE softmax over [kvh x rows, bt], then
        # the value products: the heads' products follow each other through
        # the MXU and the softmax's passes are long ones (a head at a time
        # the same work took a third longer: PERF.md section 5, PR 44)
        s = jnp.concatenate([jax.lax.dot_general(
            q_ref[0, h], ks[h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) for h in range(kvh)], axis=0) * scale
        s = jnp.where(jnp.concatenate([seen] * kvh, axis=0), s, -1e30)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        for h in range(kvh):  # unrolled: ``jax.lax`` bindings trace in half the time (PR 43)
            mine = (h * rows, (h + 1) * rows)
            ph = jax.lax.convert_element_type(jax.lax.slice_in_dim(p, *mine), vs[h].dtype)
            acc_ref[h] = jax.lax.add(
                jax.lax.mul(acc_ref[h], jax.lax.slice_in_dim(alpha, *mine)),
                jax.lax.dot_general(ph, vs[h], (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32))

    jax.lax.fori_loop(0, n, block, None)

    @pl.when(n > 0)
    def _():
        out_ref[0] = (acc_ref[...] / l_ref[...].reshape(kvh, rows, 1)).astype(out_ref.dtype)

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def walk_group(q: jax.Array, q_pos: jax.Array, k_arena: jax.Array, v_arena: jax.Array, row: Any,
               tab: jax.Array, trips: jax.Array, first: Any = 0, *,
               block_pages: int, scale: float, window: Optional[int] = None,
               first_blocks: Optional[jax.Array] = None) -> jax.Array:
    """The walk of one group of ``G`` tiles, tiles ``first`` to ``first + G``
    of a step's.  q: ``[tiles, kvh, rows, hd]``, every tile's queries (a K/V
    head's ``slots x rep`` product rows, in the arenas' dtype: the group's
    are read in place); q_pos: int32 ``[G, slots]``, each slot's position;
    k_arena / v_arena: ``[arena rows, N, ps, kvh, hd]``; row: the arena row (a
    traced int); tab: int32 ``[G, P]``, each tile's table row, ``P`` a whole
    number of blocks; trips: int32 ``[G]`` (``attention.tile_trips``).
    Under a ``window`` (static) ``tab`` is each tile's RING, any number of
    pages wide, ``first_blocks`` int32 ``[G]`` the block each tile's walk
    starts at (``attention.first_block``) and ``trips`` counts from there.
    Returns the group's outputs ``[G, kvh, rows, hd]`` in q's dtype, an idle
    tile's zeros."""
    _, kvh, rows, hd = q.shape
    (g, slots), ps = q_pos.shape, k_arena.shape[2]
    bt = block_pages * ps
    if (window is None and tab.shape[1] % block_pages) or rows % slots:
        raise ValueError(f"a table {tab.shape[1]} pages wide in blocks of {block_pages}, tiles "
                         f"of {rows} rows for {slots} slots: neither may leave a rest")
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
    need = vmem_bytes(kvh, rows, hd, bt, k_arena.dtype.itemsize)
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(f"the walk's kernel needs {need} bytes of VMEM for tiles of {kvh} x "
                         f"{rows} rows and blocks of {bt} positions: over {VMEM_BUDGET_BYTES}")
    trips = trips.astype(jnp.int32)
    # where a tile's queries are fetched from: its own block, or for an idle
    # tile the block of the last live one before it (an index that does not
    # move fetches nothing)
    src = jax.lax.cummax(jnp.where(trips > 0, jnp.arange(g, dtype=jnp.int32), 0))
    at = jnp.stack([jnp.asarray(row, jnp.int32), jnp.asarray(first, jnp.int32)])
    block = (BUFFERS, block_pages, ps, kvh, hd)
    scalars = (at, trips, src, q_pos.astype(jnp.int32).reshape(-1),
               tab.astype(jnp.int32).reshape(-1))
    if window is not None:
        scalars += (first_blocks.astype(jnp.int32),)
    return pl.pallas_call(
        partial(_kernel, block_pages=block_pages, page_size=ps, slots=slots, scale=scale,
                tab_width=tab.shape[1], window=window),
        out_shape=jax.ShapeDtypeStruct((g, kvh, rows, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(g,),
            in_specs=[pl.BlockSpec((1, kvh, rows, hd),
                                   lambda i, at, trips, src, *_: (at[1] + src[i], 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, kvh, rows, hd), lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM(block, k_arena.dtype),
                            pltpu.VMEM(block, v_arena.dtype),
                            pltpu.SemaphoreType.DMA((BUFFERS,)),
                            pltpu.SMEM((2,), jnp.int32),
                            pltpu.VMEM((kvh * rows, 1), jnp.float32),
                            pltpu.VMEM((kvh * rows, 1), jnp.float32),
                            pltpu.VMEM((kvh, rows, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_BUDGET_BYTES),
        name=KERNEL_NAME,
    )(*scalars, q, k_arena, v_arena)
