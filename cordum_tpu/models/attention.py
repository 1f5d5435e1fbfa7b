"""The paged cache and the attention's walk over it, for every serving family.

What the serving families' step programs share and none of them owns
(docs/SERVING.md §The ragged entry point): the page arenas of K and V by
head and the page programs over them (copy-on-write, export, import), the
rules of the walk (tiles, groups, blocks, a window's ring), the walk itself
(:func:`paged_attention`, over K and V by head or over ONE latent array), the
ONE rule that says which Pallas kernel a lowered program walks with
(:func:`walk_kernel`: ``models/head_walk.py``, ``models/latent_walk.py``), and
the host's count of the walk as the program makes it (:func:`count_walk`).
The traced rule and the host's count of it stand in one file.  It imports no
family; the kernels' modules import Pallas and are imported only inside
:func:`walk_kernel`.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import AXIS_TP

# ---------------------------------------------------------------------------
# paged KV cache: the ragged mixed prefill+decode entry (serving subsystem)
# ---------------------------------------------------------------------------
#
# The serving path (cordum_tpu/serving) holds the conversation KV cache as a
# block-granular page arena shaped [L, num_pages, page_size, kvh, hd]; a
# sequence's logical position ``p`` lives at page ``page_table[p // ps]``,
# slot ``p % ps`` (the Ragged Paged Attention layout, PAPERS.md — here a
# jnp formulation that runs anywhere, :func:`paged_attention`: a walk over
# the page table's rows in blocks of pages with an online softmax; a Pallas kernel
# that walks it in VMEM is the TPU upgrade path).  Page 0 is the NULL page:
# padding rows and padded page-table tails point at it, so their writes land
# harmlessly in slots no live sequence ever attends to (the causal mask cuts
# every k_pos > position).
#
# Page aliasing invariants (docs/SERVING.md §Prefix cache and tiering): the
# attention gather walks ONLY the row of ``page_tables`` handed to it for
# each sequence, so two tables may point at the SAME physical page and the
# kernel cannot tell — physical-page aliasing is free here, which is what
# makes copy-on-write prefix sharing a pure control-plane feature.  The
# contract the serving layer must keep for an aliased page:
#   * read-only — a write lands in every table that maps the page, so the
#     engine CoW-copies (``copy_page``) before any position inside a
#     shared page is written;
#   * identical logical prefix — a page's K/V depends on every position
#     before it (attention), so a page may only be shared between
#     sequences whose token ids agree on [0, end_of_page).
# The allocator's refcount table (serving/pager.py) enforces the lifetime
# half: an aliased page cannot return to the free list while any table
# still maps it.


#: KV page arenas shard by attention head — axis 3 of
#: ``[L, num_pages, page_size, kvh, hd]`` — matching the column-parallel
#: wk/wv layout, so the ragged step's page writes and gathers stay local to
#: each TP rank (docs/SERVING.md §Sharded serving).
KV_ARENA_SPEC = P(None, None, None, AXIS_TP, None)


def init_kv_pages(
    cfg: Any, num_pages: int, page_size: int, dtype: Any = None,
    n_layers: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Preallocated page arenas for K and V: [L, num_pages, page_size, kvh, hd].
    ``n_layers`` is for a model whose layers are of two kinds, each kind
    with an arena pair of its own (``models/afmoe``): the layers of ONE kind."""
    layers = cfg.n_layers if n_layers is None else n_layers
    shape = (layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    dt = dtype or cfg.dtype
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


# One jitted program each for reading/writing a single arena page with the
# page INDEX as a traced operand: every page of every migration reuses the
# same two executables (a python-int index baked into an eager slice would
# compile one executable per (page, length) pair — ~150ms per page hop).
@jax.jit
def _gather_page(pages: jax.Array, pid: jax.Array) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(pages, pid, axis=1, keepdims=False)


@jax.jit
def _scatter_page(pages: jax.Array, pid: jax.Array, block: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_index_in_dim(pages, block, pid, axis=1)


def _copy_page(pages: jax.Array, src: jax.Array, dst: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_index_in_dim(
        pages,
        jax.lax.dynamic_index_in_dim(pages, src, axis=1, keepdims=False),
        dst, axis=1,
    )


# the arena donated: the copy is in place and holds no second arena (a latent
# arena of 4 GB beside 8 GB of weights leaves no room for one); both programs
# carry the function's name in a compile log
_copy_page_in_place = jax.jit(_copy_page, donate_argnums=0)
_copy_page = jax.jit(_copy_page)


def copy_page(arenas: list[jax.Array], src: int, dst: int) -> list[jax.Array]:
    """Duplicate one page on device in every arena of its kind (K and V by
    head, or a latent kind's one array) — the copy-on-write half of prefix
    sharing (docs/SERVING.md §Prefix cache and tiering).  Both indices are
    traced operands, so every CoW of every session reuses the same cached
    executable an arena shape; the copy never leaves the device (no host
    round trip, unlike the migration gather/scatter pair).  Off the CPU the
    arenas are DONATED, as the step donates them: the caller keeps only what
    is returned."""
    copy = _copy_page if jax.default_backend() == "cpu" else _copy_page_in_place
    return [copy(a, src, dst) for a in arenas]


def gather_kv_pages(
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_ids: list[int],
    used: list[int],
) -> list[tuple[Any, Any]]:
    """Read pages out of the arena at their TRUE lengths — the export half
    of live KV-page migration (docs/PROTOCOL.md §Page transfer).

    ``page_ids[i]`` is an arena page index and ``used[i]`` how many of its
    ``page_size`` token slots hold live positions (only the sequence's last
    page is partial).  The device read is always the full page (static
    shape → one cached program); the trim to ``used`` happens host-side so
    only live slots ride the wire.  Returns per-page ``(k, v)`` numpy
    arrays of shape ``[L, used, kvh, hd]`` upcast to float32 — an exact
    round trip for the bf16/fp32 arenas, and a wire format the receiver
    can cast back without knowing the sender's dtype."""

    out = []
    for pid, n in zip(page_ids, used):
        k = np.asarray(_gather_page(k_pages, pid))[:, :n].astype(np.float32)
        v = np.asarray(_gather_page(v_pages, pid))[:, :n].astype(np.float32)
        out.append((k, v))
    return out


def scatter_kv_pages(
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_ids: list[int],
    blocks: list[tuple[Any, Any]],
) -> tuple[jax.Array, jax.Array]:
    """Write migrated pages into the arena at their true lengths — the
    import half of live KV-page migration.  ``blocks[i]`` is the
    ``(k, v)`` pair :func:`gather_kv_pages` produced for ``page_ids[i]``.
    Each write pads its block to the full page (static shape → one cached
    program); slots past the true length are zero-filled, which is inert —
    the causal mask makes unwritten positions unreachable, and the resumed
    session overwrites them as it decodes.  Returns the updated arenas."""

    dt = k_pages.dtype
    ps = k_pages.shape[2]
    for pid, (k, v) in zip(page_ids, blocks):
        n = k.shape[1]
        if n < ps:
            pad = [(0, 0), (0, ps - n), (0, 0), (0, 0)]
            k = np.pad(np.asarray(k), pad)
            v = np.pad(np.asarray(v), pad)
        k_pages = _scatter_page(k_pages, pid, jnp.asarray(k, dt))
        v_pages = _scatter_page(v_pages, pid, jnp.asarray(v, dt))
    return k_pages, v_pages


#: token positions the least block of :func:`paged_attention`'s walk aims
#: at, and the least number of blocks a page table is cut into.  Chosen on
#: the chip (PERF.md section 6, PR 25) for K and V by head under the
#: ``jax.numpy`` walk (``walk_jnp``), where a block's bytes outweigh the
#: walk's state two to eight times: a block of THAT walk costs about 7 us
#: beyond its bytes, so the walk's granularity (the last block is half empty
#: on average) weighs more than the count of blocks, down to 64 positions at
#: T = 32.  That reasoning holds while a trip's gather is the larger part of
#: what it moves; :func:`attn_block_pages` grows the block where it is not.
#: The walks' kernels (``models/head_walk.py``, ``models/latent_walk.py``)
#: keep a trip's state in VMEM and walk the same blocks: the block stays the
#: unit both the program and the host count in
ATTN_BLOCK_TOKENS = 128
ATTN_MIN_BLOCKS = 8
#: a trip reads a block of keys and rewrites the tile's float32 accumulator
#: (``acc * alpha + p @ v``: read and written once a trip, whatever the
#: block's length).  The block doubles from ``ATTN_BLOCK_TOKENS`` until its
#: gathered bytes are at least this many times the accumulator's.  K and V
#: by head at 2-8 query heads a K/V head gather 8-2 times the state at 128
#: positions and stay there; a latent page (1280 B a position under 64 heads
#: x 512 values: 160 KiB gathered against 512 KiB rewritten, a tile) grows
#: to 512.  Measured on the chip at fixed steps of the latent program
#: (PERF.md section 6, PR 31; wall ms), blocks of 128 / 256 / 512 / 1024
#: positions: a 48-slot chunk at depth 8k and two decode rows 31.1 / 30.0 /
#: 28.8 / 28.9, fifteen decode rows and a chunk at 16k 65.2 / 59.7 / 54.2 /
#: 54.3, five decode rows alone 22.6 / 20.9 / 19.3 / 19.1, a chunk at 24k and
#: eight decode rows 57.9 / 53.0 / 48.1 / 48.0, a chunk at depth 0 alone
#: 19.5 / 19.8 / 20.1 / 20.5 (one trip either way, and a longer one costs
#: more): a ratio of 2 (blocks of 1024) gains nothing over 1 and loses there
ATTN_STATE_RATIO = 1


def window_ring_pages(window: int, page_size: int, max_batch_tokens: int) -> int:
    """Pages a sequence holds of a window layer, as a ring (logical page
    ``n`` in slot ``n % ring``): the least ring in which the newest write of
    a step (a chunk of up to ``max_batch_tokens`` positions) cannot land on
    a page that the chunk's oldest token still sees — the window, one
    chunk, and a page of misalignment."""
    return (window + max_batch_tokens + page_size - 3) // page_size + 1


def attn_block_pages(page_size: int, pages_per_seq: int, pos_bytes: int,
                     n_heads: int, n_kv_heads: int, v_dim: int) -> int:
    """Pages in one block of :func:`paged_attention`'s walk over a page
    table ``pages_per_seq`` wide, for ONE kind of page: ``pos_bytes`` is what
    a position holds in all the kind's arenas of a layer
    (:func:`arena_pos_bytes`), ``n_heads`` / ``n_kv_heads`` / ``v_dim`` what
    the walk accumulates (``v_dim`` wide values under ``n_heads`` query
    heads).  The least power-of-two multiple of ``ATTN_BLOCK_TOKENS``
    positions whose gathered bytes are ``ATTN_STATE_RATIO`` times a tile's
    float32 accumulator, then at most an ``ATTN_MIN_BLOCKS``-th of the
    table — from the shapes alone, so the backend counts blocks on the host
    exactly as the program walks them."""
    if pos_bytes <= 0 or v_dim <= 0:
        raise ValueError(f"a position of {pos_bytes} bytes under values {v_dim} wide: "
                         "the rule needs both shapes")
    state = attn_tile_slots(n_heads // n_kv_heads) * n_heads * v_dim * 4
    tokens = ATTN_BLOCK_TOKENS
    while tokens * pos_bytes < ATTN_STATE_RATIO * state:
        tokens *= 2
    return max(1, min(tokens // page_size, -(-pages_per_seq // ATTN_MIN_BLOCKS)))


def arena_pos_bytes(shapes: Any, itemsize: int) -> int:
    """Bytes one position holds in a layer of one kind of page: ``shapes``
    are the trailing shapes of the kind's arenas behind ``[layers, pages,
    page_size]`` (``ModelSpec.arenas[kind]``, or ``a.shape[3:]`` of each)."""
    return sum(math.prod(shape) for shape in shapes) * itemsize


#: query slots a tile of :func:`paged_attention`'s walk holds, and tiles a
#: trip computes together: 8 x 8 slots, one step's buffer at T = 64.  The
#: walk's loop body is compiled once a layer, and XLA unrolls it over its
#: tensors, so its size sets the program's (PERF.md section 6, PR 29: static
#: classes of rows, 260 padded slots a trip, made the 7B program 2.4 times
#: as large and a warm start 10 s longer)
ATTN_TILE_SLOTS = 8
ATTN_GROUP_TILES = 8
#: the most product rows (slots x query heads a K/V head) a tile holds.  A
#: grouped-query model has 2 to 8 query heads a K/V head and keeps tiles of
#: ``ATTN_TILE_SLOTS``; the absorbed form of latent attention has ONE shared
#: key head under all its query heads (64 of them), and a tile of 8 slots
#: would be 512 rows of which a decode row fills 64: its tile narrows to 4
#: slots.  Measured on the chip at fixed steps of the latent program, 1 / 2 /
#: 4 / 8 slots a tile (PERF.md section 6, PR 30): a 48-slot chunk at depth 8k
#: and two decode rows 43.8 / 37.4 / 34.0 / 34.1 ms, fifteen decode rows and
#: a chunk at 16k 72.5 / 61.8 / 59.7 / 68.2, five decode rows alone 20.1 /
#: 21.1 / 22.2 / 30.2
ATTN_TILE_ROWS = 256


def attn_tile_slots(rep: int) -> int:
    """Query slots a tile holds at ``rep`` query heads a K/V head."""
    return max(1, min(ATTN_TILE_SLOTS, ATTN_TILE_ROWS // rep))


def attn_tiles(n_slots: int, n_rows: int, tile_slots: int = ATTN_TILE_SLOTS) -> int:
    """The most tiles a step can hold, in whole groups: a tile is up to
    ``tile_slots`` consecutive slots of ONE table row, so every row wastes
    less than one tile and ``n_slots // tile_slots + n_rows`` bound them (as
    ``n_slots`` does: a tile holds a slot) — from the shapes alone, whatever
    the engine feeds."""
    most = min(n_slots, n_slots // tile_slots + n_rows)
    return -(-most // ATTN_GROUP_TILES) * ATTN_GROUP_TILES


def walk_order(newest: Any, live: Any) -> Any:
    """The order in which :func:`paged_attention` walks its tiles (numpy or
    jax arrays, one entry a tile): the live ones first, by falling newest
    position, so that a group holds tiles of like length and the groups of
    short ones end their walks early."""
    xp = np if isinstance(newest, np.ndarray) else jnp
    return xp.argsort(xp.where(live, -newest, 1), stable=True)


def first_block(oldest: Any, block_tokens: int, window: Optional[int]) -> Any:
    """The block a walk starts at (numpy or jax int arrays: the position of
    each tile's first fed slot, or of each slot): 0, or under a window the
    block of the oldest key that position sees."""
    return 0 * oldest if window is None else (oldest - (window - 1)).clip(0) // block_tokens


def walk_blocks(oldest: Any, newest: Any, block_tokens: int,
                window: Optional[int] = None) -> tuple[Any, Any]:
    """The rule of the ``jax.numpy`` walk of :func:`paged_attention`, for the
    program's traced bound and the host's count alike (numpy or jax int
    arrays, one entry a tile of one group): ``oldest`` / ``newest`` are the
    positions of the tile's first and last fed slot (0 and 0 for a tile with
    none).  Returns ``(first, trips)``: the block each tile's walk starts at
    (:func:`first_block`) and the trips of the group, its longest tile-walk:
    each ends at the block of its tile's newest slot."""
    first = first_block(oldest, block_tokens, window)
    return first, (newest // block_tokens - first).max() + 1


def tile_trips(newest: Any, live: Any, block_tokens: int, first: Any = None) -> Any:
    """Blocks each tile of a walk's kernel walks (numpy or jax int arrays, one
    entry a tile): from its own ``first`` block (:func:`first_block`; None:
    block 0) to the block of its own newest slot, none for an idle tile — the
    kernel's loop bound and the host's count alike."""
    xp = np if isinstance(newest, np.ndarray) else jnp
    last = newest // block_tokens
    return xp.where(live, (last if first is None else last - first) + 1, 0)


def tile_runs(rows: Any, first: Any, last: Any, live: Any) -> Any:
    """The RUNS of the by-head kernel's walk (``models/head_walk.py``; numpy
    or jax arrays, one entry a tile of a step's, in the walk's order): within
    a group of ``ATTN_GROUP_TILES`` tiles, the consecutive live tiles that
    sit on the same table row (``rows``).  A run's tiles share each block's
    copy: its blocks go from the least of its tiles' ``first`` blocks
    (:func:`first_block`) to the greatest of their ``last`` (the block of a
    tile's newest slot).  Returns int ``[tiles, 3]``: at the tile that heads
    a run the run's tiles, its blocks and the first of them, zeros at every
    other tile — the kernel's prefetched scalars and the host's count of its
    copies alike."""
    xp = np if isinstance(rows, np.ndarray) else jnp
    at = xp.arange(1, rows.shape[0])
    joins = live[1:] & live[:-1] & (rows[1:] == rows[:-1]) & (at % ATTN_GROUP_TILES != 0)
    head = live & ~xp.concatenate([live[:1] & False, joins])
    run = xp.cumsum(head)
    # [tile, tile]: the tiles of each tile's own run
    mine = (run[:, None] == run[None, :]) & live[:, None] & live[None, :]
    lo = xp.where(mine, first[None, :], 2 ** 30).min(-1)
    hi = xp.where(mine, last[None, :], -1).max(-1)
    return xp.where(head[:, None], xp.stack([mine.sum(-1), hi - lo + 1, lo], axis=-1), 0)


def mesh_devices(arena: Any) -> int:
    """Devices of the mesh ``arena`` is laid out over, for
    :func:`walk_kernel`: of a traced operand (its type carries the mesh of
    the sharding it was given, through every jit) or of an array on its
    devices."""
    if isinstance(arena, jax.core.Tracer):
        return jax.typeof(arena).sharding.mesh.size
    return len(arena.devices())


def walk_kernel(platform: Optional[str], by_head: bool, window: Optional[int],
                mesh_devices: int) -> Any:
    """THE rule that says which kernel walks one kind of page: the kernel's
    module (``KERNEL_NAME``, ``PLATFORM``, ``walk_group``) where a step
    program lowered for ``platform`` holds one, None where the walk is
    ``jax.numpy``'s.  From what the code can observe alone: the arena's form
    (``by_head``: K and V by head, ``models/head_walk.py``; else ONE latent
    array, ``models/latent_walk.py``), the ``window`` (the by-head kernel
    walks a ring from each tile's own first block; a latent arena under a
    window, which no family has, keeps the ``jax.numpy`` walk), the devices of
    the mesh the arenas are laid out over (:func:`mesh_devices`: a Pallas call
    is one device's) and the platform, each by the kernel's own
    ``holds_kernel``.  ``platform`` None asks for the
    kernel's own platform: what a trace holds for the lowering to choose from
    (:func:`paged_attention`); a family's ``ModelSpec.kernels`` asks for the
    platform its arenas live on (:func:`walk_label`).  A kernel's module
    imports Pallas, a second or more: it is imported here and nowhere else, so
    a program with no such arena never pays it."""
    if by_head:
        from . import head_walk as kernel

        held = kernel.holds_kernel(platform or kernel.PLATFORM, True, mesh_devices)
    elif window is not None:
        return None
    else:
        from . import latent_walk as kernel

        held = kernel.holds_kernel(platform or kernel.PLATFORM, latent=True)
    return kernel if held else None


#: the walk's role in a family's ``ModelSpec.kernels`` a kind of page, in the
#: kinds' order: the whole-row kind's, the window kind's rings'
WALK_ROLES = ("walk", "ring")


def walk_label(platform: str, by_head: bool, mesh_devices: int,
               window: Optional[int] = None) -> dict[str, str]:
    """The walks' roles of a family's ``ModelSpec.kernels`` (:data:`WALK_ROLES`):
    the name of the kernel its whole-row kind of page is walked with and, for
    a family with a ``window``, the one its rings are — "" for ``jax.numpy``'s.
    The host counts each kind by its role (:func:`count_walk`)."""
    kinds = (None,) if window is None else (None, window)
    held = {role: walk_kernel(platform, by_head, win, mesh_devices)
            for role, win in zip(WALK_ROLES, kinds)}
    return {role: kernel.KERNEL_NAME if kernel is not None else "" for role, kernel in held.items()}


# jitted, with the layer a traced operand: the layers of a step program trace
# and lower ONE walk a kind of page (a warm start pays the tracing)
@partial(jax.jit, static_argnames=("block_pages", "window", "v_dim", "scale"))
def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: Optional[jax.Array],
    layer: Any,
    tables: jax.Array,
    token_seq: jax.Array,
    positions: jax.Array,
    block_pages: int,
    window: Optional[int] = None,
    *,
    v_dim: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal attention of every fed buffer slot over its own sequence's
    pages, walked once a TILE of a table row's slots and not once a slot.

    q: [T, h, hd]; k_pages / v_pages: the arenas ``[L, N, ps, kvh, hd]``
    (or ``k_pages`` [L, N, ps, hd] with no head axis and ``v_pages`` None: a
    latent cache, below); tables: [S+1, P] int32, the page tables (row S is the padding row, which
    is not walked: nothing reads a padding slot's attention); token_seq: [T]
    int32 table row of each slot; positions: [T] int32.  Returns [T, h, hd]
    in q's dtype.  It leans on one contract of the caller: **a row's slots
    are contiguous in the buffer** (as ``ServingBackend.step`` packs them).
    Four properties (docs/SERVING.md §The ragged entry point):

    * **tiles** — a row's slots are cut into tiles of ``ATTN_TILE_SLOTS``
      (a decode row is one tile, a draft row of 1 + k slots one, a 48-slot
      chunk six; narrower where one K/V head serves very many query heads:
      :func:`attn_tile_slots`); :func:`attn_tiles` bounds their number from
      the shapes alone.  The tiles are found once a step from ``token_seq`` and
      ``positions`` (identical in every layer: XLA computes it once),
      ordered by :func:`walk_order` and walked ``ATTN_GROUP_TILES`` at a
      time; a group none of whose tiles is fed is not walked at all, so the
      padding slots, most of the buffer at low occupancy, cost nothing;
    * **a tile's slots are the products' rows** — q is read as ``[kvh,
      slots x rep, hd]`` per tile and both products keep ``kvh`` a batch
      dimension, so K and V are never repeated to ``h`` heads.  A trip
      gathers ``block_pages`` pages of each tile's row in one gather that
      carries the layer index (the table's width padded to whole blocks
      with the null page): a chunk's pages are read once a tile of eight
      slots, not once a slot;
    * **online softmax** — scores and the running maximum / sum /
      accumulator are float32 per slot and head, probabilities are cast to
      the arena's dtype for the value product, the causal mask is per slot
      from ``positions``;
    * **a group's walk ends at its longest tile** (:func:`walk_blocks`) — a
      traced trip count on static shapes: one program, and blocks past it
      are never read (the walks' kernels start and end each TILE at its own
      blocks: below).

    A masked key scores ``-1e30``, not ``-inf``.  A slot whose first walked
    blocks hold none of its visible keys (under a window the TILE starts the
    walk, at its oldest slot's oldest key) carries a maximum of ``-1e30``
    through them (finite sums of values nobody keeps), and at its first
    visible key the maximum becomes that key's score, ``alpha = exp(-1e30 -
    score)`` is exactly 0 and the state restarts from it; a later, wholly
    masked block contributes exact zeros.  In a full layer position 0 is
    visible to every slot, so this only happens under a window.  A tile's
    slots beyond its count, a group that was not walked (zeros) and the
    output of a padding slot are finite values nothing reads.

    **A window layer** (``window`` = W, docs/SERVING.md §Two kinds of page)
    adds a lower bound to the mask and to the walk: position ``p`` sees keys
    ``p - W + 1 .. p``, and ``tables`` is then a RING of pages per sequence
    (:func:`window_ring_pages` wide): logical page ``n`` of the row sits in
    ring slot ``n % ring``, so a row holds a bounded number of pages however
    long it grows.  Each tile starts its walk at the block that holds the
    oldest key its oldest slot sees (:func:`first_block`) and ends it at its
    newest slot's block — at most ``W / block_tokens + 2`` blocks, whatever
    the row's length; as ``jax.numpy`` a group's trip count is its longest
    such walk, under the by-head kernel each tile makes its own.  The ring
    is the window, one step's buffer and a page wide, so no key a slot sees
    has been overwritten by its row's newest write; a ring slot the walk
    reads twice is masked by its logical position.

    **A latent cache** (the absorbed form of latent attention,
    ``models/axk1.py``): ``k_pages`` is the ONE array ``[L, N, ps, hd]`` a
    layer keeps, no head axis — one shared key under all ``h`` query heads
    — and ``v_pages`` is None: a slot's value is the leading ``v_dim``
    columns of its key, so a trip's ONE gather feeds both products and the
    accumulators are ``v_dim`` wide; returns [T, h, v_dim].  ``scale`` is the
    softmax scale where it is not ``1 / sqrt(hd)``.  With a V arena and no
    scale the program is the one it was.

    **Two walks behind either form of arena** (``models/latent_walk.py``,
    ``models/head_walk.py``): where the program is LOWERED for the TPU, a
    group's block walk is one Pallas kernel — a tile's queries, the scores,
    the mask and the float32 softmax state live in VMEM for the tile's whole
    walk, a block's pages are copied page by page from the arena (both
    arenas by head; they stay in HBM) into a VMEM block of a few buffers, and
    **each tile ends at its OWN newest block** (:func:`tile_trips`; a ring
    is walked by the by-head kernel from the tile's OWN first block too, its
    pages found round the ring where their copies are started),
    where the ``jax.numpy`` walk drags every tile of a group to the longest.
    Under the by-head kernel the consecutive tiles of a group on ONE table
    row, a RUN (:func:`tile_runs`: a prefill chunk's tiles lie side by side in
    the walk's order), share each block's copy and its taking apart, and a
    tile that feeds few slots (a decode row) computes their rows alone: the
    kernel is handed -1 for the position of a slot its tile does not feed.
    Everything round it is shared: the tiles, their order, the groups, the
    loop over the groups that hold a live tile, the gather of the tiles'
    queries, the scatter back to buffer slots, the block as the counted unit.
    ``jax.lax.platform_dependent`` chooses, by what the code can observe
    alone: the arena's form (one latent array, ``v_pages is None``: the
    latent kernel, with no window; K and V by head: the by-head kernel, whole
    rows and a window's rings alike, the ``window`` a static parameter of
    it), the lowering platform, and for K and V by head that the program
    is not partitioned over a mesh (:func:`mesh_devices` of the traced
    arena: the tensor-parallel gang shards the arenas by head, and a Pallas
    call is one device's).  Every other platform (the CPU's tests and float32
    references), a latent arena under a window and a program over a mesh keep
    the ``jax.numpy`` walk below, byte for byte the program it was.  Same numerics
    in all: operands in the arena's dtype, float32 scores and state,
    probabilities cast to the arena's dtype, a masked key ``-1e30``."""
    t, h, hd = q.shape
    ps = k_pages.shape[2]
    kvh = k_pages.shape[3] if k_pages.ndim == 5 else 1
    vd = hd if v_pages is not None else v_dim
    rep = h // kvh
    s_rows = tables.shape[0] - 1
    bp = block_pages
    bt = bp * ps  # token positions a block
    w, g = attn_tile_slots(rep), ATTN_GROUP_TILES
    n_tiles = attn_tiles(t, s_rows, w)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    itype = positions.dtype
    # the tiles: a fed slot starts one where it is the first of its row's
    # run in the buffer or a whole number of tiles behind it
    at = jnp.arange(t, dtype=itype)
    fed = token_seq < s_rows
    prev = jnp.concatenate([token_seq[:1] + 1, token_seq[:-1]])
    run_lo = jax.lax.cummax(jnp.where(token_seq != prev, at, 0))  # [T] its row's first slot
    nxt = jnp.concatenate([token_seq[1:], token_seq[-1:] + 1])
    run_hi = jnp.flip(jax.lax.cummin(jnp.flip(jnp.where(token_seq != nxt, at, t - 1))))
    starts = fed & ((at - run_lo) % w == 0)
    last = jnp.minimum(at + (w - 1), run_hi)  # [T] the last slot of a tile that starts here
    pad = (0, max(0, n_tiles - t))  # a buffer of fewer slots than tiles
    order = walk_order(jnp.pad(positions[last], pad), jnp.pad(starts, pad))[:n_tiles]
    live = jnp.pad(starts, pad)[order]
    slot0 = jnp.where(live, order, 0)  # [tiles] a tile's first buffer slot
    slots = jnp.minimum(slot0[:, None] + jnp.arange(w, dtype=itype)[None, :], t - 1)
    oldest = jnp.where(live, positions[slot0], 0)
    newest = jnp.where(live, positions[last[slot0]], 0)
    trow = jnp.where(live, token_seq[slot0], s_rows)  # idle tiles sit on the padding row
    tab = tables[trow]  # [tiles, P]
    if window is None:
        n_blocks = -(-tab.shape[1] // bp)
        tab = jnp.pad(tab, ((0, 0), (0, n_blocks * bp - tab.shape[1])))
    else:
        ring = tab.shape[1]
        lane = jnp.arange(bp, dtype=itype)
    offs = jnp.arange(bt, dtype=itype)
    # the kernel a lowering of THIS program would hold, None where none would
    kernel = walk_kernel(None, v_pages is not None, window, mesh_devices(k_pages))
    # each tile's queries [tiles, kvh, slots x rep, hd] and their positions
    qt = q.reshape(t, kvh, rep, hd)[slots].transpose(0, 2, 1, 3, 4).reshape(
        n_tiles, kvh, w * rep, hd)
    pslot = positions[slots]  # [tiles, slots]
    pt = jnp.repeat(pslot, rep, axis=1)[:, :, None]  # [tiles, slots x rep, 1]

    if kernel is not None and v_pages is not None:
        # what the by-head kernel is told of every tile: its own first block
        # and trips, the runs of tiles that share a copy, and which of its
        # slots it feeds (the others' positions are -1)
        firsts = first_block(oldest, bt, window)
        trips = tile_trips(newest, live, bt, None if window is None else firsts)
        runs = tile_runs(trow, firsts, newest // bt, live)
        behind = jnp.arange(w, dtype=itype)[None, :] <= (last[slot0] - slot0)[:, None]
        pfed = jnp.where(live[:, None] & behind, pslot, -1)

    def walk_jnp(lo, out):
        """One group's walk as ``jax.numpy``, every tile to the group's
        longest, its outputs written behind ``out``'s slot ``lo * w``."""
        qc, pc, tab_c = (jax.lax.dynamic_slice_in_dim(x, lo, g) for x in (qt, pt, tab))
        first, trips = walk_blocks(jax.lax.dynamic_slice_in_dim(oldest, lo, g),
                                   jax.lax.dynamic_slice_in_dim(newest, lo, g), bt, window)

        def block(j, carry):
            m, l, acc = carry
            with jax.named_scope("attn_gather"):
                if window is None:
                    ids = jax.lax.dynamic_slice_in_dim(tab_c, j * bp, bp, axis=1)
                    k_pos = (j * bt + offs)[None, None, :]
                else:
                    blk = first + j  # [G]: each tile's own block of this trip
                    ids = jnp.take_along_axis(
                        tab_c, (blk[:, None] * bp + lane[None, :]) % ring, axis=1)
                    k_pos = (blk[:, None] * bt + offs[None, :])[:, None, :]
                kb = k_pages[layer, ids].reshape(g, bt, kvh, hd)
                vb = (kb[..., :vd] if v_pages is None
                      else v_pages[layer, ids].reshape(g, bt, kvh, hd))
            with jax.named_scope("attn_scores"):
                s = jnp.einsum("rgmd,rkgd->rgmk", qc, kb,
                               preferred_element_type=jnp.float32) * scale
                seen = k_pos <= pc  # [G, slots x rep, bt]
                if window is not None:
                    seen &= k_pos > pc - window
                s = jnp.where(seen[:, None], s, -1e30)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None])
                l = l * alpha + jnp.sum(p, axis=-1)
                acc = acc * alpha[..., None] + jnp.einsum(
                    "rgmk,rkgd->rgmd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
            return m_new, l, acc

        stat = (g, kvh, w * rep)
        init = (jnp.full(stat, -1e30, jnp.float32), jnp.zeros(stat, jnp.float32),
                jnp.zeros(stat + (vd,), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, trips, block, init)
        done = (acc / l[..., None]).astype(q.dtype).reshape(g, kvh, w, rep, vd)
        return jax.lax.dynamic_update_slice_in_dim(
            out, done.transpose(0, 2, 1, 3, 4).reshape(g * w, h, vd), lo * w, axis=0)

    def walk_latent(lo, out):
        """The same group through ``latent_walk``'s kernel: every tile to
        its OWN end, its queries read from and its outputs written into the
        step's whole arrays in place (one key head: a tile's rows are its
        slots x heads as ``out`` has them)."""
        tab_c, new_c, live_c, pslot_c = (
            jax.lax.dynamic_slice_in_dim(x, lo, g) for x in (tab, newest, live, pslot))
        return kernel.walk_group(
            qt[:, 0], pslot_c, k_pages, layer, tab_c, tile_trips(new_c, live_c, bt),
            out.reshape(n_tiles, w * h, vd), lo, block_pages=bp, v_dim=vd, scale=scale,
        ).reshape(out.shape)

    def walk_heads(lo, out):
        """The same group through ``head_walk``'s kernel: every tile to its
        OWN end (under a window: from its own first block too, round its
        ring), the tiles of a RUN sharing each block's copy, its queries read
        from the step's whole array in place; the group's outputs come back a
        K/V head's rows together and are laid out by slot as ``walk_jnp`` lays
        its own."""
        tab_c, pslot_c, trips_c, runs_c, firsts_c = (
            jax.lax.dynamic_slice_in_dim(x, lo, g) for x in (tab, pfed, trips, runs, firsts))
        ring = {} if window is None else dict(window=window, first_blocks=firsts_c)
        done = kernel.walk_group(
            qt, pslot_c, k_pages, v_pages, layer, tab_c, trips_c, runs_c,
            lo, block_pages=bp, scale=scale, **ring).reshape(g, kvh, w, rep, vd)
        return jax.lax.dynamic_update_slice_in_dim(
            out, done.transpose(0, 2, 1, 3, 4).reshape(g * w, h, vd), lo * w, axis=0)

    def group(i, out):
        lo = i * g
        if kernel is None:
            return walk_jnp(lo, out)
        # two walks, chosen where the program is lowered
        return jax.lax.platform_dependent(
            lo, out, default=walk_jnp,
            **{kernel.PLATFORM: walk_heads if v_pages is not None else walk_latent})

    # the groups that hold a live tile (they come first), each to its own end
    walked = (jnp.sum(live, dtype=itype) + (g - 1)) // g
    out = jax.lax.fori_loop(0, walked, group, jnp.zeros((n_tiles * w, h, vd), q.dtype))
    # where a buffer slot finds its output: its tile's rank, its place in it
    rank = jnp.zeros((t,), itype).at[jnp.where(live, slot0, t)].set(
        jnp.arange(n_tiles, dtype=itype), mode="drop")
    mine = at - (at - run_lo) % w  # [T] the slot that starts this slot's tile
    return out[rank[mine] * w + (at - mine)]


def count_walk(spans: Any, positions: Any, tile_slots: int, block_tokens: tuple[int, ...],
               window: Optional[int], own_ends: tuple[bool, ...],
               shared: tuple[bool, ...] = ()) -> tuple[int, int, tuple[int, int], int]:
    """One step's walk as :func:`paged_attention` makes it, counted on the
    host (plain numpy, no kernel's module) from ``spans`` (int [rows, 2]: each
    fed row's buffer slots, packed one behind the other from slot 0) and the
    packed ``positions``: the rows cut into tiles of ``tile_slots``, the tiles
    in the program's order, and a KIND of page after the other
    (``block_tokens``: a block's positions, the whole-row kind's, then under
    ``window`` the ring kind's) by the rule of that kind's walk (``own_ends``,
    one flag a kind): each tile from its OWN first block to its OWN end
    (:func:`first_block`, :func:`tile_trips`) where the kind's walk is a
    kernel (:func:`walk_kernel`; a family's ``ModelSpec.kernels`` says so a
    kind: :func:`walk_label`), each group of tiles to its longest
    (:func:`walk_blocks`) where it is ``jax.numpy``'s.  ``shared`` (one flag a
    kind, none by default) says which kinds' kernel copies a block ONCE for a
    run of tiles (:func:`tile_runs`: the by-head kernel's; the latent kernel
    and ``jax.numpy`` copy or gather a block a tile-trip).  Returns the step's
    report: the longest walk over whole rows and over rings, in blocks;
    ``(table rows gathered, query slots computed)``, a block each, by one
    layer of each kind together: the copies or gathers the program makes, and
    ``tile_slots`` times its tile-trips, so ``1 - gathered x tile_slots /
    computed`` is the share of tile-trips that rode another tile's copy; and
    the FED slots among those that needed their block."""
    w, g = tile_slots, ATTN_GROUP_TILES
    per_row = -(-(spans[:, 1] - spans[:, 0]) // w)  # tiles a row
    lo = np.concatenate([np.arange(a, b, w) for a, b in spans])  # a tile's first slot
    hi = np.minimum(lo + w, np.repeat(spans[:, 1], per_row))
    order = walk_order(positions[hi - 1], np.ones(len(lo), bool))
    oldest, newest = positions[lo][order], positions[hi - 1][order]
    rows, every = np.repeat(np.arange(len(spans)), per_row)[order], np.ones(len(order), bool)
    gathered = walked = live = 0  # block copies or gathers; tile-trips, ``w`` query slots each
    fed = positions[:spans[-1, 1]]
    longest = [0, 0]
    kinds = zip(block_tokens, (None, window), own_ends, tuple(shared) + (False, False))
    for kind, (bt, win, own, runs) in enumerate(kinds):
        # a fed slot needs the blocks from its oldest visible key's to its own
        live += int((fed // bt - first_block(fed, bt, win) + 1).sum())
        first = first_block(oldest, bt, win)
        if own:
            trips = tile_trips(newest, every, bt, first)
        else:
            trips = np.repeat([walk_blocks(oldest[a:a + g], newest[a:a + g], bt, win)[1]
                               for a in range(0, len(order), g)], g)
        longest[kind] = int(trips.max())
        walked += int(trips.sum())
        # a copy a tile-trip, but where a row of several tiles shares its blocks' copies
        gathered += int(tile_runs(rows, first, newest // bt, every)[:, 1].sum()
                        if runs and per_row.max() > 1 else trips.sum())
    return longest[0], longest[1], (gathered, w * walked), live
