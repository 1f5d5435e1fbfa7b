"""Llama-family decoder, TPU-first.

This is the flagship model the TPU worker executes for inference jobs
(BASELINE.json config #5: "Llama-3-8B JAX inference step behind safety-kernel
REQUIRE_APPROVAL").  Design choices for the MXU/ICI:

  * functional pytree params + pure ``forward`` — everything jits, no
    framework indirection; params default to bfloat16 (MXU-native)
  * GQA attention with RoPE, RMSNorm, SwiGLU — Llama-3 architecture family
  * sharding by annotation: :func:`param_specs` gives the Megatron-style
    tensor-parallel layout (column-parallel qkv/gate, row-parallel
    out/down), activations are constrained to ``(dp, sp, ·)`` so long
    sequences shard over the ``sp`` axis; XLA GSPMD inserts the ICI
    collectives (all-gather for KV over ``sp``, psum for row-parallel
    matmuls) — no hand-written NCCL-style code, per the scaling-book recipe
  * static shapes, ``lax``-friendly: causal mask built with iota/compare,
    no data-dependent Python control flow
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP

Params = dict


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1536
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    # long-context: ring attention over `sp` (K/V rotate via ppermute, no
    # device ever holds the full sequence) instead of the KV all-gather
    use_ring_attention: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, rope_theta=500000.0, max_seq_len=8192,
        )

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        return cls(vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=128)

    def serving_spec(self) -> Any:
        return serving_spec(self)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    keys = jax.random.split(key, cfg.n_layers + 2)
    d, h, kvh, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff

    def dense(k, shape, scale_dim):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(scale_dim)).astype(cfg.dtype)

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], 7)
        layers.append(
            {
                "attn_norm": jnp.ones((d,), cfg.dtype),
                "wq": dense(lk[0], (d, h * hd), d),
                "wk": dense(lk[1], (d, kvh * hd), d),
                "wv": dense(lk[2], (d, kvh * hd), d),
                "wo": dense(lk[3], (h * hd, d), h * hd),
                "mlp_norm": jnp.ones((d,), cfg.dtype),
                "w_gate": dense(lk[4], (d, f), d),
                "w_up": dense(lk[5], (d, f), d),
                "w_down": dense(lk[6], (f, d), f),
            }
        )
    return {
        "embed": dense(keys[-2], (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), cfg.dtype),
        "lm_head": dense(keys[-1], (d, cfg.vocab_size), d),
    }


def param_specs(cfg: LlamaConfig) -> Params:
    """Megatron-style TP layout as a PartitionSpec pytree."""
    layer = {
        "attn_norm": P(),
        "wq": P(None, AXIS_TP),
        "wk": P(None, AXIS_TP),
        "wv": P(None, AXIS_TP),
        "wo": P(AXIS_TP, None),
        "mlp_norm": P(),
        "w_gate": P(None, AXIS_TP),
        "w_up": P(None, AXIS_TP),
        "w_down": P(AXIS_TP, None),
    }
    return {
        "embed": P(AXIS_TP, None),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
        "final_norm": P(),
        "lm_head": P(None, AXIS_TP),
    }


def shard_params(params: Params, cfg: LlamaConfig, mesh: Mesh) -> Params:
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: isinstance(x, jnp.ndarray) or dataclasses.is_dataclass(x),
    )


#: KV page arenas shard by attention head — axis 3 of
#: ``[L, num_pages, page_size, kvh, hd]`` — matching the column-parallel
#: wk/wv layout, so the ragged step's page writes and gathers stay local to
#: each TP rank (docs/SERVING.md §Sharded serving).
KV_ARENA_SPEC = P(None, None, None, AXIS_TP, None)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: [B, T, H, Dh], positions: [B, T]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, Dh/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _attention(q, k, v, cfg: LlamaConfig, *, causal: bool = True, q_offset=None):
    """SDPA with GQA head expansion; fp32 softmax accumulation."""
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    rep = cfg.n_heads // cfg.n_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
    if causal:
        q_pos = jnp.arange(tq)[:, None] if q_offset is None else q_offset[:, :, None]
        k_pos = jnp.arange(tk)[None, :]
        mask = q_pos >= k_pos  # [Tq, Tk] or [B, Tq, Tk]
        if mask.ndim == 2:
            mask = mask[None, None, :, :]
        else:
            mask = mask[:, None, :, :]
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block(x, layer, cfg: LlamaConfig, positions, constrain, mesh=None):
    b, t, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    attn_in = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = (attn_in @ layer["wq"]).reshape(b, t, h, hd)
    k = (attn_in @ layer["wk"]).reshape(b, t, kvh, hd)
    v = (attn_in @ layer["wv"]).reshape(b, t, kvh, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if cfg.use_ring_attention and mesh is not None and mesh.shape.get(AXIS_SP, 1) > 1:
        # ring flavor: K/V never materialize the full sequence anywhere —
        # chunks rotate the sp ring with an online softmax (long contexts)
        from ..ops.ring_attention import ring_attention

        attn = ring_attention(q, k, v, mesh)
    else:
        # context parallelism (all-gather flavor): Q stays sequence-sharded
        # over `sp`; K/V are constrained to full sequence, so GSPMD inserts
        # the all-gather over the sp axis
        k = constrain(k, P(AXIS_DP, None, None, None))
        v = constrain(v, P(AXIS_DP, None, None, None))
        attn = _attention(q, k, v, cfg, q_offset=positions)
    x = x + (attn.reshape(b, t, h * hd) @ layer["wo"])
    x = constrain(x, P(AXIS_DP, AXIS_SP, None))

    mlp_in = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    gate = jax.nn.silu(mlp_in @ layer["w_gate"])
    up = mlp_in @ layer["w_up"]
    x = x + ((gate * up) @ layer["w_down"])
    x = constrain(x, P(AXIS_DP, AXIS_SP, None))
    return x


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    mesh: Optional[Mesh] = None,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Logits for next-token prediction; tokens: [B, T] int32 → [B, T, V]."""
    if mesh is not None and AXIS_SP in mesh.axis_names:
        def constrain(x, spec):
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    else:
        def constrain(x, spec):  # single-device / no-mesh path
            return x

    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    x = params["embed"][tokens]  # gather; embed sharded over tp on vocab dim
    x = constrain(x, P(AXIS_DP, AXIS_SP, None))
    for layer in params["layers"]:
        x = _block(x, layer, cfg, positions, constrain, mesh=mesh)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["lm_head"]


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


def walk_blocks(oldest: Any, newest: Any, block_tokens: int,
                window: Optional[int] = None) -> tuple[Any, Any]:
    """The rule of :func:`paged_attention`'s walk, for the program's traced
    bound and the host's count alike (numpy or jax int arrays, one entry a
    tile of one group): ``oldest`` / ``newest`` are the positions of the
    tile's first and last fed slot (0 and 0 for a tile with none).  Returns
    ``(first, trips)``: the block each tile's walk starts at — 0, or under a
    window the block of the oldest key the tile's oldest slot sees — and the
    trips of the group, its longest tile-walk: each ends at the block of its
    tile's newest slot."""
    first = 0 * oldest if window is None else (
        (oldest - (window - 1)).clip(0) // block_tokens)
    return first, (newest // block_tokens - first).max() + 1


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
      are never read (the walks' kernels end each TILE at its own: below).

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
    oldest key its oldest slot sees and ends it at its newest slot's block;
    a group's trip count is its longest such walk — at most ``W /
    block_tokens + 2`` blocks, whatever the row's length.  The ring is the
    window, one step's buffer and a page wide, so no key a slot sees has
    been overwritten by its row's newest write; a ring slot the walk reads
    twice is masked by its logical position.

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
    **each tile ends at its OWN newest block** (``latent_walk.tile_trips``),
    where the ``jax.numpy`` walk drags every tile of a group to the longest.
    Everything round it is shared: the tiles, their order, the groups, the
    loop over the groups that hold a live tile, the gather of the tiles'
    queries, the scatter back to buffer slots, the block as the counted unit.
    ``jax.lax.platform_dependent`` chooses, by what the code can observe
    alone: the arena's form (one latent array, ``v_pages is None``: the
    latent kernel; K and V by head: the by-head kernel; either with no
    window), the lowering platform, and for K and V by head that the program
    is not partitioned over a mesh (``head_walk.mesh_devices`` of the traced
    arena: the tensor-parallel gang shards the arenas by head, and a Pallas
    call is one device's).  Every other platform (the CPU's tests and float32
    references), the window's ring and a program over a mesh keep the
    ``jax.numpy`` walk below, byte for byte the program it was.  Same numerics
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
    latent = v_pages is None and window is None
    if latent:  # imported here: Pallas costs a second that a program with neither kernel never pays
        from . import latent_walk
    # K and V by head: the kernel where a TPU's lowering of THIS program would hold it
    by_head = False
    if v_pages is not None and window is None:
        from . import head_walk

        by_head = head_walk.holds_kernel(head_walk.PLATFORM, True, None,
                                         head_walk.mesh_devices(k_pages))
    # each tile's queries [tiles, kvh, slots x rep, hd] and their positions
    qt = q.reshape(t, kvh, rep, hd)[slots].transpose(0, 2, 1, 3, 4).reshape(
        n_tiles, kvh, w * rep, hd)
    pslot = positions[slots]  # [tiles, slots]
    pt = jnp.repeat(pslot, rep, axis=1)[:, :, None]  # [tiles, slots x rep, 1]

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

    def walk_kernel(lo, out):
        """The same group through ``latent_walk``'s kernel: every tile to
        its OWN end, its queries read from and its outputs written into the
        step's whole arrays in place (one key head: a tile's rows are its
        slots x heads as ``out`` has them)."""
        tab_c, new_c, live_c, pslot_c = (
            jax.lax.dynamic_slice_in_dim(x, lo, g) for x in (tab, newest, live, pslot))
        return latent_walk.walk_group(
            qt[:, 0], pslot_c, k_pages, layer, tab_c, latent_walk.tile_trips(new_c, live_c, bt),
            out.reshape(n_tiles, w * h, vd), lo, block_pages=bp, v_dim=vd, scale=scale,
        ).reshape(out.shape)

    def walk_heads(lo, out):
        """The same group through ``head_walk``'s kernel: every tile to its
        OWN end, its queries read from the step's whole array in place; the
        group's outputs come back a K/V head's rows together and are laid
        out by slot as ``walk_jnp`` lays its own."""
        tab_c, new_c, live_c, pslot_c = (
            jax.lax.dynamic_slice_in_dim(x, lo, g) for x in (tab, newest, live, pslot))
        done = head_walk.walk_group(
            qt, pslot_c, k_pages, v_pages, layer, tab_c, head_walk.tile_trips(new_c, live_c, bt),
            lo, block_pages=bp, scale=scale).reshape(g, kvh, w, rep, vd)
        return jax.lax.dynamic_update_slice_in_dim(
            out, done.transpose(0, 2, 1, 3, 4).reshape(g * w, h, vd), lo * w, axis=0)

    def group(i, out):
        lo = i * g
        if latent:  # two walks, chosen where the program is lowered
            return jax.lax.platform_dependent(
                lo, out, default=walk_jnp, **{latent_walk.PLATFORM: walk_kernel})
        if by_head:
            return jax.lax.platform_dependent(
                lo, out, default=walk_jnp, **{head_walk.PLATFORM: walk_heads})
        return walk_jnp(lo, out)

    # the groups that hold a live tile (they come first), each to its own end
    walked = (jnp.sum(live, dtype=itype) + (g - 1)) // g
    out = jax.lax.fori_loop(0, walked, group, jnp.zeros((n_tiles * w, h, vd), q.dtype))
    # where a buffer slot finds its output: its tile's rank, its place in it
    rank = jnp.zeros((t,), itype).at[jnp.where(live, slot0, t)].set(
        jnp.arange(n_tiles, dtype=itype), mode="drop")
    mine = at - (at - run_lo) % w  # [T] the slot that starts this slot's tile
    return out[rank[mine] * w + (at - mine)]


def serving_spec(cfg: LlamaConfig) -> Any:
    """The family's specification for the serving backend
    (``serving/modelspec.py``): one kind of page, no counters."""
    from ..serving.modelspec import ModelSpec, kv_pair

    def program(sample_logits):
        def ragged_program(p, kp, vp, toks, pos, pt, ts, oi):
            return ragged_step(p, kp, vp, toks, pos, pt, ts, oi, cfg, sample_logits=sample_logits)

        return ragged_program

    return ModelSpec(
        family="llama", cfg=cfg, vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        init_params=lambda key: init_params(key, cfg),
        init_arenas=lambda n, ps, _w: init_kv_pages(cfg, n, ps),
        program=program, arenas=(kv_pair(cfg.n_kv_heads, cfg.head_dim),), value_dim=cfg.head_dim,
    )


def ragged_step(
    params: Params,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    token_seq: jax.Array,
    out_idx: jax.Array,
    cfg: LlamaConfig,
    *,
    sample_logits: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One ragged mixed prefill+decode step over the paged KV cache — the
    Ragged Paged Attention entry point (PAPERS.md): a single XLA program
    serves any mix of prefill chunks and decode steps over arbitrary
    per-sequence lengths.

    The batch dimension is **tokens, not sequences**: a decode step
    contributes one token, a prefill chunk contributes its whole slice, and
    they ride the same flat buffer.

    tokens: [T] int32 flat token buffer (decode last-tokens and prefill
    chunk tokens interleaved; tail padded with 0s mapped to the padding
    row); positions: [T] int32 global sequence position of each token (==
    the page slot it writes); page_tables: [S+1, P] int32 per-sequence page
    tables — row S is the all-null padding row; token_seq: [T] int32 row of
    ``page_tables`` each token belongs to (padding tokens → S); out_idx:
    [S] int32 index into the token buffer of each sequence's last fed token
    (the sampling position; unused rows point anywhere).  Returns
    (next_tokens [T] int32 — the next-token argmax after every fed buffer
    position; a sequence's sample is row ``out_idx[s]``, a draft row's
    per-position verification votes are its contiguous token slots —
    k_pages, v_pages).

    Shape discipline is the whole point: every operand has a static shape
    regardless of how many sequences are live or how long each one is, so
    the program compiles exactly ONCE — no prompt-length buckets, no batch
    buckets, no recompile cliff when sessions join or leave.  Raggedness is
    expressed through the metadata: each token writes its K/V at
    ``(page_tables[token_seq[t]][positions[t] // ps], positions[t] % ps)``
    *before* the gather, then attends to its own sequence's pages under the
    causal mask ``k_pos <= position`` — in-chunk tokens see each other
    exactly as a full-sequence forward would, padding rows park on the null
    page, and no token can reach another sequence's pages because the
    gather walks only its own page-table row.  The attention is
    :func:`paged_attention`: the walk runs over TILES of a table row's
    slots, not over the buffer slots — a row's fed slots are cut into tiles
    of ``ATTN_TILE_SLOTS``, a tile's pages are gathered once a block of
    :func:`attn_block_pages` pages and its slots are the rows of
    grouped-query products (K and V are read as stored, never repeated);
    the tiles are ordered by length and walked a group at a time, each group
    to the block that holds its longest tile — traced trip counts from
    ``token_seq`` and ``positions``, so the cost follows the slots that are
    fed and their rows' lengths, not the buffer's size nor the context the
    table could hold.  It leans on this function's own packing contract: a
    row's slots are contiguous in the buffer.  (A jnp formulation that runs
    anywhere; a Pallas kernel walking the page table in VMEM is the TPU
    upgrade path.)

    ``sample_logits`` is a STATIC flag for serving-gang followers
    (docs/SERVING.md §Sharded serving): rank 0 alone owns sampling, so
    follower ranks compile with ``sample_logits=False`` and get a program
    whose lm_head projection + argmax are dead-code-eliminated — they still
    produce byte-identical K/V arena updates (the writes depend only on the
    transformer stack), but return an all-zeros token buffer nothing
    reads."""
    t_buf = tokens.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ps = k_pages.shape[2]
    pos2 = positions[:, None]  # [T, 1]
    page_idx = page_tables[token_seq, positions // ps]  # [T] — each token's own page
    slot = positions % ps
    block_pages = attn_block_pages(
        ps, page_tables.shape[1],
        arena_pos_bytes((k_pages.shape[3:], v_pages.shape[3:]), k_pages.dtype.itemsize),
        h, kvh, hd)
    # the named scopes are metadata only: they label the operations in a
    # device trace (per-kernel time by scope) and change none of them
    with jax.named_scope("embed"):
        x = params["embed"][tokens][:, None, :]  # [T, 1, d]
    for li, layer in enumerate(params["layers"]):
        attn_in = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q = (attn_in @ layer["wq"]).reshape(t_buf, 1, h, hd)
        k = (attn_in @ layer["wk"]).reshape(t_buf, 1, kvh, hd)
        v = (attn_in @ layer["wv"]).reshape(t_buf, 1, kvh, hd)
        q = rope(q, pos2, cfg.rope_theta)
        k = rope(k, pos2, cfg.rope_theta)
        # write EVERY token's K/V before the gather: a prefill chunk's later
        # tokens must attend to its earlier ones within the same call (the
        # causal mask cuts the other direction), and a decode token must
        # attend to itself
        with jax.named_scope("kv_write"):
            k_pages = k_pages.at[li, page_idx, slot].set(k[:, 0])
            v_pages = v_pages.at[li, page_idx, slot].set(v[:, 0])
        # attn_gather and attn_scores label the body of the block walk
        attn = paged_attention(
            q[:, 0], k_pages, v_pages, li, page_tables, token_seq, positions, block_pages)
        x = x + (attn.reshape(t_buf, 1, h * hd) @ layer["wo"])
        with jax.named_scope("mlp"):
            mlp_in = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            gate = jax.nn.silu(mlp_in @ layer["w_gate"])
            up = mlp_in @ layer["w_up"]
            x = x + ((gate * up) @ layer["w_down"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    # lm_head over EVERY buffer row: speculative verification needs the
    # next-token prediction at each fed draft position, not just the
    # sequence-final one — a draft row's k+1 per-position argmaxes are the
    # accept-prefix votes (docs/SERVING.md §Speculative decoding).  The
    # per-row argmax at ``out_idx`` positions is unchanged math, so
    # non-draft sampling reads ``preds[out_idx]`` and gets exactly the
    # tokens the sequence-final projection produced; padding rows project
    # too but nothing reads them.
    if not sample_logits:
        # follower ranks: K/V writes above are the whole job — skip the
        # [T, V] projection entirely (static flag → XLA never emits it)
        return jnp.zeros((t_buf,), jnp.int32), k_pages, v_pages
    with jax.named_scope("lm_head"):
        logits = x[:, 0] @ params["lm_head"]  # [T, V]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, k_pages, v_pages


def loss_fn(params: Params, tokens: jax.Array, cfg: LlamaConfig, *, mesh=None) -> jax.Array:
    """Next-token cross entropy over all positions but the last."""
    logits = forward(params, tokens, cfg, mesh=mesh).astype(jnp.float32)
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# training step (used by the multi-chip dry run + training jobs)
# ---------------------------------------------------------------------------


def make_train_step(cfg: LlamaConfig, mesh: Mesh, optimizer=None):
    """Build a jitted SPMD train step: params sharded per :func:`param_specs`,
    batch over ``(dp, sp)``; gradients/optimizer states inherit param
    shardings via jit output shardings."""
    import optax

    opt = optimizer or optax.adamw(3e-4, weight_decay=0.01)
    pspecs = param_specs(cfg)
    param_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    batch_sharding = NamedSharding(mesh, P(AXIS_DP, AXIS_SP))

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg, mesh=mesh))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    jstep = jax.jit(
        step,
        in_shardings=(param_shardings, None, batch_sharding),
        out_shardings=(param_shardings, None, None),
        donate_argnums=(0, 1),
    )

    def init(key):
        params = init_params(key, cfg)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs
        )
        opt_state = opt.init(params)
        return params, opt_state

    return init, jstep
