"""``attention.paged_attention``: the ragged step's attention (docs/SERVING.md
§The ragged entry point) — one gather of pages a table row and block, a
row's slots as the rows of grouped-query products, an online softmax, and a
walk that ends at the step's longest live row — held to a plain dense
per-slot reference, and the counters the serving engine keeps of the walk."""
import asyncio
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cordum_tpu.models import attention, llama
from cordum_tpu.serving.backend import LlamaServingBackend, StepEntry
from cordum_tpu.serving.engine import GenRequest, ServingEngine

# a table of 11 pages of 4 positions walked 3 pages at a time: four blocks
# of 12 positions, the last one padded with the null page
PS, P, BLOCK_PAGES, KVH, HD, LAYERS = 4, 11, 3, 2, 16, 2
CONTEXT = P * PS
BLOCK_TOKENS = BLOCK_PAGES * PS
NAN_PAGE = 39
NUM_PAGES = 40


def dense_reference(q, k_pages, v_pages, layer, tables, token_seq, positions,
                    window=None, scale=None):
    """Per slot and head, softmax(q . K / sqrt(hd)) . V over the slot's own
    visible positions ([0, position], or the last ``window`` of them), in
    float64, K and V read page by page out of the slot's table row (a ring
    under a window: logical page n in slot n % ring).  ``scale`` where it is
    not ``1 / sqrt(hd)``; the values may be narrower than the keys."""
    q, kp, vp = (np.asarray(x, np.float64) for x in (q, k_pages, v_pages))
    t, h, hd = q.shape
    ps = kp.shape[2]
    rep = h // kp.shape[3]
    scale = 1 / math.sqrt(hd) if scale is None else scale
    out = np.zeros((t, h, vp.shape[-1]))
    for i in range(t):
        row = np.asarray(tables[token_seq[i]])
        hi = int(positions[i]) + 1
        lo = 0 if window is None else max(0, hi - window)
        at = np.arange(lo, hi)
        pages = row[(at // ps) % len(row)] if window else row[at // ps]
        k, v = kp[layer][pages, at % ps], vp[layer][pages, at % ps]
        for j in range(h):
            s = k[:, j // rep] @ q[i, j] * scale
            p = np.exp(s - s.max())
            out[i, j] = (p / p.sum()) @ v[:, j // rep]
    return out


def arena_and_rows(rep, dtype, seed=0):
    """Random arenas and a buffer of 12 slots: a decode row at the last
    position of the context, a row at position 0, a prefill chunk that
    crosses the first block boundary (positions 9..14), two rows that share
    their first page, and two padding slots on the all-null row."""
    rng = np.random.default_rng(seed)
    shape = (LAYERS, NUM_PAGES, PS, KVH, HD)
    k_pages = jnp.asarray(rng.normal(size=shape), dtype)
    v_pages = jnp.asarray(rng.normal(size=shape), dtype)
    rows = np.zeros((6, P), np.int32)  # row 5: the padding row
    rows[0] = np.arange(1, 1 + P)  # the whole context
    rows[1, :1] = [12]
    rows[2, :4] = [13, 14, 15, 16]
    rows[3, :2] = [17, 18]
    rows[4, :2] = [17, 19]  # shares page 17 with row 3
    token_seq = np.array([0, 1, 2, 2, 2, 2, 2, 2, 3, 4, 5, 5], np.int32)
    positions = np.array([CONTEXT - 1, 0, 9, 10, 11, 12, 13, 14, 6, 7, 0, 0], np.int32)
    q = jnp.asarray(rng.normal(size=(len(positions), KVH * rep, HD)), dtype)
    return q, k_pages, v_pages, rows, token_seq, positions


def attend(q, k_pages, v_pages, layer, tables, token_seq, positions, block_pages,
           window=None):
    return attention.paged_attention(
        q, k_pages, v_pages, layer, jnp.asarray(tables), jnp.asarray(token_seq),
        jnp.asarray(positions), block_pages, window=window)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rep", [1, 2, 4, 5, 8])
def test_matches_dense_reference(rep, dtype, tol):
    q, k_pages, v_pages, tables, token_seq, positions = arena_and_rows(rep, dtype)
    fed = token_seq < len(tables) - 1
    for layer in range(LAYERS):
        got = attend(q, k_pages, v_pages, layer, tables, token_seq, positions, BLOCK_PAGES)
        assert got.dtype == q.dtype and got.shape == q.shape
        got = np.asarray(got, np.float64)
        assert np.isfinite(got).all()  # the padding slots too
        want = dense_reference(q, k_pages, v_pages, layer, tables, token_seq, positions)
        np.testing.assert_allclose(got[fed], want[fed], atol=tol, rtol=tol)


@pytest.mark.parametrize("longest", [0, BLOCK_TOKENS - 1, BLOCK_TOKENS, 2 * BLOCK_TOKENS + 5])
def test_blocks_past_the_longest_live_row_are_not_read(longest):
    """Every table entry beyond the last block the longest row reaches
    points at a page of NaN: the result is finite and the reference's."""
    rng = np.random.default_rng(longest)
    shape = (LAYERS, NUM_PAGES, PS, KVH, HD)
    k_pages = jnp.asarray(rng.normal(size=shape), jnp.float32).at[:, NAN_PAGE].set(jnp.nan)
    v_pages = jnp.asarray(rng.normal(size=shape), jnp.float32).at[:, NAN_PAGE].set(jnp.nan)
    walked_pages = (longest // BLOCK_TOKENS + 1) * BLOCK_PAGES
    tables = np.full((5, P), NAN_PAGE, np.int32)
    tables[4] = 0  # the padding row: the null page
    for i in range(4):
        tables[i, :walked_pages] = rng.integers(1, NAN_PAGE, size=min(P, walked_pages))
    token_seq = np.arange(4, dtype=np.int32)
    positions = np.array([longest, 0, longest // 2, 0], np.int32)
    q = jnp.asarray(rng.normal(size=(4, KVH * 2, HD)), jnp.float32)
    got = np.asarray(attend(q, k_pages, v_pages, 1, tables, token_seq, positions, BLOCK_PAGES))
    assert np.isfinite(got).all()
    want = dense_reference(q, k_pages, v_pages, 1, tables, token_seq, positions)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# ------------------------------------------------------ the walk over tiles
# steps as ``ServingBackend.step`` packs them: each row's slots contiguous,
# the tail of the buffer on the padding row.  (start, slots) a row.
ROW_T, ROW_S = 32, 16
ROW_STEPS = {
    "decode-rows-only": [(5, 1), (0, 1), (43, 1), (17, 1), (30, 1)],
    "one-chunk-of-T-slots": [(7, ROW_T)],
    "15-decode-rows-and-a-chunk": [(3 * i, 1) for i in range(15)] + [(11, ROW_T - 15)],
    "two-chunks-of-unlike-size": [(0, 19), (25, 6), (40, 1)],
    "draft-rows-of-1+k-slots": [(9, 5), (33, 5), (2, 5), (21, 1), (14, 5)],
    "no-live-row-but-one": [(38, 1)],
    # window 8 over blocks of 8 positions: a tile's first slot reaches back
    # into the block before, its later slots see nothing there
    "window:a-chunk-straddles-the-windows-edge": [(15, 20), (3, 1)],
    # a ring of 11 pages (44 positions): positions 70.. sit on their second
    # lap, and a block of the chunk's walk spans the ring's seam
    "window:a-row-whose-walk-wraps-its-ring": [(70, 27), (60, 3), (39, 2)],
}


def packed(rows, t_buf, s_rows):
    token_seq = np.full((t_buf,), s_rows, np.int32)
    positions = np.zeros((t_buf,), np.int32)
    ti = 0
    for i, (start, n) in enumerate(rows):
        token_seq[ti:ti + n] = i
        positions[ti:ti + n] = np.arange(start, start + n)
        ti += n
    return token_seq, positions


@pytest.mark.parametrize("case", list(ROW_STEPS))
def test_every_fed_slot_of_a_tiled_step_matches_the_reference(case):
    rows = ROW_STEPS[case]
    window = 8 if case.startswith("window:") else None
    ps, kvh, rep, hd, bp = 4, 2, 3, 8, 2
    width = attention.window_ring_pages(window, ps, ROW_T) if window else 24
    assert not window or width == 11
    rng = np.random.default_rng(len(case))
    n_pages = 1 + ROW_S * width
    shape = (1, n_pages, ps, kvh, hd)
    k_pages, v_pages = (rng.normal(size=shape).astype(np.float32) for _ in range(2))
    tables = np.zeros((ROW_S + 1, width), np.int32)
    tables[:ROW_S] = 1 + rng.permutation(ROW_S * width).reshape(ROW_S, width)
    token_seq, positions = packed(rows, ROW_T, ROW_S)
    fed = token_seq < ROW_S
    q = rng.normal(size=(ROW_T, kvh * rep, hd)).astype(np.float32)
    got = np.asarray(attend(jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages), 0,
                            tables, token_seq, positions, bp, window=window))
    assert np.isfinite(got).all()  # a slot nobody reads is finite too
    want = dense_reference(q, k_pages, v_pages, 0, tables, token_seq, positions, window)
    np.testing.assert_allclose(got[fed], want[fed], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("t_buf,s_rows,want", [(64, 16, 24), (32, 16, 24), (12, 4, 8), (2, 8, 8)])
def test_the_tiles_bound_every_split_of_the_buffer(t_buf, s_rows, want):
    """A tile holds up to 8 slots of ONE row, so a row wastes less than one:
    T // 8 + S tiles hold any split of the buffer over the rows."""
    assert attention.attn_tiles(t_buf, s_rows) == want
    assert want % attention.ATTN_GROUP_TILES == 0
    rng = np.random.default_rng(t_buf)
    for _ in range(200):
        cuts = np.sort(rng.integers(0, t_buf + 1, size=s_rows))
        counts = np.diff(np.concatenate([[0], cuts]))
        assert sum(-(-int(n) // attention.ATTN_TILE_SLOTS) for n in counts) <= want


@pytest.mark.parametrize("window", [None, 20])
def test_one_rule_for_the_traced_bound_and_the_host_count(window):
    """``walk_blocks`` on numpy and on jax arrays alike; under a window a
    tile walks from its OLDEST slot's oldest visible key."""
    oldest, newest = np.array([100, 0, 37, 0]), np.array([130, 0, 37, 0])
    first, trips = attention.walk_blocks(oldest, newest, 16, window)
    jfirst, jtrips = jax.jit(lambda a, b: attention.walk_blocks(a, b, 16, window))(oldest, newest)
    assert list(first) == list(np.asarray(jfirst)) and int(trips) == int(jtrips)
    if window is None:
        assert list(first) == [0, 0, 0, 0] and trips == 130 // 16 + 1
    else:  # the long tile's first key is 100 - 19 = 81: blocks 5..8
        assert list(first) == [5, 0, 1, 0] and trips == 4


KV_BY_HEAD = ((8, 128), (8, 128))  # K and V of 8 heads of 128: 4096 B a position in bfloat16


@pytest.mark.parametrize("page_size,pages_per_seq,arenas,itemsize,heads,kvh,v_dim,want", [
    (16, 128, KV_BY_HEAD, 2, 32, 8, 128, 8),  # Mistral, context 2048: blocks of 128 positions
    (16, 32, KV_BY_HEAD, 2, 16, 8, 128, 4),  # InternLM2, context 512: an eighth of the table
    (16, 256, KV_BY_HEAD, 2, 32, 8, 128, 8),
    (8, 16, ((2, 16), (2, 16)), 4, 4, 2, 16, 2),  # tiny(): eight blocks of 16 positions
    (4, 4, ((2, 16), (2, 16)), 4, 4, 2, 16, 1),
    (512, 8, KV_BY_HEAD, 2, 32, 8, 128, 1),  # a page longer than a block
    # Trinity, both kinds of page (48 query heads: a tile's state is 192 KiB
    # against a block of 512 KiB), and InternLM2 at a context past the cap
    (16, 1024, KV_BY_HEAD, 2, 48, 8, 128, 8),
    (16, 2048, KV_BY_HEAD, 2, 16, 8, 128, 8),
    # the latent arena of ``axk1-docsessions-open``: 1280 B a position under
    # 4 slots x 64 heads x 512 values in float32 (512 KiB): 128 positions
    # gather 160 KiB, 256 gather 320, 512 gather 640
    (16, 2048, ((640,),), 2, 64, 1, 512, 32),
    # one K/V head of 128 under 32 query heads (512 B a position, a state of
    # 128 KiB): thin pages under fat tiles double the block once, whatever
    # the family; a short table still caps it at an eighth
    (16, 512, ((1, 128), (1, 128)), 2, 32, 1, 128, 16),
    (16, 64, ((1, 128), (1, 128)), 2, 32, 1, 128, 8),
    (8, 32, ((128,),), 4, 64, 1, 96, 4),  # the serving tests' latent config, capped
])
def test_block_follows_from_the_shapes(page_size, pages_per_seq, arenas, itemsize, heads, kvh,
                                       v_dim, want):
    pos_bytes = attention.arena_pos_bytes(arenas, itemsize)
    assert attention.attn_block_pages(page_size, pages_per_seq, pos_bytes, heads, kvh, v_dim) == want


def test_a_latent_walk_at_a_grown_block_matches_the_reference():
    """A latent arena whose rule gives blocks of 256 positions (4 pages of
    64): rows that cross several blocks, end inside one, end on a block's
    last position and are shorter than one, and a chunk across a boundary,
    against the dense float64 softmax over one shared key whose leading
    columns are the value."""
    ps, width, vd, h, per = 64, 12, 8, 64, 32
    bp = attention.attn_block_pages(ps, per, attention.arena_pos_bytes(((width,),), 4), h, 1, vd)
    assert bp == 4
    rows = [(700, 1), (37, 1), (255, 1), (256, 1), (250, 12), (0, 5)]
    rng = np.random.default_rng(31)
    arena = rng.normal(size=(1, 1 + len(rows) * per, ps, width)).astype(np.float32)
    tables = np.zeros((len(rows) + 1, per), np.int32)
    tables[:-1] = 1 + rng.permutation(len(rows) * per).reshape(len(rows), per)
    t_buf = 24
    token_seq, positions = packed(rows, t_buf, len(rows))
    fed = token_seq < len(rows)
    q = rng.normal(size=(t_buf, h, width)).astype(np.float32)
    got = np.asarray(attention.paged_attention(
        jnp.asarray(q), jnp.asarray(arena), None, 0, jnp.asarray(tables),
        jnp.asarray(token_seq), jnp.asarray(positions), bp, v_dim=vd, scale=0.3))
    assert got.shape == (t_buf, h, vd) and np.isfinite(got).all()
    want = dense_reference(q, arena[..., None, :], arena[..., None, :vd], 0, tables,
                           token_seq, positions, scale=0.3)
    np.testing.assert_allclose(got[fed], want[fed], atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------- the program
# sizes at which every array the step may hold is smaller than one of
# T x context x n_heads elements: the per-block gather is T x 24 x 2 x 8
WALK_CFG = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=8,
                             n_kv_heads=2, d_ff=128, max_seq_len=160,
                             dtype=jnp.float32)
WALK_T, WALK_S, WALK_PS, WALK_PAGES = 12, 4, 8, 24


def all_eqns(jaxpr):
    """Every equation of ``jaxpr``, bodies of loops and calls included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from all_eqns(inner)


def oversized(step):
    """Names of the equations in ``step``'s jaxpr that produce an array of
    T x context x n_heads elements or more, or gather T x context x kvh x hd."""
    cfg = WALK_CFG
    pages_per_seq = cfg.max_seq_len // WALK_PS
    params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    arena = jax.ShapeDtypeStruct(
        (cfg.n_layers, WALK_PAGES, WALK_PS, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    closed = jax.make_jaxpr(step)(
        params, arena, arena, i32(WALK_T), i32(WALK_T), i32(WALK_S + 1, pages_per_seq),
        i32(WALK_T), i32(WALK_S))
    scores = WALK_T * cfg.max_seq_len * cfg.n_heads
    row_gather = WALK_T * cfg.max_seq_len * cfg.n_kv_heads * cfg.head_dim
    bad, loops = [], 0
    for eqn in all_eqns(closed.jaxpr):
        loops += eqn.primitive.name == "while"
        for out in eqn.outvars:
            size = math.prod(getattr(out.aval, "shape", ()))
            if size >= scores or (eqn.primitive.name == "gather" and size >= row_gather):
                bad.append(f"{eqn.primitive.name} {out.aval.str_short()}")
    return bad, loops


def whole_row_step(params, k_pages, v_pages, tokens, positions, page_tables,
                   token_seq, out_idx):
    """The attention this PR replaced, as the control: each slot gathers
    its whole page-table row, K and V are repeated to all query heads."""
    def whole_row(q, k_pages, v_pages, layer, tables, token_seq, positions, block_pages):
        t = q.shape[0]
        kc = k_pages[layer][tables[token_seq]].reshape(t, -1, *k_pages.shape[3:])
        vc = v_pages[layer][tables[token_seq]].reshape(t, -1, *v_pages.shape[3:])
        return llama._attention(
            q[:, None], kc, vc, WALK_CFG, q_offset=positions[:, None])[:, 0]

    # the name ``llama.ragged_step`` calls the walk by
    real, llama.paged_attention = llama.paged_attention, whole_row
    try:
        return llama.ragged_step(params, k_pages, v_pages, tokens, positions,
                                 page_tables, token_seq, out_idx, WALK_CFG)
    finally:
        llama.paged_attention = real


def test_no_array_of_the_whole_context_in_the_program():
    bad, loops = oversized(lambda *a: llama.ragged_step(*a, WALK_CFG))
    assert not bad, bad
    # the walk was looked into: a layer's loop over groups, the blocks' inside
    # it, and (ISSUE 44: both walks are handed to the lowering) the kernel's
    # four (ISSUE 50: a run's blocks, and its tiles entering, at a block and leaving)
    assert loops == 6 * WALK_CFG.n_layers


def test_the_walk_over_the_program_sees_a_repeat_and_a_whole_row_gather():
    bad, _ = oversized(whole_row_step)
    assert any(b.startswith("gather") for b in bad), bad
    assert any("160,8,8]" in b for b in bad), bad  # K repeated to 8 heads
    assert any(b.endswith("[12,8,1,160]") for b in bad), bad  # the scores


def walk_gathers(jaxpr):
    """Result shapes of the arena gathers inside the program's loops (a
    loop inside a loop is looked into once)."""
    found = {}
    for eqn in all_eqns(jaxpr):
        if eqn.primitive.name == "while":
            for inner in all_eqns(eqn.params["body_jaxpr"].jaxpr):
                if inner.primitive.name == "gather" and inner.outvars[0].aval.ndim >= 4:
                    found[id(inner)] = tuple(inner.outvars[0].aval.shape)
    return list(found.values())


@pytest.mark.parametrize("name,block_pages", [
    # K and V by head: the blocks of 128 positions PR 25 chose, so the three
    # programs lower to the text they lowered to before the rule saw bytes
    ("mistral-7b-v0.3", 8), ("internlm2-1.8b", 4), ("trinity-large-preview-ep8", 8),
    ("a.x-k1-ep16", 32),  # the latent page: 512 positions
])
def test_the_walk_gathers_a_block_a_table_row_in_every_configuration(name, block_pages):
    """The serving program of each benchmark configuration at its cell's
    pool, traced over shapes (nothing is built or compiled): every gather of
    pages inside a walk has S rows, never T, in full and window layers, and
    is ``block_pages`` pages long — read off the program, not off the rule."""
    import importlib
    import json
    import pathlib

    from cordum_tpu.serving.backend import FeedLayout, make_ragged_program
    from cordum_tpu.serving.modelspec import spec_for

    doc = json.loads((pathlib.Path(__file__).parent.parent / "benchmarks" / "configs"
                      / f"{name}.json").read_text())
    cfg = importlib.import_module(f"benchmarks.families.{doc['family']}").program_config(doc)
    spec, pool = spec_for(cfg), doc["pool"]
    s_rows, ps = pool["max_sessions"], pool["page_size"]
    t_buf = s_rows + pool["prefill_budget"]
    ring = attention.window_ring_pages(spec.window, ps, t_buf) if spec.window else 0
    widths = (cfg.max_seq_len // ps,) + ((ring,) if ring else ())
    layout = FeedLayout(t_buf, s_rows, widths)
    params = jax.eval_shape(spec.init_params, jax.random.PRNGKey(0))
    arenas = jax.eval_shape(
        lambda: spec.init_arenas(pool["pages"], ps, s_rows * ring + 1 if ring else 0))
    program = make_ragged_program(cfg, layout, sample_logits=True, donate=False)
    closed = jax.make_jaxpr(program)(
        params, *arenas, jax.ShapeDtypeStruct((layout.size,), jnp.int32))
    shapes = walk_gathers(closed.jaxpr)
    # a group of tiles' block of each arena of a kind: never a gather a
    # buffer slot, and ONE walk traced a kind of page, whatever the number
    # of layers
    assert set(shapes) == {(attention.ATTN_GROUP_TILES, block_pages, ps, *a)
                           for kind in spec.arenas for a in kind}
    assert attention.ATTN_GROUP_TILES < t_buf and len(shapes) == spec.n_arenas
    # and the backend counts in the unit the program walks in
    from cordum_tpu.serving.backend import ServingBackend

    be = ServingBackend(cfg, num_pages=pool["pages"], page_size=ps, max_seqs=s_rows,
                        max_batch_tokens=t_buf)
    assert be.attn_block_tokens == block_pages * ps and set(be._block_tokens) == {block_pages * ps}


def test_the_walk_over_slots_would_be_seen(monkeypatch):
    """The control of the test above: with tiles of ONE slot, a group a
    buffer (the walk this one replaced), a trip gathers T rows."""
    def rows_gathered():
        cfg = WALK_CFG
        params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
        arena = jax.ShapeDtypeStruct(
            (cfg.n_layers, WALK_PAGES, WALK_PS, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        closed = jax.make_jaxpr(lambda *a: llama.ragged_step(*a, cfg))(
            params, arena, arena, i32(WALK_T), i32(WALK_T),
            i32(WALK_S + 1, cfg.max_seq_len // WALK_PS), i32(WALK_T), i32(WALK_S))
        return {s[0] for s in walk_gathers(closed.jaxpr)}

    assert rows_gathered() == {attention.ATTN_GROUP_TILES}
    monkeypatch.setattr(attention, "ATTN_TILE_SLOTS", 1)
    monkeypatch.setattr(attention, "ATTN_GROUP_TILES", WALK_T + WALK_S)
    # a fresh function under a fresh jit: the walk's trace is cached by the
    # function and its shapes, not by the constants
    walk = attention.paged_attention.__wrapped__
    monkeypatch.setattr(llama, "paged_attention",
                        jax.jit(lambda *a: walk(*a), static_argnums=(7,)))
    assert rows_gathered() == {WALK_T + WALK_S}


# ------------------------------------------------------- backend and engine
@pytest.fixture(scope="module")
def backend():
    cfg = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=128,
                            dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    # 16 pages a table, blocks of 2: 16 positions a block, 8 blocks
    return LlamaServingBackend(cfg, num_pages=64, page_size=8,
                               params_provider=lambda: params)


def test_one_program_whatever_the_live_lengths(backend):
    pages = list(range(1, 17))
    walked = []
    for start, n in [(0, 3), (30, 5), (100, 1), (127, 1), (0, 1), (60, 10)]:
        backend.step([
            StepEntry(tokens=[7] * n, start=start, pages=pages, sample=True,
                      phase="prefill"),
            StepEntry(tokens=[9], start=2, pages=[20], sample=True),
        ])
        walked.append(backend.last_attn_blocks)
    assert walked == [(1, 8), (3, 8), (7, 8), (8, 8), (1, 8), (5, 8)]
    assert backend.compiled_programs() == 1
    assert backend._ragged_jit._cache_size() == 1


@pytest.mark.parametrize("prompt_len,new", [(5, 4), (40, 30), (90, 38)])
def test_engine_counts_the_walk_and_stamps_the_step_span(backend, monkeypatch, prompt_len, new):
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.obs.tracer import Tracer
    from cordum_tpu.protocol import subjects as subj

    async def run_blocking(fn, *args):
        return await asyncio.get_running_loop().run_in_executor(None, fn, *args)

    async def main():
        bus, spans = LoopbackBus(), []

        async def on_span(subject, pkt):
            spans.append(pkt.span)

        await bus.subscribe(subj.TRACE_SPAN, on_span)
        eng = ServingEngine(backend, run_blocking=run_blocking,
                            tracer=Tracer("worker", bus))
        eng.worker_id = "w-a"
        seen, rows = [], []
        real_step = backend.step

        def step(entries):
            res = real_step(entries)
            longest = max(e.start + len(e.tokens) for e in entries)
            seen.append((-(-longest // 16), 8))
            assert backend.last_attn_blocks == seen[-1]
            # one fed row: its tiles of 8 slots by falling position, 8 a group,
            # a group to the block of its newest slot
            (e,) = entries
            ends = sorted((min(e.start + k + 8, e.start + len(e.tokens)) - 1
                           for k in range(0, len(e.tokens), 8)), reverse=True)
            trips = sum(ends[a] // 16 + 1 for a in range(0, len(ends), 8))
            rows.append((8 * trips, 64 * trips))
            assert backend.last_attn_rows == rows[-1]
            return res

        monkeypatch.setattr(backend, "step", step)
        out = await eng.submit(
            GenRequest(prompt=list(range(1, prompt_len + 1)), max_new_tokens=new,
                       stream=False), job_id=f"walk-{prompt_len}")
        await eng.stop()
        await bus.drain()
        assert len(out["tokens"]) == new
        assert eng.stats.steps == len(seen)
        assert eng.stats.attn_blocks_total == 8 * len(seen)
        assert eng.stats.attn_blocks_walked == sum(w for w, _ in seen)
        assert seen[-1][0] == -(-(prompt_len + new - 1) // 16)
        steps = [sp for sp in spans if sp.name == "step"]
        assert steps  # the first cycle is always kept
        assert eng.stats.attn_rows_gathered == sum(kv for kv, _ in rows)
        for sp in steps:
            n = int(sp.trace_id.rsplit("-", 1)[1])
            assert sp.attrs["kv_blocks"] == f"{seen[n][0]}/8"
            assert sp.attrs["kv_block_tokens"] == "16"  # the unit of those blocks
            assert (sp.attrs["kv_rows"], sp.attrs["q_rows"]) == tuple(map(str, rows[n]))

    asyncio.run(main())
