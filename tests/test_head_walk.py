"""``models/head_walk.py``: the walk over K and V pages by head of a group of
tiles as one Pallas TPU kernel (ISSUE 44; docs/SERVING.md §The ragged entry
point).

On the CPU the step programs hold the ``jax.numpy`` walk (the kernel is
chosen where a program is lowered for the TPU), so these tests steer the
choice IN THE TEST, as ``tests/test_latent_walk.py`` does for the latent
form: ``jax.lax.platform_dependent`` is made to take the by-head walk's
``tpu`` branch and the kernel runs interpreted (Pallas' own interpreter: the
TPU one knows no reshaped or bitcast reference, which is how the kernel takes
a block's heads apart).  Each case holds the kernel to the ``jax.numpy`` walk
over the same feed and to a plain float32 softmax over each slot's own keys;
pages a tile must not read are poisoned with NaN for the kernel alone.  Since
ISSUE 47 the same kernel walks a window layer's RING (its second, static
form: a first block a tile, the ring's modulus where a page's copy is started,
the window's lower bound in the mask), and the same tests hold it there.  Since
ISSUE 50 the consecutive tiles of a group that sit on one table row (a RUN:
``attention.tile_runs``) share each block's copy and its taking apart, each
tile still walking its OWN blocks: the mixes below hold chunks of many tiles,
runs that decode rows split and that cross a group's end, and the host's count
of the copies (``attention.count_walk(shared=...)``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from cordum_tpu.models import afmoe, attention, head_walk, latent_walk
from cordum_tpu.serving.backend import ServingBackend, StepEntry

HD, PS, BP = 32, 4, 2  # blocks of 8 positions
BT = BP * PS
#: query heads a K/V head -> K/V heads: Falcon-H1's 5 over 4, Mistral's 4 over 8; under a window
#: Trinity's 6 (over 8 there) and Mellum's 8 (over 4)
KVH = {2: 2, 4: 8, 5: 4, 6: 4, 8: 2}
#: the longest chunk of a window case: it sets the ring's width beside the window
CHUNK = 20


@pytest.fixture
def kernel_walk(monkeypatch):
    """The by-head form takes the kernel's branch, interpreted (``take`` names
    the branch; a test sets ``"default"`` for the walk the CPU runs; every
    other choice a program makes stays the CPU's); the jitted walk's traces of
    this test are dropped behind it."""
    take = ["tpu"]

    def choose(*args, default, tpu):
        mine = tpu.__name__ == "walk_heads" and take[0] == "tpu"
        return (tpu if mine else default)(*args)

    monkeypatch.setattr(jax.lax, "platform_dependent", choose)
    monkeypatch.setattr(pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True))
    attention.paged_attention.clear_cache()
    yield take
    attention.paged_attention.clear_cache()


def feed_of(rows, rep, t_buf, s_rows, p_width, n_pages, dtype=jnp.float32, seed=0, window=None,
            chunk=CHUNK):
    """``rows``: ``(depth, slots)`` a table row, packed one behind the other;
    pages are dealt in a shuffled order, the unused tail of a table row is
    the null page.  Under a ``window`` a table row is a RING (``p_width`` is
    not read: ``attention.window_ring_pages`` wide): logical page ``n`` lies
    in slot ``n % ring``, as the row's writes left it — a page that was
    overwritten holds what overwrote it.  Returns K, V, the tables,
    ``token_seq``, ``positions`` and the queries.  ``chunk``: the longest
    chunk a window case feeds (the ring's width follows it)."""
    rng = np.random.default_rng(seed)
    kvh = KVH[rep]
    k, v = (rng.standard_normal((2, n_pages, PS, kvh, HD)).astype(np.float32) for _ in "kv")
    k[:, 0] = v[:, 0] = 0.0  # the null page
    free = list(rng.permutation(np.arange(1, n_pages)))
    if window is not None:
        p_width = attention.window_ring_pages(window, PS, chunk)
    tables = np.zeros((s_rows + 1, p_width), np.int32)
    token_seq = np.full(t_buf, s_rows, np.int32)
    positions = np.zeros(t_buf, np.int32)
    at = 0
    for r, (depth, n) in enumerate(rows):
        need = -(-(depth + n) // PS)
        held = min(need, p_width)
        tables[r, :held] = [free.pop() for _ in range(held)]
        for arena in (k, v) if need > held else ():
            # a ring that has lapped: logical page n of the row in slot n % ring
            pages = rng.standard_normal((2, need, PS, kvh, HD)).astype(np.float32)
            for page in range(need):
                arena[:, tables[r, page % p_width]] = pages[:, page]
        token_seq[at:at + n] = r
        positions[at:at + n] = depth + np.arange(n)
        at += n
    q = rng.standard_normal((t_buf, kvh * rep, HD)).astype(np.float32)
    return (jnp.asarray(k, dtype), jnp.asarray(v, dtype), jnp.asarray(tables),
            jnp.asarray(token_seq), jnp.asarray(positions), jnp.asarray(q, dtype))


def reference(k, v, tables, token_seq, positions, q, row, rep, window=None):
    """Plain float32: every fed slot's softmax over its own row's keys (under
    a ``window``: the last ``window`` of them, each read from the ring slot
    its logical page lies in), a query head over its K/V head's."""
    k, v, q = (np.asarray(x, np.float32) for x in (k, v, q))
    tables, token_seq, positions = (np.asarray(x) for x in (tables, token_seq, positions))
    out = np.zeros(q.shape, np.float32)
    for t in np.flatnonzero(token_seq < tables.shape[0] - 1):
        seen = np.arange(0 if window is None else max(0, positions[t] - window + 1), positions[t] + 1)
        at = tables[token_seq[t], (seen // PS) % tables.shape[1]], seen % PS
        keys, vals = k[row][at], v[row][at]
        for head in range(q.shape[1]):
            s = keys[:, head // rep] @ q[t, head] / np.sqrt(HD)
            p = np.exp(s - s.max())
            out[t, head] = (p / p.sum()) @ vals[:, head // rep]
    return out


def poisoned(arena, tables, token_seq, positions, window=None):
    """NaN in every page no tile may read: those past the block of a ROW's
    newest position (the null page stays sound: a last block is padded with
    it) and, under a ``window``, those before the block of the oldest key the
    row's oldest fed slot sees, the ring's slots taken round; what a TILE may
    not read of its own row is held in the test of one group below."""
    arena = np.array(arena, np.float32)
    used = {0}
    for r in set(np.asarray(token_seq)) - {tables.shape[0] - 1}:
        mine = np.asarray(positions)[np.asarray(token_seq) == r]
        first = int(attention.first_block(mine.min(), BT, window))
        walked = np.arange(first * BP, (int(mine.max()) // BT + 1) * BP)
        used |= set(np.asarray(tables)[r, walked % tables.shape[1]].tolist())
    for n in set(range(arena.shape[1])) - used:
        arena[:, n] = np.nan
    return arena


MIXES = {
    "decode-rows-alone": dict(rows=[(37, 1), (20, 1), (9, 1), (3 * BT, 1), (0, 1)]),
    # a chunk of three tiles beside decode rows of unlike depth: two groups
    "a-chunk-of-several-tiles-beside-decode-rows": dict(
        rows=[(30, 20), (45, 1), (7, 1), (18, 1), (2, 1), (33, 1), (12, 1)]),
    "a-row-of-one-position": dict(rows=[(0, 1)]),
    # 1.5 blocks: the second block's tail is the null page
    "a-last-block-partly-filled": dict(rows=[(BT + 2, 2), (3, 1)]),
    # 11 pages in blocks of 2: the table is padded to whole blocks with the null page
    "a-table-not-a-whole-number-of-blocks-wide": dict(rows=[(39, 3), (11, 1)], p_width=11),
}
#: the same walk over RINGS (blocks of 8 positions; a ring of W + 20 + 1 positions in whole pages)
RING_MIXES = {
    # every slot sees 5 keys: one block or two, wherever its position lies in its block
    "a-window-shorter-than-a-block": dict(
        window=5, rows=[(30, 20), (45, 1), (7, 1), (3, 1), (0, 1), (16, 1)]),
    # 2.5 blocks: a tile walks three or four, from its own first block
    "a-window-of-several-blocks": dict(
        window=20, rows=[(41, 1), (27, 1), (63, 1), (19, 1), (20, 1), (5, 1), (33, 3)]),
    # rows of 100 to 240 positions in rings of 44 (11 pages: no whole number of blocks): the ring
    # has lapped two to five times, and the last block of a walk wraps onto slots it read before
    "a-ring-that-has-lapped": dict(
        window=20, rows=[(100, 20), (131, 1), (207, 1), (88, 1), (239, 1), (43, 1), (44, 1)]),
    # a chunk whose oldest slots see position 0 and whose newest do not: beside decode rows
    # deeper than the window, so that tiles of one group start at unlike blocks
    "a-chunk-that-crosses-the-windows-edge": dict(
        window=20, rows=[(10, 20), (60, 1), (22, 1), (0, 3), (37, 1)]),
}
#: ISSUE 50: tiles of one table row that lie side by side in a group share a block's copy
RUN_MIXES = {
    # Mellum's prefill budget: a chunk of 28 tiles whose run two decode rows split (their positions
    # fall inside the chunk's), beside decode rows deeper and shallower: five groups, and runs
    # that end with their group three times
    "a-28-tile-chunk-that-decode-rows-split": dict(
        rows=[(30, 224), (100, 1), (181, 1), (300, 1), (12, 1), (5, 1)], t_buf=232, p_width=80,
        n_pages=400),
    # a decode row deeper than a chunk of ten tiles: the chunk's run is cut by the group's end
    # into seven tiles and three, the last of four slots
    "a-run-that-crosses-a-groups-end": dict(
        rows=[(200, 1), (10, 76)], t_buf=80, p_width=52, n_pages=200),
}
RING_RUN_MIXES = {
    # a chunk of five tiles in a ring that has lapped (17 pages: no whole number of blocks): the
    # run's tiles start at blocks 9 to 13 and end at 13 to 17, so the run's nine blocks are one
    # page more than the ring and its last block wraps onto the slot its first was read from
    "a-ring-whose-runs-tiles-start-apart-and-lap": dict(
        window=24, chunk=40, rows=[(100, 40), (131, 1), (207, 1), (43, 1)], t_buf=48),
}
RING_MIXES.update(RING_RUN_MIXES)
MIXES.update(RUN_MIXES)
MIXES.update(RING_MIXES)
CASES = [(rep, mix, "float32") for rep in (2, 4, 5, 8)
         for mix in sorted(set(MIXES) - set(RING_MIXES) - set(RUN_MIXES))] + [
    (rep, mix, "bfloat16") for rep in (4, 5)
    for mix in ("a-chunk-of-several-tiles-beside-decode-rows", "a-last-block-partly-filled")] + [
    (rep, mix, dtype) for rep, dtype in ((6, "float32"), (8, "bfloat16"))
    for mix in sorted(set(RING_MIXES) - set(RING_RUN_MIXES))] + [
    (6, "a-ring-that-has-lapped", "bfloat16"), (8, "a-chunk-that-crosses-the-windows-edge", "float32")] + [
    # ISSUE 50's runs: Mellum's 8 query heads a K/V head, Falcon-H1's 5 and Mistral's 4
    (rep, mix, dtype) for rep, dtype in ((8, "float32"), (5, "bfloat16"), (4, "float32"))
    for mix in sorted(RUN_MIXES)] + [
    (rep, mix, dtype) for rep, dtype in ((8, "bfloat16"), (6, "float32")) for mix in sorted(RING_RUN_MIXES)]


@pytest.mark.parametrize("rep,mix,dtype", CASES, ids=[f"rep{r}-{m}-{d}" for r, m, d in CASES])
def test_the_kernel_equals_the_jnp_walk_and_a_plain_reference(rep, mix, dtype, kernel_walk, monkeypatch):
    spec = MIXES[mix]
    tol = 2e-5 if dtype == "float32" else 3e-2
    t_buf, s_rows, row, window = spec.get("t_buf", 40), 8, 1, spec.get("window")
    k, v, tables, token_seq, positions, q = feed_of(
        spec["rows"], rep, t_buf, s_rows, spec.get("p_width", 14), spec.get("n_pages", 120),
        jnp.dtype(dtype), window=window, chunk=spec.get("chunk", CHUNK))
    walk = attention.paged_attention.__wrapped__
    args = (row, tables, token_seq, positions, BP, window)
    bad_k, bad_v = (jnp.asarray(poisoned(a, tables, token_seq, positions, window), a.dtype)
                    for a in (k, v))
    forms, real = [], head_walk.walk_group

    def noted(*a, **kw):
        forms.append(kw.get("window"))
        return real(*a, **kw)

    monkeypatch.setattr(head_walk, "walk_group", noted)
    got = np.asarray(walk(q, bad_k, bad_v, *args), np.float32)
    assert forms == [window]  # the kernel's form of this case, traced once
    fed = np.asarray(token_seq) < s_rows
    assert got.shape == q.shape and np.isfinite(got).all()
    kernel_walk[0] = "default"  # the jax.numpy walk of the same feed, as on the CPU
    want = np.asarray(walk(q, k, v, *args), np.float32)
    np.testing.assert_allclose(got[fed], want[fed], atol=tol, rtol=tol)
    ref = reference(k, v, tables, token_seq, positions, q, row, rep, window)
    np.testing.assert_allclose(got[fed], ref[fed], atol=max(tol, 1e-4), rtol=max(tol, 1e-4))


@pytest.mark.parametrize("block_pages", [8, 16, 64])
def test_a_block_wider_than_its_ring_laps_it(block_pages, kernel_walk):
    """A tiny model's ring (7 pages here) can be narrower than a block (8 or
    16 pages): the block's pages lap the ring once or twice (a remainder where
    each copy is started), and every slot read again is masked by its logical
    position.  A block of 64 pages is 256 positions, wider than the lanes the
    kernel keeps a tile's positions, maxima and sums on (``head_walk.LANES``)."""
    rep, window = 8, 5
    k, v, tables, token_seq, positions, q = feed_of(
        RING_MIXES["a-window-shorter-than-a-block"]["rows"], rep, 40, 8, 0, 120, window=window)
    assert tables.shape[1] == 7
    walk = attention.paged_attention.__wrapped__
    args = (1, tables, token_seq, positions, block_pages, window)
    got = np.asarray(walk(q, k, v, *args))
    kernel_walk[0] = "default"
    fed = np.asarray(token_seq) < 8
    np.testing.assert_allclose(got[fed], np.asarray(walk(q, k, v, *args))[fed], atol=2e-5, rtol=2e-5)
    ref = reference(k, v, tables, token_seq, positions, q, 1, rep, window)
    np.testing.assert_allclose(got[fed], ref[fed], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype,live,buffers,window,rows,copies", [
    ("float32", [1, 1, 1, 1, 1, 1, 0, 0], 2, None, None, None),
    ("bfloat16", [1, 1, 1, 1, 1, 1, 0, 0], 2, None, None, None),
    # the walk hands the kernel its live tiles first; the kernel itself asks for no order
    ("float32", [1, 0, 1, 1, 0, 0, 1, 1], 2, None, None, None),
    ("float32", [0, 1, 1, 1, 1, 1, 0, 1], 3, None, None, None),
    ("bfloat16", [1, 1, 1, 1, 1, 1, 0, 0], 4, None, None, None),
    # rings of 15 pages in blocks of 2 (no whole number of blocks), a window of 1.25 blocks
    ("float32", [1, 1, 1, 1, 1, 1, 0, 0], 2, 10, None, None),
    ("bfloat16", [1, 0, 1, 1, 0, 0, 1, 1], 4, 10, None, None),
    ("float32", [0, 1, 1, 1, 1, 1, 0, 1], 3, 5, None, None),
    # ISSUE 50, tiles that share a table row: runs of three, two and one tiles ending apart
    ("float32", [1, 1, 1, 1, 1, 1, 0, 0], 2, None, [0, 0, 0, 1, 1, 2, 6, 7], 8 + 2 + 1),
    ("bfloat16", [1, 1, 1, 1, 1, 1, 0, 0], 4, None, [0, 0, 0, 0, 0, 0, 6, 7], 8),
    # under a window a run's tiles start apart too; row 1's tiles are two runs that idle tiles split
    ("bfloat16", [1, 1, 1, 1, 0, 0, 1, 1], 4, 10, [0, 0, 1, 1, 4, 5, 1, 1], 6 + 4 + 3),
    ("float32", [0, 1, 1, 1, 1, 1, 0, 1], 3, 5, [0, 1, 1, 1, 2, 2, 6, 2], 5 + 1 + 2)])
def test_a_tile_reads_nothing_past_its_own_last_block(dtype, live, buffers, window, rows, copies,
                                                      kernel_walk, monkeypatch):
    """One group, tiles ending apart: the kernel is called as the walk calls
    it, with NaN in every block past each TILE's own trips, in both arenas;
    whatever the number of blocks it keeps on their way in.  Under a window
    the tiles start apart too: NaN in every ring slot that lies before a
    tile's own first block or past its own last.  Where tiles share a table
    row (``rows``: a RUN's tiles share each block's copy), NaN in every page
    of the row that none of its tiles walks: no copy is started for a block no
    tile of the run walks, and each tile's output is its own keys' softmax."""
    monkeypatch.setattr(head_walk, "BUFFERS", buffers)
    rng = np.random.default_rng(3)
    g, kvh, rep, w, n_pages = 8, 4, 5, 8, 40
    p_width = 16 if window is None else 15
    newest = np.array([7 * BT + 5, 4 * BT, 4 * BT - 1, BT, 3, 0, 2 * BT, 9])
    live = np.array(live, bool)
    rows = np.arange(g) if rows is None else np.array(rows)
    pos = np.maximum(newest[:, None] - np.arange(w)[None, ::-1], 0)  # [tiles, slots]
    first = attention.first_block(pos[:, 0], BT, window)
    # the one trips rule: neither kernel's own
    trips = attention.tile_trips(newest, live, BT, None if window is None else first)
    assert not hasattr(head_walk, "tile_trips") and not hasattr(latent_walk, "tile_trips")
    own = {None: [8, 5, 4, 2, 1, 1, 3, 2], 10: [3, 3, 3, 2, 1, 1, 3, 2], 5: [2, 3, 2, 2, 1, 1, 3, 2]}
    assert list(trips) == [t if on else 0 for t, on in zip(own[window], live)]
    # the one runs rule too: what the kernel is told of the tiles that share a copy
    runs = attention.tile_runs(rows, first, first + trips - 1, live)
    assert not hasattr(head_walk, "tile_runs")
    if copies is None:  # a table row a tile: every run is one tile, its blocks its trips
        assert (runs[:, 0] == live).all() and (runs[:, 1] == trips).all()
        assert (runs[live, 2] == first[live]).all()
    else:  # the blocks copied, a run each from its tiles' least first block to their greatest last
        assert runs[:, 1].sum() == copies < trips.sum()
        assert runs[:, 0].sum() == live.sum()  # every live tile is in one run
    tab = np.zeros((g, p_width), np.int32)
    for i in range(g):  # every table row its own pages
        tab[i] = 1 + rows[i] * n_pages + (np.arange(p_width) + 7 * rows[i]) % (n_pages - 1)
    clean = [rng.standard_normal((1, g * n_pages + 1, PS, kvh, HD)).astype(np.float32) for _ in "kv"]
    bad = [a.copy() for a in clean]
    for r in set(rows):
        walked = np.concatenate([np.arange(first[i] * BP, (first[i] + trips[i]) * BP) % p_width
                                 for i in np.flatnonzero(rows == r)])
        for a in bad:
            a[0, np.delete(tab[np.flatnonzero(rows == r)[0]], walked)] = np.nan
    # the group is tiles 8..15 of a step's sixteen: its queries are read in place
    q = rng.standard_normal((2 * g, kvh, w * rep, HD)).astype(np.float32)
    dt = jnp.dtype(dtype)
    ring = {} if window is None else dict(window=window, first_blocks=jnp.asarray(first, jnp.int32))
    call = lambda k, v: np.asarray(head_walk.walk_group(  # noqa: E731
        jnp.asarray(q, dt), jnp.asarray(pos, jnp.int32), jnp.asarray(k, dt), jnp.asarray(v, dt), 0,
        jnp.asarray(tab), jnp.asarray(trips, jnp.int32), jnp.asarray(runs, jnp.int32), g,
        block_pages=BP, scale=1 / np.sqrt(HD), **ring), np.float32)
    got, want = call(*bad), call(*clean)
    assert got.shape == (g, kvh, w * rep, HD) and np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    assert not got[~live].any() and got[live].any(axis=(1, 2, 3)).all()  # idle tiles write zeros
    # tiles 8 + 3 and 8 + 0 (the deepest: a window cuts its keys), K/V head 2, against a plain
    # softmax over each slot's own keys
    tol = 2e-5 if dtype == "float32" else 3e-2
    for i in (i for i in (3, 0) if live[i]):
        k_pos = np.arange(first[i] * BT, (first[i] + trips[i]) * BT)
        at = tab[i, (k_pos // PS) % p_width], k_pos % PS
        ki, vi = (np.asarray(jnp.asarray(a, dt), np.float32)[0][at][:, 2] for a in clean)
        qi = np.asarray(jnp.asarray(q, dt), np.float32)[g + i, 2]
        mine = np.repeat(pos[i], rep)[:, None]
        seen = (k_pos[None] <= mine) & (k_pos[None] > mine - (window or 10 ** 6))
        si = np.where(seen, qi @ ki.T / np.sqrt(HD), -np.inf)
        pi = np.exp(si - si.max(-1, keepdims=True))
        np.testing.assert_allclose(got[i, 2], (pi / pi.sum(-1, keepdims=True)) @ vi, atol=tol, rtol=tol)


@pytest.mark.parametrize("rep,dtype,window", [(5, "float32", None), (8, "bfloat16", None),
                                              (4, "float32", 10), (2, "bfloat16", None)])
def test_a_tile_that_feeds_few_slots_computes_their_rows_alone(rep, dtype, window, kernel_walk):
    """ISSUE 50: a decode row's tile feeds ONE of its eight slots, and the
    kernel computes the first product rows a K/V head that hold the fed slots
    (whole groups of ``head_walk.SUBLANES``: one slot at 5 or 8 query heads a
    K/V head, two at 4, four at 2) and not all ``slots x rep``.  The fed
    slots' outputs are bit for bit what the same tiles give when every slot
    counts as fed (all rows computed, as before), the other rows are zeros,
    and a tile that feeds more slots computes all its rows."""
    g, kvh, w, n_pages, p_width = 8, KVH[rep], 8, 40, 16
    few = -(-rep // head_walk.SUBLANES) * head_walk.SUBLANES // rep
    fed = np.array([1, few, few + 1, w, 1, 0, 3, 1])  # slots a tile feeds; tile 5 is idle
    newest = np.array([7 * BT + 5, 4 * BT, 4 * BT - 1, BT, 3, 0, 2 * BT, 9])
    rng = np.random.default_rng(5)
    live = fed > 0
    pos = newest[:, None] + np.arange(w)[None, :] - (np.maximum(fed, 1) - 1)[:, None]  # [tiles, slots]
    last = newest // BT
    first = attention.first_block(pos[:, 0], BT, window)
    trips = attention.tile_trips(newest, live, BT, None if window is None else first)
    runs = attention.tile_runs(np.arange(g), first, last, live)
    tab = 1 + (np.arange(g)[:, None] * n_pages + np.arange(p_width)[None, :]).astype(np.int32)
    dt = jnp.dtype(dtype)
    k, v = (jnp.asarray(rng.standard_normal((1, g * n_pages + 1, PS, kvh, HD)), dt) for _ in "kv")
    q = jnp.asarray(rng.standard_normal((g, kvh, w * rep, HD)), dt)
    ring = {} if window is None else dict(window=window, first_blocks=jnp.asarray(first, jnp.int32))

    def call(q_pos):
        return np.asarray(head_walk.walk_group(
            q, jnp.asarray(q_pos, jnp.int32), k, v, 0, jnp.asarray(tab), jnp.asarray(trips, jnp.int32),
            jnp.asarray(runs, jnp.int32), 0, block_pages=BP, scale=1 / np.sqrt(HD), **ring),
            np.float32).reshape(g, kvh, w, rep, HD)

    slot = np.arange(w)[None, :]
    got = call(np.where(slot < fed[:, None], pos, -1))  # as ``paged_attention`` hands them over
    want = call(pos)  # every slot counts: all rows computed
    assert np.isfinite(got).all()
    for i in range(g):
        np.testing.assert_array_equal(got[i, :, :fed[i]], want[i, :, :fed[i]])
        if fed[i] <= few:  # the slots behind the first group of sublanes: zeros nobody reads
            assert not got[i, :, few + 1:].any()
    assert got[live].any(axis=(1, 2, 3, 4)).all() and not got[5].any()


def test_the_host_counts_each_kind_of_page_as_its_program_walks_it(kernel_walk, monkeypatch):
    """A program with a window kind beside the whole-row kind: both kinds'
    walks are the kernel, each in its own form, and the host's count of each
    kind equals the trips the kernel's own loop bounds admit: a full layer's
    tiles each to their own end, a window layer's from their own first block
    to their own end.  The host's gathered count is the COPIES the kernel's
    scalars start (ISSUE 50: a block once a run of one row's tiles), and its
    computed count the tile-trips' slots, as it was."""
    cfg = afmoe.AfmoeConfig(dtype=jnp.float32, n_heads=8, n_kv_heads=2, max_seq_len=512,
                            window=32)
    be = ServingBackend(cfg, num_pages=300, page_size=PS, max_seqs=6, max_batch_tokens=6 + 20,
                        params=afmoe.init_params(jax.random.PRNGKey(1), cfg))
    be._ensure()
    assert be.kernels == {"walk": "", "ring": "", "expert": ""}  # the arenas live on the CPU
    # as a backend on the TPU reports, by the specification's own rule
    be.kernels = be.spec.kernels(head_walk.PLATFORM, 1)
    assert be.kernels["walk"] == be.kernels["ring"] == head_walk.KERNEL_NAME
    bt, wbt = be._block_tokens
    w, g = attention.attn_tile_slots(cfg.n_heads // cfg.n_kv_heads), attention.ATTN_GROUP_TILES
    assert w == 8 and bt == wbt == 64
    admitted = {None: [], cfg.window: []}  # a form of the kernel each: (tile-trips, copies) a group
    real = head_walk.walk_group

    def noted(*args, **kw):
        jax.debug.callback(
            lambda t, r, form=admitted[kw.get("window")]: form.append((int(t.sum()), int(r[:, 1].sum()))),
            args[6], args[7])
        return real(*args, **kw)

    monkeypatch.setattr(head_walk, "walk_group", noted)
    per, ring = be.pages_per_seq, be.ring_pages
    rows = [(3 * bt + 9, 20), (2 * bt - 1, 1), (bt + 5, 1), (40, 1), (0, 1)]
    entries = [StepEntry(tokens=[1 + i] * n, start=depth,
                         pages=list(range(1 + i * (per // 8), 1 + i * (per // 8) + -(-(depth + n) // PS))),
                         window_pages=list(range(1 + i * ring, 1 + (i + 1) * ring)),
                         sample=True, draft=n - 1) for i, (depth, n) in enumerate(rows)]
    be.step(entries)
    jax.effects_barrier()
    # whole rows: the chunk's three tiles end in block 3, the decode rows in 1, 1, 0, 0
    own = 3 * 4 + 2 + 2 + 1 + 1
    n_full = cfg.n_layers - len(cfg.window_layers)
    assert sum(t for t, _ in admitted[None]) == own * n_full
    # the rings, a window of 32 positions in blocks of 64: the chunk's three tiles walk blocks
    # 2 and 3, the decode row at 127 block 1 alone, the one at 69 blocks 0 and 1, the others 0
    ringed = 3 * 2 + 1 + 2 + 1 + 1
    assert sum(t for t, _ in admitted[cfg.window]) == ringed * len(cfg.window_layers)
    # the copies: the chunk's three tiles lie side by side in the walk's order, ONE run, so its
    # four whole-row blocks and its two ring blocks are copied once and not three times
    copied, ring_copied = 4 + 2 + 2 + 1 + 1, 2 + 1 + 2 + 1 + 1
    assert sum(c for _, c in admitted[None]) == copied * n_full
    assert sum(c for _, c in admitted[cfg.window]) == ring_copied * len(cfg.window_layers)
    assert be.last_attn_rows == (copied + ring_copied, w * (own + ringed))
    assert 1 - be.last_attn_rows[0] * w / be.last_attn_rows[1] == pytest.approx(12 / 29)
    assert be.last_attn_blocks[0] == 4 and be.last_window_blocks == 2
    # the group rule, every tile to its group's longest walk (one group of seven tiles here),
    # for a kind whose program holds no kernel: the flags are one a kind
    spans = np.array([[0, 20], [20, 21], [21, 22], [22, 23], [23, 24]])
    positions = np.concatenate([d + np.arange(n) for d, n in rows])
    shapes = (spans, positions, w, (bt, wbt), cfg.window)
    assert attention.count_walk(*shapes, own_ends=(False, False))[2] == (
        g * 4 + g * 2, w * (g * 4 + g * 2))
    assert attention.count_walk(*shapes, own_ends=(True, False))[2] == (
        own + g * 2, w * (own + g * 2))
    # own ends alone (the latent kernel's rule): a copy a tile-trip, as before ISSUE 50
    assert attention.count_walk(*shapes, own_ends=(True, True))[2] == (
        own + ringed, w * (own + ringed))
    # and the by-head kernel's rule is the one the step above was counted by
    assert attention.count_walk(*shapes, own_ends=(True, True), shared=(True, True)) == (
        4, 2, be.last_attn_rows, be.last_attn_live)


def test_a_run_of_one_tile_is_the_walk_it_was():
    """``attention.tile_runs``, the ONE rule of the trace and of the host:
    decode rows alone are runs of one tile, whose blocks are the tiles' own
    trips from their own first blocks (what the kernel was told before ISSUE
    50: its sequence of copies is then tile after tile), and the host gathers
    a copy a tile-trip; a chunk's tiles side by side are one run to a group's
    end; a decode row whose position falls inside the chunk splits it."""
    g, bt = attention.ATTN_GROUP_TILES, 16
    live = np.ones(12, bool)
    depth = np.array([300, 290, 200, 150, 149, 90, 60, 33, 20, 9, 3, 0])
    for window in (None, 40):
        first = attention.first_block(depth, bt, window)
        trips = attention.tile_trips(depth, live, bt, first)
        runs = attention.tile_runs(np.arange(12), first, depth // bt, live)
        assert (runs == np.stack([live, trips, first], axis=1)).all()
        spans = np.stack([np.arange(12), np.arange(12) + 1], axis=1)
        counted = attention.count_walk(spans, depth, 8, (bt,) * 2, window, (True, True), (True, True))
        assert counted[2][0] * 8 == counted[2][1]  # no tile-trip rode another tile's copy
    # a chunk of 80 slots at depth 100 (ten tiles, newest first) behind a decode row at 400 and
    # before one at 50: the first group ends in the chunk's run, a second run heads the next
    rows = np.array([1] + [0] * 10 + [2] + [3] * 4)  # the last four: idle tiles on the padding row
    newest = np.array([400] + [179 - 8 * i for i in range(10)] + [50] + [0] * 4)
    live = np.arange(16) < 12
    runs = attention.tile_runs(rows, 0 * newest, newest // bt, live)
    assert runs[:, 0].tolist() == [1, 7, 0, 0, 0, 0, 0, 0, 3, 0, 0, 1, 0, 0, 0, 0]
    assert runs[:, 1].tolist() == [26, 12, 0, 0, 0, 0, 0, 0, 8, 0, 0, 4, 0, 0, 0, 0]
    assert not runs[:, 2].any() and runs.shape == (2 * g, 3)
    # the same as jax arrays, as the trace computes it
    assert (np.asarray(attention.tile_runs(*map(jnp.asarray, (rows, 0 * newest, newest // bt, live))))
            == runs).all()
    # a decode row at 140, inside the chunk's positions, on its own table row: the run is two
    split = attention.tile_runs(np.array([0, 0, 0, 0, 0, 1, 0, 0]), np.zeros(8, int),
                                np.array([11, 10, 10, 9, 9, 8, 8, 7]), np.ones(8, bool))
    assert split[:, 0].tolist() == [5, 0, 0, 0, 0, 1, 2, 0]
    assert split[:, 1].tolist() == [12, 0, 0, 0, 0, 9, 9, 0]


def test_the_rule_is_the_arenas_form_the_platform_and_one_device():
    assert head_walk.holds_kernel("tpu", True, 0) and head_walk.holds_kernel("tpu", True, 1)
    assert not head_walk.holds_kernel("cpu", True, 1)  # another platform
    assert not head_walk.holds_kernel("tpu", True, 4)  # a mesh of more than one device
    assert not head_walk.holds_kernel("tpu", False, 1)  # a latent arena: its own kernel
    assert latent_walk.holds_kernel("tpu", True) and not latent_walk.holds_kernel("cpu", True)
    assert head_walk.PLATFORM == latent_walk.PLATFORM == "tpu"
    assert head_walk.KERNEL_NAME != latent_walk.KERNEL_NAME
    # the ONE rule the trace and the families' ``ModelSpec.kernels`` both ask
    assert attention.walk_kernel("tpu", True, None, 1) is head_walk
    assert attention.walk_kernel(None, True, None, 1) is head_walk  # the kernel's own platform
    assert attention.walk_kernel("cpu", True, None, 1) is None
    assert attention.walk_kernel("tpu", True, None, 4) is None
    # a window's ring: the by-head kernel's second form, on one device; a latent arena has none
    assert attention.walk_kernel("tpu", True, 32, 1) is head_walk
    assert attention.walk_kernel("tpu", True, 32, 4) is None is attention.walk_kernel("cpu", True, 32, 1)
    assert attention.walk_kernel("tpu", False, 32, 1) is None
    assert attention.walk_kernel("tpu", False, None, 4) is latent_walk
    assert attention.walk_label("tpu", False, 1) == {"walk": "latent_walk"}
    assert attention.walk_label("tpu", True, 1) == {"walk": "head_walk"}
    assert attention.walk_label("cpu", True, 1) == attention.walk_label("tpu", True, 2) == {"walk": ""}
    # a role a kind of page, in the kinds' order
    assert attention.WALK_ROLES == ("walk", "ring")
    assert attention.walk_label("tpu", True, 1, 32) == {"walk": "head_walk", "ring": "head_walk"}
    assert attention.walk_label("tpu", True, 4, 32) == {"walk": "", "ring": ""}
    assert attention.walk_label("tpu", False, 1, 32) == {"walk": "latent_walk", "ring": ""}


@pytest.mark.parametrize("form", ["by-head", "window", "mesh", "latent"])
def test_the_traced_program_holds_the_walk_its_form_asks_for(form):
    """What ``paged_attention`` hands to the lowering: both walks for K and V
    by head on one device (the platform chooses), whole rows and a window's
    rings alike, the ``jax.numpy`` walk alone over a mesh of more than one
    device, the latent form's own kernel for a latent arena."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rep = 2
    k, v, tables, token_seq, positions, q = feed_of([(9, 3), (4, 1)], rep, 16, 4, 6, 30)
    window = 8 if form == "window" else None
    if form == "mesh":
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
        k, v = (jax.device_put(a, NamedSharding(mesh, P(None, None, None, "tp", None))) for a in (k, v))
        q = jax.device_put(q, NamedSharding(mesh, P(None, "tp", None)))
    if form == "latent":
        k, v, q = k[:, :, :, 0], None, jnp.tile(q, (1, 16, 1))  # one shared key head, 64 query heads
    kw = dict(v_dim=HD // 2, scale=0.2) if form == "latent" else {}
    text = str(jax.make_jaxpr(
        lambda q, k, v: attention.paged_attention(q, k, v, 0, tables, token_seq, positions, BP, window, **kw)
    )(q, k, v))
    assert (head_walk.KERNEL_NAME in text) == (form in ("by-head", "window"))
    assert (latent_walk.KERNEL_NAME in text) == (form == "latent")
    assert ("platform_index" in text) == (form != "mesh")


def test_the_busy_share_reader_finds_the_kernels_events_or_nothing():
    """``benchmarks/layer_metrics/head_walk_busy_share.py`` and its entry in
    ``BENCHMARK.json``: the kernel's seconds over busy seconds, None where the
    trace holds no such event (the parent's traced run under this benchmark)."""
    from benchmarks.harness import cells

    reader = cells.load_reader("head_walk_busy_share")
    assert reader.OP_NAME == head_walk.KERNEL_NAME
    ops = [["ssd_step_f32_208_32_128_", 0.6], ["head_walk", 0.12], ["head_walk_1", 0.03]]
    assert reader.read({"trace": {"busy_s": 2.5, "device_ops": ops}}) == pytest.approx(6.0)
    assert reader.read({"trace": {"busy_s": 2.5, "device_ops": ops[:1]}}) is None
    assert reader.read({"trace": None}) is None and reader.read({}) is None
    entry = next(m for m in cells.load_benchmark()["per_layer"] if m["name"] == reader.OP_NAME + "_busy_share")
    # the cells whose traced slice holds the kernel among its ten heaviest operations: Falcon-H1's
    # (ISSUE 44) and, appended by ISSUE 46, Mellum's (two full layers to 17k positions)
    assert entry == {"name": "head_walk_busy_share", "unit": reader.UNIT, "better": reader.BETTER,
                     "source": reader.SOURCE, "layer": reader.LAYER, "moves": reader.MOVES,
                     "workloads": ["falconh1-chatbursts-open", "mellum2-idechat-open"]}
