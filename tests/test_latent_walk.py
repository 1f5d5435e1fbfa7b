"""``models/latent_walk.py``: the latent walk of a group of tiles as one
Pallas TPU kernel (ISSUE 39; docs/SERVING.md §The ragged entry point).

On the CPU the step programs hold the ``jax.numpy`` walk (the kernel is
chosen where a program is lowered for the TPU), so these tests steer the
choice IN THE TEST: ``jax.lax.platform_dependent`` is made to take its
``tpu`` branch and the kernel runs in Pallas' TPU interpret mode.  Each case
holds the kernel to the ``jax.numpy`` walk over the same feed and to a plain
float32 softmax over each slot's own keys; pages a tile must not read (past
its OWN last block) are poisoned with NaN for the kernel alone, so a finite,
equal output shows the per-tile end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from cordum_tpu.models import attention, axk1, latent_walk
from cordum_tpu.serving.backend import ServingBackend, StepEntry

H, WIDTH, VD, PS, BP = 64, 256, 128, 16, 2  # tiles of 4 slots x 64 heads; blocks of 32
BT = BP * PS
SCALE = 0.11


@pytest.fixture
def kernel_walk(monkeypatch):
    """The latent form takes the kernel's branch, interpreted (``take`` names
    the branch; a test sets ``"default"`` for the walk the CPU runs); the
    jitted walk's traces of this test are dropped behind it."""
    take = ["tpu"]
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, default, tpu: {"tpu": tpu, "default": default}[take[0]](*args))
    attention.paged_attention.clear_cache()
    with pltpu.force_tpu_interpret_mode():
        yield take
    attention.paged_attention.clear_cache()


def feed_of(rows, t_buf, s_rows, p_width, n_pages, arena_rows=1, dtype=jnp.float32, seed=0):
    """``rows``: ``(depth, slots)`` a table row, packed one behind the other;
    pages are dealt in a shuffled order, the unused tail of a table row is
    the null page.  Returns the arena, the tables, ``token_seq``,
    ``positions`` and the queries."""
    rng = np.random.default_rng(seed)
    arena = rng.standard_normal((arena_rows, n_pages, PS, WIDTH)).astype(np.float32)
    arena[:, 0] = 0.0  # the null page
    free = list(rng.permutation(np.arange(1, n_pages)))
    tables = np.zeros((s_rows + 1, p_width), np.int32)
    token_seq = np.full(t_buf, s_rows, np.int32)
    positions = np.zeros(t_buf, np.int32)
    at = 0
    for r, (depth, n) in enumerate(rows):
        need = -(-(depth + n) // PS)
        tables[r, :need] = [free.pop() for _ in range(need)]
        token_seq[at:at + n] = r
        positions[at:at + n] = depth + np.arange(n)
        at += n
    q = rng.standard_normal((t_buf, H, WIDTH)).astype(np.float32)
    return (jnp.asarray(arena, dtype), jnp.asarray(tables), jnp.asarray(token_seq),
            jnp.asarray(positions), jnp.asarray(q, dtype))


def reference(arena, tables, token_seq, positions, q, row):
    """Plain float32: every fed slot's softmax over its own row's keys."""
    arena, q = np.asarray(arena, np.float32), np.asarray(q, np.float32)
    tables, token_seq, positions = (np.asarray(x) for x in (tables, token_seq, positions))
    out = np.zeros(q.shape[:2] + (VD,), np.float32)
    for t in np.flatnonzero(token_seq < tables.shape[0] - 1):
        keys = arena[row, tables[token_seq[t]]].reshape(-1, WIDTH)[:positions[t] + 1]
        s = q[t] @ keys.T * SCALE
        p = np.exp(s - s.max(-1, keepdims=True))
        out[t] = (p / p.sum(-1, keepdims=True)) @ keys[:, :VD]
    return out


def poisoned(arena, tables, token_seq, positions):
    """NaN in every page no tile may read: those past the block of a ROW's
    newest position (the null page stays sound: a last block is padded with
    it) — and, with tiles of a row ending apart, nothing more can be said by
    page; the kernel's own count is held in the test below."""
    arena = np.array(arena, np.float32)
    used = {0}
    for r in set(np.asarray(token_seq)) - {tables.shape[0] - 1}:
        newest = int(np.asarray(positions)[np.asarray(token_seq) == r].max())
        used |= set(np.asarray(tables)[r, :(newest // BT + 1) * BP].tolist())
    for n in set(range(arena.shape[1])) - used:
        arena[:, n] = np.nan
    return arena


CASES = {
    # a 48-slot chunk deep in a row beside decode rows of unlike depth: 12 + 3
    # tiles, two groups, the chunk's tiles in one with a decode row's
    "chunk-beside-decode-rows": dict(rows=[(300, 48), (200, 1), (37, 1), (5, 1)]),
    # tiles of one group that end 1, 3 and 7 blocks apart
    "tiles-end-apart": dict(rows=[(8 * BT + 3, 1), (7 * BT + 1, 1), (5 * BT, 1), (4 * BT + 9, 1),
                                  (BT - 1, 1), (0, 1)]),
    "a-row-at-depth-0": dict(rows=[(0, 9)]),
    # 40 positions = 2.5 pages of a 2-page block: the second block's tail is the null page
    "last-block-padded-with-the-null-page": dict(rows=[(BT + 7, 2), (3, 1)]),
    # one live tile, seven on the padding row
    "idle-tiles": dict(rows=[(70, 1)]),
    "one-fed-slot-of-four": dict(rows=[(90, 5)]),  # a full tile, then one slot
    # LongCat's two sublayers a layer: the same feed over row 1 of two
    "two-arena-rows": dict(rows=[(130, 6), (20, 1)], arena_rows=2, row=1),
    "bfloat16": dict(rows=[(150, 10), (64, 1), (2, 1)], dtype=jnp.bfloat16, tol=3e-2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_equals_the_jnp_walk_and_a_plain_reference(case, kernel_walk):
    spec = dict(CASES[case])
    rows, row = spec["rows"], spec.get("row", 0)
    tol = spec.get("tol", 2e-5)
    t_buf, s_rows, p_width = 64, 8, 24
    arena, tables, token_seq, positions, q = feed_of(
        rows, t_buf, s_rows, p_width, 200, spec.get("arena_rows", 1),
        spec.get("dtype", jnp.float32))
    walk = attention.paged_attention.__wrapped__
    args = (tables, token_seq, positions, BP)
    got = np.asarray(walk(q, jnp.asarray(poisoned(arena, tables, token_seq, positions), arena.dtype),
                          None, row, *args, v_dim=VD, scale=SCALE), np.float32)
    fed = np.asarray(token_seq) < s_rows
    assert got.shape == (t_buf, H, VD) and np.isfinite(got).all()
    kernel_walk[0] = "default"  # the jax.numpy walk of the same feed, as on the CPU
    want = np.asarray(walk(q, arena, None, row, *args, v_dim=VD, scale=SCALE), np.float32)
    np.testing.assert_allclose(got[fed], want[fed], atol=tol, rtol=tol)
    ref = reference(arena, tables, token_seq, positions, q, row)
    np.testing.assert_allclose(got[fed], ref[fed], atol=max(tol, 1e-4), rtol=max(tol, 1e-4))


def test_a_tile_reads_nothing_past_its_own_last_block(kernel_walk):
    """One group, tiles of one ROW ending apart: the kernel is called as the
    walk calls it, with NaN in every block past each TILE's own trips."""
    rng = np.random.default_rng(3)
    g, rows, n_pages, p_width = 8, 4 * H, 64, 16
    arena = rng.standard_normal((1, n_pages, PS, WIDTH)).astype(np.float32)
    newest = np.array([7 * BT + 5, 4 * BT, 4 * BT - 1, BT, 3, 0, 0, 0])
    live = np.array([1, 1, 1, 1, 1, 1, 0, 0], bool)
    trips = attention.tile_trips(newest, live, BT)
    assert list(trips) == [8, 5, 4, 2, 1, 1, 0, 0]
    tab = np.zeros((g, p_width), np.int32)
    for i in range(g):
        tab[i] = 1 + (np.arange(p_width) + 7 * i) % (n_pages - 1)
    # every tile its own pages' copy: poison what lies past its end
    arena = np.concatenate([arena] * g, axis=1)
    clean = arena.copy()
    for i in range(g):
        tab[i] += i * n_pages
        arena[0, tab[i, trips[i] * BP:]] = np.nan
    # the group is tiles 8..15 of a step's sixteen: its queries are read and
    # its outputs written in place, the other tiles' outputs stay
    q = rng.standard_normal((2 * g, rows, WIDTH)).astype(np.float32)
    pos = np.maximum(newest[:, None] - np.arange(4)[None, ::-1], 0)  # [tiles, slots]
    kept = np.full((2 * g, rows, VD), 7.0, np.float32)
    call = lambda a: np.asarray(latent_walk.walk_group(  # noqa: E731
        jnp.asarray(q), jnp.asarray(pos, jnp.int32), jnp.asarray(a), 0, jnp.asarray(tab),
        jnp.asarray(trips, jnp.int32), jnp.asarray(kept), g, block_pages=BP, v_dim=VD,
        scale=SCALE))
    got, want = call(arena), call(clean)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    assert (got[:g] == 7.0).all() and not got[g + 6:].any()  # idle tiles write zeros
    # tile 8 + 4 against a plain softmax over its own keys
    keys = clean[0, tab[4, :BP]].reshape(-1, WIDTH)
    s4 = np.where(np.arange(BT)[None] <= np.repeat(pos[4], H)[:, None], q[g + 4] @ keys.T * SCALE, -np.inf)
    p4 = np.exp(s4 - s4.max(-1, keepdims=True))
    np.testing.assert_allclose(got[g + 4], (p4 / p4.sum(-1, keepdims=True)) @ keys[:, :VD],
                               atol=2e-5, rtol=2e-5)


def test_the_host_counts_the_tile_trips_the_kernel_admits(kernel_walk, monkeypatch):
    """``attention.count_walk`` under the kernel's rule (``own_ends``, as the
    backend asks for it where its ``kernels`` hold a walk) against the trips
    the kernel's own loop bounds admit, summed over a real step's groups."""
    cfg = axk1.Axk1Config(
        vocab_size=96, d_model=64, n_heads=64, q_rank=32, kv_rank=96, nope_dim=4, rope_dim=2,
        v_dim=4, d_ff=128, d_expert=32, n_layers=2, n_dense_layers=1, n_experts=8,
        first_expert=0, experts_held=8, top_k=2, n_group=2, topk_group=1, max_seq_len=2048,
        dtype=jnp.float32, rope_factor=32.0, rope_original_len=64, rope_beta_fast=32.0,
        rope_beta_slow=1.0)
    be = ServingBackend(cfg, num_pages=523, page_size=8, max_seqs=5, max_batch_tokens=5 + 14,
                        params=axk1.init_params(jax.random.PRNGKey(1), cfg))
    be._ensure()
    assert be.kernels == {"walk": "", "expert": ""}  # the arenas live on the CPU
    # as a backend on the TPU reports, by the specification's own rule
    be.kernels = be.spec.kernels(latent_walk.PLATFORM, 1)
    assert be.kernels["walk"] == latent_walk.KERNEL_NAME
    bt, w = be.attn_block_tokens, attention.attn_tile_slots(cfg.n_heads)
    assert (bt, w) == (256, 4)
    admitted = []
    real = latent_walk.walk_group

    def noted(*args, **kw):
        jax.debug.callback(lambda t: admitted.append(int(t.sum())), args[5])
        return real(*args, **kw)

    monkeypatch.setattr(latent_walk, "walk_group", noted)
    per = be.pages_per_seq
    rows = [(3 * bt + 9, 14), (2 * bt - 1, 1), (bt + 5, 1), (40, 1), (0, 1)]
    entries = [StepEntry(tokens=[1 + i] * n, start=depth,
                         pages=list(range(1 + i * (per // 4), 1 + i * (per // 4) + -(-(depth + n) // 8))),
                         sample=True, draft=n - 1) for i, (depth, n) in enumerate(rows)]
    be.step(entries)
    jax.effects_barrier()
    # the chunk's four tiles end in block 3, the decode rows in 1, 1, 0, 0
    want = 4 * 4 + 2 + 2 + 1 + 1
    assert sum(admitted) == want * cfg.n_layers
    assert be.last_attn_rows == (want, w * want)
    assert be.last_attn_blocks[0] == 4
    # the group rule (the jax.numpy walk's) counts every tile to its group's longest
    spans = np.array([[0, 14], [14, 15], [15, 16], [16, 17], [17, 18]])
    positions = np.concatenate([d + np.arange(n) for d, n in rows])
    longest, ringed, gathered, live = attention.count_walk(
        spans, positions, w, (bt,), None, own_ends=(False,))
    assert (longest, ringed, gathered) == (4, 0, (8 * 4, 8 * w * 4)) and live > 0
    # and the kernel's rule is the one the step above was counted by
    assert attention.count_walk(spans, positions, w, (bt,), None, own_ends=(True,)) == (
        be.last_attn_blocks[0], 0, be.last_attn_rows, be.last_attn_live)


def test_the_rule_is_the_arenas_form_and_the_platform():
    assert latent_walk.holds_kernel("tpu", True)
    assert not latent_walk.holds_kernel("cpu", True) and not latent_walk.holds_kernel("tpu", False)
    assert latent_walk.PLATFORM == "tpu"
