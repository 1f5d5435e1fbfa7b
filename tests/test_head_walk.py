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
pages a tile must not read are poisoned with NaN for the kernel alone."""
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
#: query heads a K/V head -> K/V heads: Falcon-H1's 5 over 4, Mistral's 4 over 8
KVH = {2: 2, 4: 8, 5: 4, 8: 2}


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


def feed_of(rows, rep, t_buf, s_rows, p_width, n_pages, dtype=jnp.float32, seed=0):
    """``rows``: ``(depth, slots)`` a table row, packed one behind the other;
    pages are dealt in a shuffled order, the unused tail of a table row is
    the null page.  Returns K, V, the tables, ``token_seq``, ``positions`` and
    the queries."""
    rng = np.random.default_rng(seed)
    kvh = KVH[rep]
    k, v = (rng.standard_normal((2, n_pages, PS, kvh, HD)).astype(np.float32) for _ in "kv")
    k[:, 0] = v[:, 0] = 0.0  # the null page
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
    q = rng.standard_normal((t_buf, kvh * rep, HD)).astype(np.float32)
    return (jnp.asarray(k, dtype), jnp.asarray(v, dtype), jnp.asarray(tables),
            jnp.asarray(token_seq), jnp.asarray(positions), jnp.asarray(q, dtype))


def reference(k, v, tables, token_seq, positions, q, row, rep):
    """Plain float32: every fed slot's softmax over its own row's keys, a
    query head over its K/V head's."""
    k, v, q = (np.asarray(x, np.float32) for x in (k, v, q))
    tables, token_seq, positions = (np.asarray(x) for x in (tables, token_seq, positions))
    out = np.zeros(q.shape, np.float32)
    for t in np.flatnonzero(token_seq < tables.shape[0] - 1):
        keys = k[row, tables[token_seq[t]]].reshape(-1, *k.shape[3:])[:positions[t] + 1]
        vals = v[row, tables[token_seq[t]]].reshape(-1, *v.shape[3:])[:positions[t] + 1]
        for head in range(q.shape[1]):
            s = keys[:, head // rep] @ q[t, head] / np.sqrt(HD)
            p = np.exp(s - s.max())
            out[t, head] = (p / p.sum()) @ vals[:, head // rep]
    return out


def poisoned(arena, tables, token_seq, positions):
    """NaN in every page no tile may read: those past the block of a ROW's
    newest position (the null page stays sound: a last block is padded with
    it); what a TILE may not read of its own row is held in the test of one
    group below."""
    arena = np.array(arena, np.float32)
    used = {0}
    for r in set(np.asarray(token_seq)) - {tables.shape[0] - 1}:
        newest = int(np.asarray(positions)[np.asarray(token_seq) == r].max())
        used |= set(np.asarray(tables)[r, :(newest // BT + 1) * BP].tolist())
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
CASES = [(rep, mix, "float32") for rep in sorted(KVH) for mix in sorted(MIXES)] + [
    (rep, mix, "bfloat16") for rep in (4, 5)
    for mix in ("a-chunk-of-several-tiles-beside-decode-rows", "a-last-block-partly-filled")]


@pytest.mark.parametrize("rep,mix,dtype", CASES, ids=[f"rep{r}-{m}-{d}" for r, m, d in CASES])
def test_the_kernel_equals_the_jnp_walk_and_a_plain_reference(rep, mix, dtype, kernel_walk):
    spec = MIXES[mix]
    tol = 2e-5 if dtype == "float32" else 3e-2
    t_buf, s_rows, row = 40, 8, 1
    k, v, tables, token_seq, positions, q = feed_of(
        spec["rows"], rep, t_buf, s_rows, spec.get("p_width", 14), 120, jnp.dtype(dtype))
    walk = attention.paged_attention.__wrapped__
    args = (row, tables, token_seq, positions, BP)
    bad_k, bad_v = (jnp.asarray(poisoned(a, tables, token_seq, positions), a.dtype) for a in (k, v))
    got = np.asarray(walk(q, bad_k, bad_v, *args), np.float32)
    fed = np.asarray(token_seq) < s_rows
    assert got.shape == q.shape and np.isfinite(got).all()
    kernel_walk[0] = "default"  # the jax.numpy walk of the same feed, as on the CPU
    want = np.asarray(walk(q, k, v, *args), np.float32)
    np.testing.assert_allclose(got[fed], want[fed], atol=tol, rtol=tol)
    ref = reference(k, v, tables, token_seq, positions, q, row, rep)
    np.testing.assert_allclose(got[fed], ref[fed], atol=max(tol, 1e-4), rtol=max(tol, 1e-4))


@pytest.mark.parametrize("dtype,live,buffers", [
    ("float32", [1, 1, 1, 1, 1, 1, 0, 0], 2), ("bfloat16", [1, 1, 1, 1, 1, 1, 0, 0], 2),
    # the walk hands the kernel its live tiles first; the kernel itself asks for no order
    ("float32", [1, 0, 1, 1, 0, 0, 1, 1], 2), ("float32", [0, 1, 1, 1, 1, 1, 0, 1], 3),
    ("bfloat16", [1, 1, 1, 1, 1, 1, 0, 0], 4)])
def test_a_tile_reads_nothing_past_its_own_last_block(dtype, live, buffers, kernel_walk, monkeypatch):
    """One group, tiles ending apart: the kernel is called as the walk calls
    it, with NaN in every block past each TILE's own trips, in both arenas;
    whatever the number of blocks it keeps on their way in."""
    monkeypatch.setattr(head_walk, "BUFFERS", buffers)
    rng = np.random.default_rng(3)
    g, kvh, rep, w, n_pages, p_width = 8, 4, 5, 8, 40, 16
    newest = np.array([7 * BT + 5, 4 * BT, 4 * BT - 1, BT, 3, 0, 2 * BT, 9])
    live = np.array(live, bool)
    trips = attention.tile_trips(newest, live, BT)  # the one trips rule: neither kernel's own
    assert not hasattr(head_walk, "tile_trips") and not hasattr(latent_walk, "tile_trips")
    assert list(trips) == [t if on else 0 for t, on in zip([8, 5, 4, 2, 1, 1, 3, 2], live)]
    tab = np.zeros((g, p_width), np.int32)
    for i in range(g):  # every tile its own pages
        tab[i] = 1 + i * n_pages + (np.arange(p_width) + 7 * i) % (n_pages - 1)
    clean = [rng.standard_normal((1, g * n_pages + 1, PS, kvh, HD)).astype(np.float32) for _ in "kv"]
    bad = [a.copy() for a in clean]
    for i in range(g):
        for a in bad:
            a[0, tab[i, trips[i] * BP:]] = np.nan
    # the group is tiles 8..15 of a step's sixteen: its queries are read in place
    q = rng.standard_normal((2 * g, kvh, w * rep, HD)).astype(np.float32)
    pos = np.maximum(newest[:, None] - np.arange(w)[None, ::-1], 0)  # [tiles, slots]
    dt = jnp.dtype(dtype)
    call = lambda k, v: np.asarray(head_walk.walk_group(  # noqa: E731
        jnp.asarray(q, dt), jnp.asarray(pos, jnp.int32), jnp.asarray(k, dt), jnp.asarray(v, dt), 0,
        jnp.asarray(tab), jnp.asarray(trips, jnp.int32), g, block_pages=BP,
        scale=1 / np.sqrt(HD)), np.float32)
    got, want = call(*bad), call(*clean)
    assert got.shape == (g, kvh, w * rep, HD) and np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    assert not got[~live].any() and got[live].any(axis=(1, 2, 3)).all()  # idle tiles write zeros
    # tile 8 + 3, K/V head 2, against a plain softmax over its own keys
    k3, v3 = (np.asarray(jnp.asarray(a, dt), np.float32)[0, tab[3, :2 * BP]].reshape(2 * BT, kvh, HD)[:, 2]
              for a in clean)
    q3 = np.asarray(jnp.asarray(q, dt), np.float32)[g + 3, 2]
    s3 = np.where(np.arange(2 * BT)[None] <= np.repeat(pos[3], rep)[:, None],
                  q3 @ k3.T / np.sqrt(HD), -np.inf)
    p3 = np.exp(s3 - s3.max(-1, keepdims=True))
    tol = 2e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(got[3, 2], (p3 / p3.sum(-1, keepdims=True)) @ v3, atol=tol, rtol=tol)


def test_the_host_counts_each_kind_of_page_as_its_program_walks_it(kernel_walk, monkeypatch):
    """A program with a window kind beside the whole-row kind: the full
    layers' walk is the kernel (each tile to its own end: the host's count
    equals the trips the kernel's own loop bounds admit), the window layers'
    rings keep the ``jax.numpy`` walk and are counted by its group rule."""
    cfg = afmoe.AfmoeConfig(dtype=jnp.float32, n_heads=8, n_kv_heads=2, max_seq_len=512,
                            window=32)
    be = ServingBackend(cfg, num_pages=300, page_size=PS, max_seqs=6, max_batch_tokens=6 + 20,
                        params=afmoe.init_params(jax.random.PRNGKey(1), cfg))
    be._ensure()
    assert be.kernels == {"walk": "", "expert": ""}  # the arenas live on the CPU
    # as a backend on the TPU reports, by the specification's own rule
    be.kernels = be.spec.kernels(head_walk.PLATFORM, 1)
    assert be.kernels["walk"] == head_walk.KERNEL_NAME
    bt, wbt = be._block_tokens
    w, g = attention.attn_tile_slots(cfg.n_heads // cfg.n_kv_heads), attention.ATTN_GROUP_TILES
    assert w == 8 and bt == wbt == 64
    admitted = []
    real = head_walk.walk_group

    def noted(*args, **kw):
        jax.debug.callback(lambda t: admitted.append(int(t.sum())), args[6])
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
    full_layers = cfg.n_layers - len(cfg.window_layers)
    assert sum(admitted) == own * full_layers
    # the rings: one group of seven tiles, every tile to the group's longest walk
    # (a window of 32 positions in blocks of 64: one or two blocks a tile)
    spans = np.array([[0, 20], [20, 21], [21, 22], [22, 23], [23, 24]])
    positions = np.concatenate([d + np.arange(n) for d, n in rows])
    lo = np.array([0, 8, 16, 20, 21, 22, 23])
    hi = np.array([8, 16, 20, 21, 22, 23, 24])
    order = attention.walk_order(positions[hi - 1], np.ones(7, bool))
    ringed = g * int(attention.walk_blocks(positions[lo][order], positions[hi - 1][order], wbt,
                                       cfg.window)[1])
    assert be.last_attn_rows == (own + ringed, w * (own + ringed))
    assert be.last_attn_blocks[0] == 4 and be.last_window_blocks == ringed // g
    # the group rule for both kinds where the program holds no kernel
    shapes = (spans, positions, w, (bt, wbt), cfg.window)
    assert attention.count_walk(*shapes, own_ends=False)[2] == (
        g * 4 + ringed, w * (g * 4 + ringed))
    # and the kernel's rule is the one the step above was counted by
    assert attention.count_walk(*shapes, own_ends=True) == (
        4, ringed // g, be.last_attn_rows, be.last_attn_live)


def test_the_rule_is_the_arenas_form_the_platform_and_one_device():
    assert head_walk.holds_kernel("tpu", True, None, 0) and head_walk.holds_kernel("tpu", True, None, 1)
    assert not head_walk.holds_kernel("tpu", True, 32, 1)  # a window's ring
    assert not head_walk.holds_kernel("cpu", True, None, 1)  # another platform
    assert not head_walk.holds_kernel("tpu", True, None, 4)  # a mesh of more than one device
    assert not head_walk.holds_kernel("tpu", False, None, 1)  # a latent arena: its own kernel
    assert latent_walk.holds_kernel("tpu", True) and not latent_walk.holds_kernel("cpu", True)
    assert head_walk.PLATFORM == latent_walk.PLATFORM == "tpu"
    assert head_walk.KERNEL_NAME != latent_walk.KERNEL_NAME
    # the ONE rule the trace and the families' ``ModelSpec.kernels`` both ask
    assert attention.walk_kernel("tpu", True, None, 1) is head_walk
    assert attention.walk_kernel(None, True, None, 1) is head_walk  # the kernel's own platform
    assert attention.walk_kernel("cpu", True, None, 1) is None
    assert attention.walk_kernel("tpu", True, None, 4) is None
    assert attention.walk_kernel("tpu", True, 32, 1) is None is attention.walk_kernel("tpu", False, 32, 1)
    assert attention.walk_kernel("tpu", False, None, 4) is latent_walk
    assert attention.walk_label("tpu", False, 1) == {"walk": "latent_walk"}
    assert attention.walk_label("tpu", True, 1) == {"walk": "head_walk"}
    assert attention.walk_label("cpu", True, 1) == attention.walk_label("tpu", True, 2) == {"walk": ""}


@pytest.mark.parametrize("form", ["by-head", "window", "mesh", "latent"])
def test_the_traced_program_holds_the_walk_its_form_asks_for(form):
    """What ``paged_attention`` hands to the lowering: both walks for K and V
    by head on one device (the platform chooses), the ``jax.numpy`` walk alone
    under a window or over a mesh of more than one device, the latent form's
    own kernel for a latent arena."""
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
    assert (head_walk.KERNEL_NAME in text) == (form == "by-head")
    assert (latent_walk.KERNEL_NAME in text) == (form == "latent")
    assert ("platform_index" in text) == (form in ("by-head", "latent"))


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
