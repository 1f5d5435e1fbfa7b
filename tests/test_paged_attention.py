"""``llama.paged_attention``: the ragged step's attention (docs/SERVING.md
§The ragged entry point) — grouped-query products over blocks of pages, an
online softmax, and a walk that ends at the step's longest live row — held
to a plain dense per-row reference, and the counter the serving engine
keeps of the walk."""
import asyncio
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cordum_tpu.models import llama
from cordum_tpu.serving.backend import LlamaServingBackend, StepEntry
from cordum_tpu.serving.engine import GenRequest, ServingEngine

# a table of 11 pages of 4 positions walked 3 pages at a time: four blocks
# of 12 positions, the last one padded with the null page
PS, P, BLOCK_PAGES, KVH, HD, LAYERS = 4, 11, 3, 2, 16, 2
CONTEXT = P * PS
BLOCK_TOKENS = BLOCK_PAGES * PS
NAN_PAGE = 39
NUM_PAGES = 40


def dense_reference(q, k_pages, v_pages, layer, tables, positions):
    """Per slot and head, softmax(q . K / sqrt(hd)) . V over the slot's own
    positions [0, position], in float64, K and V read page by page."""
    q, kp, vp = (np.asarray(x, np.float64) for x in (q, k_pages, v_pages))
    t, h, hd = q.shape
    rep = h // kp.shape[3]
    out = np.zeros((t, h, hd))
    for i in range(t):
        n = int(positions[i]) + 1
        pages = np.asarray(tables[i])[: -(-n // PS)]
        k = kp[layer][pages].reshape(-1, kp.shape[3], hd)[:n]
        v = vp[layer][pages].reshape(-1, kp.shape[3], hd)[:n]
        for j in range(h):
            s = k[:, j // rep] @ q[i, j] / math.sqrt(hd)
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
    return q, k_pages, v_pages, rows[token_seq], positions


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rep", [1, 2, 4, 8])
def test_matches_dense_reference(rep, dtype, tol):
    q, k_pages, v_pages, tables, positions = arena_and_rows(rep, dtype)
    for layer in range(LAYERS):
        got = llama.paged_attention(
            q, k_pages, v_pages, layer, jnp.asarray(tables), jnp.asarray(positions),
            BLOCK_PAGES)
        assert got.dtype == q.dtype and got.shape == q.shape
        want = dense_reference(q, k_pages, v_pages, layer, tables, positions)
        np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol, rtol=tol)


@pytest.mark.parametrize("longest", [0, BLOCK_TOKENS - 1, BLOCK_TOKENS, 2 * BLOCK_TOKENS + 5])
def test_blocks_past_the_longest_live_row_are_not_read(longest):
    """Every table entry beyond the last block the longest row reaches
    points at a page of NaN: the result is finite and the reference's."""
    rng = np.random.default_rng(longest)
    shape = (LAYERS, NUM_PAGES, PS, KVH, HD)
    k_pages = jnp.asarray(rng.normal(size=shape), jnp.float32).at[:, NAN_PAGE].set(jnp.nan)
    v_pages = jnp.asarray(rng.normal(size=shape), jnp.float32).at[:, NAN_PAGE].set(jnp.nan)
    walked_pages = (longest // BLOCK_TOKENS + 1) * BLOCK_PAGES
    tables = np.full((4, P), NAN_PAGE, np.int32)
    for i in range(4):
        tables[i, :walked_pages] = rng.integers(1, NAN_PAGE, size=min(P, walked_pages))
    positions = np.array([longest, 0, longest // 2, 0], np.int32)
    q = jnp.asarray(rng.normal(size=(4, KVH * 2, HD)), jnp.float32)
    got = np.asarray(llama.paged_attention(
        q, k_pages, v_pages, 1, jnp.asarray(tables), jnp.asarray(positions), BLOCK_PAGES))
    assert np.isfinite(got).all()
    want = dense_reference(q, k_pages, v_pages, 1, tables, positions)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("page_size,pages_per_seq,want", [
    (16, 128, 8),  # context 2048: blocks of 128 positions
    (16, 32, 4),  # context 512: an eighth of the table
    (16, 256, 8),
    (8, 16, 2),  # tiny(): eight blocks of 16 positions
    (4, 4, 1),
    (512, 8, 1),  # a page longer than a block
])
def test_block_follows_from_the_shapes(page_size, pages_per_seq, want):
    assert llama.attn_block_pages(page_size, pages_per_seq) == want


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
    def whole_row(q, k_pages, v_pages, layer, tables, positions, block_pages):
        t = q.shape[0]
        kc = k_pages[layer][tables].reshape(t, -1, *k_pages.shape[3:])
        vc = v_pages[layer][tables].reshape(t, -1, *v_pages.shape[3:])
        return llama._attention(
            q[:, None], kc, vc, WALK_CFG, q_offset=positions[:, None])[:, 0]

    real, llama.paged_attention = llama.paged_attention, whole_row
    try:
        return llama.ragged_step(params, k_pages, v_pages, tokens, positions,
                                 page_tables, token_seq, out_idx, WALK_CFG)
    finally:
        llama.paged_attention = real


def test_no_array_of_the_whole_context_in_the_program():
    bad, loops = oversized(lambda *a: llama.ragged_step(*a, WALK_CFG))
    assert not bad, bad
    assert loops == WALK_CFG.n_layers  # the walk was looked into, once a layer


def test_the_walk_over_the_program_sees_a_repeat_and_a_whole_row_gather():
    bad, _ = oversized(whole_row_step)
    assert any(b.startswith("gather") for b in bad), bad
    assert any("160,8,8]" in b for b in bad), bad  # K repeated to 8 heads
    assert any(b.endswith("[12,8,1,160]") for b in bad), bad  # the scores


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
        seen = []
        real_step = backend.step

        def step(entries):
            res = real_step(entries)
            longest = max(e.start + len(e.tokens) for e in entries)
            seen.append((-(-longest // 16), 8))
            assert backend.last_attn_blocks == seen[-1]
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
        for sp in steps:
            n = int(sp.trace_id.rsplit("-", 1)[1])
            assert sp.attrs["kv_blocks"] == f"{seen[n][0]}/8"

    asyncio.run(main())
