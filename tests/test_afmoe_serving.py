"""The AFMoE family on the serving path (ISSUE 26): window and full attention
layers in one paged cache, the dropless expert layer that holds a share of
the experts, the counters, and what the family refuses.

The oracle is the benchmark's plain float32 reference
(``benchmarks/families/afmoe_reference.py``, which imports nothing of the
program); the program runs in float32 here, so its choice at every position
is held to the REFERENCE'S logits: the reference's best logit minus its
logit of the program's token is 0 up to rounding."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import afmoe_reference as ref_mod
from cordum_tpu.models import afmoe, attention, llama
from cordum_tpu.serving.backend import ServingBackend, StepEntry
from cordum_tpu.serving.engine import GenRequest, ServingEngine
from cordum_tpu.serving.modelspec import UnsupportedForModel, spec_for

GAP = 2e-3  # float32 program against float32 "highest" reference, logits of size ~1
PS = 8


def tiny(**kw):
    base = dict(vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                d_expert=32, n_layers=3, n_dense_layers=1,
                layer_types=(afmoe.SLIDING, afmoe.SLIDING, afmoe.FULL), window=32,
                n_experts=16, first_expert=0, experts_held=16, top_k=2, max_seq_len=256,
                dtype=jnp.float32)
    base.update(kw)
    return afmoe.AfmoeConfig(**base)


def doc_of(cfg):
    """The configuration-file keys the reference reads, from a program config."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "layer_types": list(cfg.layer_types), "num_dense_layers": cfg.n_dense_layers,
            "sliding_window": cfg.window, "num_experts_per_tok": cfg.top_k,
            "route_scale": cfg.route_scale, "route_norm": cfg.route_norm,
            "first_expert": cfg.first_expert, "mup_enabled": True}


def backend_for(cfg, params, *, max_seqs=4, budget=12, pages=160):
    return ServingBackend(cfg, num_pages=pages, page_size=PS, max_seqs=max_seqs,
                          max_batch_tokens=max_seqs + budget, params=params)


def block_tokens(cfg, be):
    """Positions a block of the walk holds, by the program's rule over the
    family's shapes (float32 K and V by head, both kinds alike)."""
    bt = PS * attention.attn_block_pages(PS, be.pages_per_seq, 2 * cfg.n_kv_heads * cfg.head_dim * 4,
                                     cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    assert be.attn_block_tokens == bt
    return bt


def gaps(cfg, params, seq, preds):
    """Reference's best logit minus its logit of the program's prediction
    after every position of ``seq``."""
    ref = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len)
    top, _, got = ref.logits_of(params, seq, [int(t) for t in preds])
    return top - got


class Rows:
    """Page bookkeeping for driving ``backend.step`` by hand: row ``i`` holds
    whole-row pages and a ring of the window kind."""

    def __init__(self, be, n_rows):
        per = be.pages_per_seq
        self.pages = [list(range(1 + i * per, 1 + (i + 1) * per)) for i in range(n_rows)]
        r = be.ring_pages
        self.ring = [list(range(1 + i * r, 1 + (i + 1) * r)) for i in range(n_rows)]

    def entry(self, i, tokens, start):
        return StepEntry(tokens=list(tokens), start=start, pages=self.pages[i],
                         window_pages=self.ring[i], sample=True, draft=len(tokens) - 1)


def feed(be, rows, seqs, chunks):
    """Teacher-force ``seqs`` through the paged cache: ``chunks[i]`` are the
    chunk lengths of row i's prefill; what is left decodes one token a step,
    all rows riding the same steps.  Returns each row's prediction after
    every position (draft rows return one per fed position)."""
    preds = [[] for _ in seqs]
    fed = [0] * len(seqs)
    plans = [list(c) for c in chunks]
    while any(f < len(s) for f, s in zip(fed, seqs)):
        entries, who = [], []
        for i, seq in enumerate(seqs):
            if fed[i] >= len(seq):
                continue
            n = min(plans[i].pop(0) if plans[i] else 1, len(seq) - fed[i])
            entries.append(rows.entry(i, seq[fed[i]:fed[i] + n], fed[i]))
            who.append((i, n))
        for (i, n), out in zip(who, be.step(entries)):
            preds[i].extend(out if isinstance(out, list) else [out])
            fed[i] += n
    return preds


@pytest.mark.parametrize("case", ["chunks-straddle-the-edge", "one-token-chunks-then-decode",
                                  "short-and-long-rows-in-one-step"])
def test_paged_prefill_and_decode_equal_the_reference_across_the_window(case):
    cfg = tiny()
    params = afmoe.init_params(jax.random.PRNGKey(3), cfg)
    be = backend_for(cfg, params)
    assert be.window == 32 and be.ring_pages * PS < 2 * (cfg.window + be.max_batch_tokens)
    rng = np.random.default_rng(5)
    if case == "short-and-long-rows-in-one-step":
        lens, chunks = [150, 9, 70, 33], [[6, 3, 6, 2] * 6, [3], [5] * 9, [1, 4, 4]]
    elif case == "chunks-straddle-the-edge":
        # chunks of 12 and 7 put the window's edge (32) and the ring's lap
        # inside chunks at varying offsets; the row is five windows long
        lens, chunks = [170], [[12, 7, 12, 5, 12, 12, 3, 12, 12, 9, 12, 12, 12]]
    else:
        lens, chunks = [120], [[1] * 40]
    seqs = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in lens]
    rows = Rows(be, len(seqs))
    preds = feed(be, rows, seqs, chunks)
    assert be.compiled_programs() == 1
    for seq, p in zip(seqs, preds):
        assert len(p) == len(seq)
        g = gaps(cfg, params, seq, p)
        assert g.max() < GAP, (case, float(g.max()), int(g.argmax()))


def dense_window_attention(q, k, v, window):
    """[T, h, hd] x [T, kvh, hd]: plain masked attention, one sequence."""
    t, h, hd = q.shape
    rep = h // k.shape[1]
    kk, vv = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
    s = np.einsum("qhd,khd->hqk", q, kk) / np.sqrt(hd)
    qp, kp = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (qp >= kp) & ((qp - kp < window) if window else True)
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, vv)


@pytest.mark.parametrize("window,ring_pages,block_pages", [(16, 5, 2), (32, 9, 4), (24, 9, 1)])
def test_paged_attention_with_a_window_reads_a_ring(window, ring_pages, block_pages):
    """The last token of rows of many lengths, each row's K and V laid out in
    a ring of pages (logical page n in slot n % ring)."""
    rng = np.random.default_rng(window)
    h, kvh, hd, ps = 4, 2, 8, 4
    lens = [1, 3, window, window + 1, 3 * window + 2, 90, 61]
    n_pages = 1 + len(lens) * ring_pages
    kp = np.zeros((1, n_pages, ps, kvh, hd), np.float32)
    vp = np.zeros_like(kp)
    tables = np.zeros((len(lens), ring_pages), np.int32)
    want, qs = [], []
    for i, n in enumerate(lens):
        q, k, v = (rng.standard_normal((n, x, hd)).astype(np.float32) for x in (h, kvh, kvh))
        tables[i] = 1 + i * ring_pages + np.arange(ring_pages)
        for p in range(n):  # later laps overwrite earlier ones, as serving does
            kp[0, tables[i, (p // ps) % ring_pages], p % ps] = k[p]
            vp[0, tables[i, (p // ps) % ring_pages], p % ps] = v[p]
        want.append(dense_window_attention(q, k, v, window)[-1])
        qs.append(q[-1])
    got = attention.paged_attention(
        jnp.asarray(np.stack(qs)), jnp.asarray(kp), jnp.asarray(vp), 0,
        jnp.asarray(np.concatenate([tables, tables[:1] * 0])),  # and the padding row
        jnp.arange(len(lens), dtype=jnp.int32),
        jnp.asarray([n - 1 for n in lens], jnp.int32), block_pages, window=window)
    np.testing.assert_allclose(np.asarray(got), np.stack(want), atol=2e-5)
    assert attention.window_ring_pages(window, ps, 1) <= ring_pages


@pytest.mark.parametrize("rows", [
    [(5, 1), (0, 1), (143, 1)],  # decode rows only
    [(7, 12)],  # one chunk
    [(40, 1), (99, 1), (3, 1), (60, 12)],  # decode rows and a chunk
    [(100, 7), (41, 5)],  # two chunks of unlike size
    [(90, 12)],  # a chunk whose first slot's window opens two blocks before its last slot
    [(200, 3), (17, 3)],  # draft rows
], ids=["decode", "chunk", "decode+chunk", "two-chunks", "chunk-across-blocks", "drafts"])
def test_the_host_counts_the_trips_the_program_walks(rows):
    """``last_attn_blocks`` / ``last_window_blocks`` / ``last_attn_rows``
    against the walk by hand: the rows cut into tiles of 8 slots, the tiles
    by falling newest position, 8 a group, a group from the block of the
    oldest key its tiles' oldest slots see to the block of their newest."""
    cfg = tiny()
    be = backend_for(cfg, afmoe.init_params(jax.random.PRNGKey(3), cfg))
    book = Rows(be, len(rows))
    be.step([book.entry(i, [1 + i] * n, start) for i, (start, n) in enumerate(rows)])
    bt = block_tokens(cfg, be)
    w, g = attention.ATTN_TILE_SLOTS, attention.ATTN_GROUP_TILES
    tiles = sorted(((s + k, min(s + k + w, s + n) - 1) for s, n in rows for k in range(0, n, w)),
                   key=lambda tile: -tile[1])
    assert len(tiles) <= attention.attn_tiles(be.max_batch_tokens, be.max_seqs)
    full = [max(hi // bt for _, hi in tiles[a:a + g]) + 1 for a in range(0, len(tiles), g)]
    ring = [max(hi // bt - max(lo - cfg.window + 1, 0) // bt for lo, hi in tiles[a:a + g]) + 1
            for a in range(0, len(tiles), g)]
    assert be.last_attn_blocks == (max(full), be.pages_per_seq * PS // bt)
    assert be.last_window_blocks == max(ring)
    assert be.last_attn_rows == (g * sum(full + ring), g * w * sum(full + ring))
    # the step's longest walk is its longest row's, whatever the tiles
    assert be.last_attn_blocks[0] == max(s + n - 1 for s, n in rows) // bt + 1


def reference_expert_part(cfg, layer, m, first, held):
    """Shared expert + the held experts' weighted terms, by the reference."""
    sel, w = ref_mod.route(m, layer["router"], layer["router_bias"], top_k=cfg.top_k,
                           route_scale=cfg.route_scale, route_norm=cfg.route_norm)
    out = ref_mod._swiglu(m, layer["s_gate"], layer["s_up"], layer["s_down"], False)
    for e in range(first, first + held):
        out = out + ref_mod.expert_term(m, sel, w, e, layer["e_gate"][e], layer["e_up"][e],
                                        layer["e_down"][e])
    return out, sel


def share_of(layer, first, held):
    cut = {k: layer[k][first:first + held] for k in ("e_gate", "e_up", "e_down")}
    return {**layer, **cut}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each chip's routed part, plus the shared expert counted once, is the
    whole layer of the uncut reference; a share alone equals the reference
    given the same share."""
    cfg = tiny()
    layer = afmoe.init_params(jax.random.PRNGKey(11), cfg)["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(12), (40, cfg.d_model), jnp.float32)
    live = jnp.ones((40,), bool)
    whole, _ = reference_expert_part(cfg, layer, m, 0, cfg.n_experts)
    shared = ref_mod._swiglu(m, layer["s_gate"], layer["s_up"], layer["s_down"], False)
    total, seen = shared, 0
    for rank in range(8):
        c = dataclasses.replace(cfg, first_expert=2 * rank, experts_held=2)
        part, counts = afmoe.expert_layer(m, share_of(layer, 2 * rank, 2), c, live)
        alone, _ = reference_expert_part(cfg, layer, m, 2 * rank, 2)
        np.testing.assert_allclose(part, alone, atol=1e-4)
        total = total + (part - shared)
        seen += int(counts.sum())
    np.testing.assert_allclose(total, whole, atol=2e-4)
    assert seen == 40 * cfg.top_k  # every assignment was some chip's


@pytest.mark.parametrize("held", [16, 4])
def test_no_token_is_dropped_when_every_token_picks_the_same_experts(held):
    cfg = tiny(experts_held=held)
    layer = dict(afmoe.init_params(jax.random.PRNGKey(2), cfg)["layers"][1])
    bias = np.zeros((cfg.n_experts,), np.float32)
    bias[[1, 3]] = 10.0  # the selection bias sends every token to experts 1 and 3
    layer["router_bias"] = jnp.asarray(bias)
    m = jax.random.normal(jax.random.PRNGKey(4), (48, cfg.d_model), jnp.float32)
    live = jnp.asarray([True] * 45 + [False] * 3)  # padding slots route nowhere
    part, counts = afmoe.expert_layer(m, layer, cfg, live)
    assert counts.tolist() == [45 if e in (1, 3) else 0 for e in range(held)]
    want, sel = reference_expert_part(cfg, layer, m, 0, held)
    assert set(np.asarray(sel).ravel().tolist()) == {1, 3}
    np.testing.assert_allclose(part[:45], want[:45], atol=1e-4)


async def run_blocking(fn, *args):
    return await asyncio.get_running_loop().run_in_executor(None, fn, *args)


async def serve(eng, prompts, n_new):
    outs = await asyncio.wait_for(asyncio.gather(*(
        eng.submit(GenRequest(prompt=p, max_new_tokens=n, stream=False), job_id=f"j{i}")
        for i, (p, n) in enumerate(zip(prompts, n_new)))), timeout=240)
    return [o["tokens"] for o in outs]


@pytest.mark.parametrize("held", [16, 6])
async def test_engine_serves_mixed_rows_bounded_and_counted(held):
    """Through the engine: short and long rows share steps; the window kind's
    pages per session never pass the ring; both allocators stay consistent;
    every counter equals a recount from what each step fed and returned."""
    cfg = tiny(experts_held=held, first_expert=4 if held < 16 else 0)
    params = afmoe.init_params(jax.random.PRNGKey(7), cfg)
    be = backend_for(cfg, params, max_seqs=3, budget=9, pages=100)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=3, max_new_tokens_cap=64)
    assert eng.prefix is None and eng.tiering is None  # sharing is off for this family
    seen = []  # per step: (live tokens, counts, window pages per row, blocks)
    seen_rows = []  # per step: table rows the walks gathered
    inner = be.step

    def tapped(entries):
        out = inner(entries)
        seen.append((sum(len(e.tokens) for e in entries), be.last_aux.copy(),
                     [len(e.window_pages) for e in entries], be.last_window_blocks))
        seen_rows.append(be.last_attn_rows[0])
        return out
    be.step = tapped
    rng = np.random.default_rng(held)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in (140, 6, 61, 20)]
    n_new = [40, 12, 30, 8]
    outs = await serve(eng, prompts, n_new)
    await eng.stop()
    for p, o in zip(prompts, outs):
        seq = p + o
        g = gaps(cfg, params, seq[:-1], seq[1:])[len(p) - 1:]
        assert g.max() < GAP, float(g.max())
    st, ring = eng.stats, be.ring_pages
    assert max(n for _, _, rows, _ in seen for n in rows) <= ring
    assert st.kv_pages_held_window <= 3 * ring < st.kv_pages_held_full
    assert st.window_pages_reused == sum(
        max(0, -(-(len(p) + n - 1) // PS) - ring) for p, n in zip(prompts, n_new))
    eng.allocator.check_consistency()
    eng.window_allocator.check_consistency()
    assert eng.allocator.used_pages == 0 and eng.window_allocator.used_pages == 0
    layers = cfg.n_expert_layers
    assert st.model["moe_assignments"] == sum(t for t, _, _, _ in seen) * cfg.top_k * layers
    assert st.model["moe_assignments_here"] == sum(int(c.sum()) for _, c, _, _ in seen)
    assert st.model["moe_experts_touched"] == sum(int((c > 0).sum()) for _, c, _, _ in seen)
    assert st.model["moe_max_expert_load"] == sum(int(c.max(axis=1).sum()) for _, c, _, _ in seen)
    assert st.window_blocks_walked == sum(b for _, _, _, b in seen)
    if held == 16:
        assert st.model["moe_assignments_here"] == st.model["moe_assignments"]  # all experts are here
    else:
        assert 0 < st.model["moe_assignments_here"] < st.model["moe_assignments"]
    # the window layers' walk is bounded by the window and a step's buffer,
    # the full layer's is not
    bt = block_tokens(cfg, be)
    assert max(b for _, _, _, b in seen) <= (cfg.window + be.max_batch_tokens) // bt + 2
    assert st.attn_blocks_walked > st.window_blocks_walked
    assert st.attn_rows_gathered == sum(seen_rows) > 0


async def test_what_the_family_cannot_do_is_refused():
    cfg = tiny()
    spec = spec_for(cfg)
    assert spec.window == 32 and not spec.kv_whole_row and spec.count_aux is not None
    # the rings' walk has a role of its own through the seam: the by-head kernel on one device
    assert spec.kernels("cpu", 1) == {"walk": "", "ring": "", "expert": ""}
    assert {r: spec.kernels("tpu", 1)[r] for r in attention.WALK_ROLES} == {
        "walk": "head_walk", "ring": "head_walk"}
    assert {spec.kernels("tpu", 4)[r] for r in attention.WALK_ROLES} == {""}
    with pytest.raises(TypeError):
        spec_for(object())  # a config that exports no specification
    be = backend_for(cfg, None)
    for call in (lambda: be.copy_page(1, 2), lambda: be.export_kv([1], 0, 8),
                 lambda: be.import_kv([1], [{}])):
        with pytest.raises(UnsupportedForModel):
            call()
    with pytest.raises(ValueError, match="window_pages"):  # a ring-less entry is not served
        be.step([StepEntry(tokens=[1], start=0, pages=[1])])
    from cordum_tpu.serving.shard import ShardedServingBackend

    with pytest.raises(UnsupportedForModel):
        ShardedServingBackend(cfg, rank=0, tp=2)
    eng = ServingEngine(be, run_blocking=run_blocking, hibernate_after_s=30.0)
    assert eng.prefix is None and eng.tiering is None and not eng.kv_whole_row
    live = asyncio.ensure_future(eng.submit(
        GenRequest(prompt=[1, 2, 3], max_new_tokens=40, stream=False), job_id="live"))
    while eng.active_sessions() == 0:
        await asyncio.sleep(0.01)
    assert eng.describe_session("live") is None  # never offered for migration
    assert eng.pick_rebalance_sessions(4) == []
    with pytest.raises(UnsupportedForModel):
        await eng.hibernate_session("live")
    with pytest.raises(UnsupportedForModel):
        await eng.export_pages("live", 0, 8)
    with pytest.raises(UnsupportedForModel):
        await eng.install_session(GenRequest(prompt=[1]), job_id="x", state={}, records=[])
    assert len((await asyncio.wait_for(live, timeout=120))["tokens"]) == 40
    await eng.stop()


def test_the_llama_program_is_built_through_the_seam_unchanged():
    """The llama family is the first specification: two arenas, one table,
    no counters behind the tokens, every sharing feature allowed."""
    spec = spec_for(llama.LlamaConfig.tiny())
    assert (spec.family, spec.n_arenas, spec.aux_shape, spec.window) == ("llama", 2, (), None)
    assert spec.count_aux is None
    be = ServingBackend(num_pages=16, page_size=8)
    assert be.ring_pages == 0 and be.num_window_pages == 0 and be.kv_whole_row
    be.step([StepEntry(tokens=[3, 4], start=0, pages=[1, 2])])
    assert be._k_pages is be._arenas[0] and be.last_aux is None and be.last_window_blocks == 0
    assert be.last_counters == {}
    be.release_arenas()
    assert be._k_pages is None and be._params is not None
