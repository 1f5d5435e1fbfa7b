"""The A.X-K1 family on the serving path (ISSUE 30): a latent (MLA) paged
cache walked in absorbed form, the group-limited router over a share of the
experts, later turns served from prefix-cache pages, the counters, and what
a latent page refuses.

The oracle is the benchmark's plain float32 reference in the PUBLISHED form
(``benchmarks/families/axk1_reference.py``: K and V by head, never absorbed;
it imports nothing of the program); the program runs in float32 here, so its
choice at every position is held to the REFERENCE'S logits: the reference's
best logit minus its logit of the program's token is 0 up to rounding."""
import asyncio
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import axk1_reference as ref_mod
from cordum_tpu.models import afmoe, attention, axk1, llama
from cordum_tpu.serving.backend import ServingBackend, StepEntry
from cordum_tpu.serving.engine import GenRequest, ServingEngine
from cordum_tpu.serving.modelspec import UnsupportedForModel, spec_for

GAP = 2e-3  # float32 program against float32 "highest" reference, logits of size ~1
PS = 8
YARN = dict(rope_factor=32.0, rope_original_len=64, rope_beta_fast=32.0, rope_beta_slow=1.0)


def tiny(**kw):
    base = dict(vocab_size=96, d_model=64, n_heads=4, q_rank=32, kv_rank=32, nope_dim=16,
                rope_dim=8, v_dim=16, d_ff=128, d_expert=32, n_layers=3, n_dense_layers=1,
                n_experts=16, first_expert=0, experts_held=16, top_k=4, n_group=4, topk_group=2,
                max_seq_len=256, dtype=jnp.float32, **YARN)
    base.update(kw)
    return axk1.Axk1Config(**base)


def doc_of(cfg):
    """The configuration-file keys the reference reads, from a program config."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "q_lora_rank": cfg.q_rank, "kv_lora_rank": cfg.kv_rank,
            "qk_nope_head_dim": cfg.nope_dim, "qk_rope_head_dim": cfg.rope_dim,
            "v_head_dim": cfg.v_dim, "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "rope_scaling": {"type": "yarn", "factor": cfg.rope_factor,
                             "original_max_position_embeddings": cfg.rope_original_len,
                             "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
                             "mscale": cfg.rope_mscale, "mscale_all_dim": cfg.rope_mscale_all_dim},
            "first_k_dense_replace": cfg.n_dense_layers, "num_experts_per_tok": cfg.top_k,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "routed_scaling_factor": cfg.route_scale, "norm_topk_prob": cfg.route_norm,
            "first_expert": cfg.first_expert}


def backend_for(cfg, params, *, max_seqs=4, budget=12, pages=160):
    return ServingBackend(cfg, num_pages=pages, page_size=PS, max_seqs=max_seqs,
                          max_batch_tokens=max_seqs + budget, params=params)


def gaps(cfg, params, seq, preds):
    """Reference's best logit minus its logit of the program's prediction
    after every position of ``seq``."""
    ref = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len)
    top, _, got = ref.logits_of(params, seq, [int(t) for t in preds])
    return top - got


def entry(be, i, tokens, start):
    per = be.pages_per_seq
    return StepEntry(tokens=list(tokens), start=start,
                     pages=list(range(1 + i * per, 1 + (i + 1) * per)),
                     sample=True, draft=len(tokens) - 1)


def feed(be, seqs, chunks):
    """Teacher-force ``seqs`` through the latent pages: ``chunks[i]`` are the
    chunk lengths of row i's prefill; what is left decodes one token a step,
    all rows riding the same steps.  Returns each row's prediction after
    every position."""
    preds = [[] for _ in seqs]
    fed = [0] * len(seqs)
    plans = [list(c) for c in chunks]
    while any(f < len(s) for f, s in zip(fed, seqs)):
        entries, who = [], []
        for i, seq in enumerate(seqs):
            if fed[i] >= len(seq):
                continue
            n = min(plans[i].pop(0) if plans[i] else 1, len(seq) - fed[i])
            entries.append(entry(be, i, seq[fed[i]:fed[i] + n], fed[i]))
            who.append((i, n))
        for (i, n), out in zip(who, be.step(entries)):
            preds[i].extend(out if isinstance(out, list) else [out])
            fed[i] += n
    return preds


@pytest.mark.parametrize("case", ["chunks-straddle-pages", "one-token-chunks-then-decode",
                                  "short-and-long-rows-in-one-step", "narrow-tiles",
                                  "a-grown-block"])
def test_paged_prefill_and_decode_equal_the_published_reference(case):
    """Chunked prefill then decode through the latent pages, absorbed, equals
    the reference's full forward in the published form."""
    cfg, pages = tiny(), 160
    rng = np.random.default_rng(5)
    if case == "short-and-long-rows-in-one-step":
        lens, chunks = [150, 9, 70, 33], [[6, 3, 6, 2] * 6, [3], [5] * 9, [1, 4, 4]]
    elif case == "chunks-straddle-pages":
        lens, chunks = [170], [[12, 7, 12, 5, 12, 12, 3, 12, 12, 9, 12, 12, 12]]
    elif case == "narrow-tiles":
        # 64 query heads over the one key head: tiles of 4 slots, not 8
        cfg = tiny(n_heads=64, nope_dim=4, rope_dim=2, v_dim=4)
        assert attention.attn_tile_slots(cfg.n_heads // cfg.n_kv_heads) == 4
        lens, chunks = [90, 30, 11], [[6, 7, 5, 6, 6] * 3, [5, 6], [4, 3, 4]]
    elif case == "a-grown-block":
        # 512 B a position under a tile of 4 slots x 64 heads x 96 values
        # (96 KiB of float32): the walk's block doubles to 256 positions.
        # One row crosses it, one stays inside the first
        cfg = tiny(n_heads=64, kv_rank=96, nope_dim=4, rope_dim=2, v_dim=4, max_seq_len=2048)
        lens, chunks, pages = [290, 20], [[12] * 23, [4] * 5], 520
    else:
        lens, chunks = [120], [[1] * 40]
    params = axk1.init_params(jax.random.PRNGKey(3), cfg)
    be = backend_for(cfg, params, pages=pages)
    assert be.attn_block_tokens == (256 if case == "a-grown-block" else 32)
    seqs = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in lens]
    preds = feed(be, seqs, chunks)
    assert be.compiled_programs() == 1
    # the cache keeps (c | kr) a token and layer, and nothing by head
    assert [a.shape for a in be._arenas] == [(cfg.n_layers, pages, PS, cfg.latent_width)]
    assert (cfg.latent_dim, cfg.latent_width) == (cfg.kv_rank + cfg.rope_dim, 128)  # zeros to the tile
    for seq, p in zip(seqs, preds):
        assert len(p) == len(seq)
        g = gaps(cfg, params, seq, p)
        assert g.max() < GAP, (case, float(g.max()), int(g.argmax()))
    if case == "a-grown-block":
        # the host counts that walk in the program's unit: both rows' last
        # positions again, two tiles in one group of 8 walked to the longer
        # row's second block
        be.step([entry(be, i, seq[-1:], len(seq) - 1) for i, seq in enumerate(seqs)])
        w, g = attention.attn_tile_slots(cfg.n_heads), attention.ATTN_GROUP_TILES
        assert be.last_attn_blocks == (2, 2048 // 256)
        assert be.last_attn_rows == (g * 2, g * w * 2) and be.last_attn_live == 2 + 1


def test_absorbed_equals_published_at_float32():
    """One layer's attention over one sequence: the walk over (c | kr) with
    the query and the output folded through Wkvb, against K and V by head."""
    rng = np.random.default_rng(0)
    t, h, nope, rd, vd, rank, ps = 45, 4, 16, 8, 16, 32, 4
    scale = 0.2
    q_nope, q_rope = rng.standard_normal((t, h, nope)), rng.standard_normal((t, h, rd))
    c, kr = rng.standard_normal((t, rank)), rng.standard_normal((t, rd))
    wkvb = rng.standard_normal((rank, h, nope + vd)) / math.sqrt(rank)
    # published: expand every key to K and V by head
    kv = np.einsum("tc,chn->thn", c, wkvb)
    k = np.concatenate([kv[..., :nope], np.broadcast_to(kr[:, None], (t, h, rd))], -1)
    s = np.einsum("qhd,khd->hqk", np.concatenate([q_nope, q_rope], -1), k) * scale
    s = np.where(np.tril(np.ones((t, t), bool))[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hqk,khv->qhv", p / p.sum(-1, keepdims=True), kv[..., nope:])
    # absorbed: one shared key head, a key's leading columns its value
    pages = -(-t // ps)
    arena = np.zeros((1, 1 + pages, ps, rank + rd), np.float32)
    arena[0, 1:].reshape(-1, rank + rd)[:t] = np.concatenate([c, kr], -1)
    ql = np.einsum("thn,chn->thc", q_nope, wkvb[..., :nope])
    tables = np.zeros((2, 16), np.int32)
    tables[0, :pages] = 1 + np.arange(pages)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    ol = attention.paged_attention(
        f32(np.concatenate([ql, q_rope], -1)), f32(arena), None, 0, jnp.asarray(tables),
        jnp.zeros((t,), jnp.int32), jnp.arange(t, dtype=jnp.int32), 2, v_dim=rank, scale=scale)
    assert ol.shape == (t, h, rank)
    got = np.einsum("thc,chv->thv", np.asarray(ol), wkvb[..., nope:])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_yarn_frequencies_equal_a_hand_count():
    """The published rope_scaling over 64 rotated dimensions: dimension 3
    turns 223 times over the original context and keeps its frequency,
    dimension 30 turns 0.12 times and is interpolated, 16 lies on the ramp
    between the correction dimensions 10 and 23."""
    cfg = tiny(rope_dim=64, rope_factor=32.0, rope_original_len=4096)
    inv = np.asarray(axk1.yarn_inv_freq(cfg))
    assert inv.shape == (32,)
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4))) == 23
    np.testing.assert_allclose(inv[3], 10000.0 ** (-6 / 64), rtol=1e-6)
    np.testing.assert_allclose(inv[30], 10000.0 ** (-60 / 64) / 32, rtol=1e-6)
    ramp = (16 - 10) / (23 - 10)
    base = 10000.0 ** (-32 / 64)
    np.testing.assert_allclose(inv[16], base / 32 * ramp + base * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(inv, ref_mod.yarn_inv_freq(64, 1e4, doc_of(cfg)["rope_scaling"]),
                               rtol=1e-6)
    # the softmax scale carries mscale_all_dim squared
    full = tiny(nope_dim=128, rope_dim=64, rope_factor=32.0)
    assert abs(full.softmax_scale - 0.13086) < 1e-5
    assert tiny(rope_factor=1.0).softmax_scale == (16 + 8) ** -0.5


# ----------------------------------------------------------------- the router
def old_route(m, layer, cfg):
    """``afmoe.route`` as it was before it knew groups (PR 26), verbatim."""
    scores = jax.nn.sigmoid(jnp.matmul(
        m.astype(jnp.float32), layer["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(scores + layer["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, sel, axis=1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel, w * cfg.route_scale


def test_one_group_is_the_router_it_was_bit_for_bit():
    cfg = afmoe.AfmoeConfig()
    assert (cfg.n_group, cfg.topk_group) == (1, 1)
    layer = afmoe.init_params(jax.random.PRNGKey(1), cfg)["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.d_model), jnp.float32)
    sel, w = afmoe.route(m, layer, cfg)
    sel0, w0 = old_route(m, layer, cfg)
    assert np.array_equal(sel, sel0) and np.array_equal(w, w0)
    same = lambda f: str(jax.make_jaxpr(lambda x: f(x, layer, cfg))(m))  # noqa: E731
    assert same(afmoe.route) == same(old_route)


def test_the_group_limit_holds_and_matches_the_reference():
    """No token's experts span more than ``topk_group`` groups; they are the
    best ``top_k`` of the kept groups, the groups the best by the sum of
    their two best; the reference picks the same."""
    cfg = tiny(n_experts=48, experts_held=48, n_group=8, topk_group=3, top_k=6)
    layer = axk1.init_params(jax.random.PRNGKey(4), cfg)["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(5), (200, cfg.d_model), jnp.float32)
    sel, w = (np.asarray(x) for x in afmoe.route(m, layer, cfg))
    scores = np.asarray(jax.nn.sigmoid(jnp.matmul(m, layer["router"],
                                                  precision=jax.lax.Precision.HIGHEST)))
    per = cfg.n_experts // cfg.n_group
    for t in range(m.shape[0]):
        groups = set((sel[t] // per).tolist())
        assert len(groups) <= cfg.topk_group
        by_group = np.sort(scores[t].reshape(cfg.n_group, per), axis=1)[:, -2:].sum(1)
        kept = set(np.argsort(-by_group)[:cfg.topk_group].tolist())
        assert groups <= kept
        allowed = [e for e in range(cfg.n_experts) if e // per in kept]
        best = sorted(allowed, key=lambda e: -scores[t, e])[:cfg.top_k]
        assert sorted(sel[t].tolist()) == sorted(best)
        np.testing.assert_allclose(w[t].sum(), cfg.route_scale, rtol=1e-5)
    # an unlimited router would have crossed the limit on this input
    free, _ = afmoe.route(m, layer, dataclasses.replace(cfg, n_group=1, topk_group=1))
    assert max(len(set((np.asarray(free)[t] // per).tolist())) for t in range(200)) > cfg.topk_group
    rsel, rw = ref_mod.route(m, layer["router"], top_k=cfg.top_k, n_group=cfg.n_group,
                             topk_group=cfg.topk_group, route_scale=cfg.route_scale,
                             route_norm=cfg.route_norm)
    assert np.array_equal(np.sort(sel, 1), np.sort(np.asarray(rsel), 1))
    np.testing.assert_allclose(np.sort(w, 1), np.sort(np.asarray(rw), 1), rtol=1e-5)
    with pytest.raises(ValueError):
        tiny(n_experts=16, n_group=3)  # groups must be equal
    with pytest.raises(ValueError):
        tiny(n_experts=16, n_group=4, topk_group=1, top_k=6)  # one kept group has four experts


def sorted_group_route(m, layer, cfg):
    """``afmoe.route`` under ``n_group`` > 1 as it was before ISSUE 48,
    verbatim: a sort of every group for its two best, a ``top_k`` over the
    groups' sums, the kept set scattered into a mask."""
    logits = jnp.matmul(m.astype(jnp.float32), layer["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if cfg.route_score == "softmax"
              else jax.nn.sigmoid(logits))
    pick = scores + layer["router_bias"] if "router_bias" in layer else scores
    t, n = pick.shape
    by_group = pick.reshape(t, cfg.n_group, n // cfg.n_group)
    best2, _ = jax.lax.top_k(by_group, 2)
    _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), cfg.topk_group)
    keep = jnp.zeros((t, cfg.n_group), bool).at[jnp.arange(t)[:, None], kept].set(True)
    pick = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(t, n)
    _, sel = jax.lax.top_k(pick, cfg.top_k)
    w = jnp.take_along_axis(scores, sel, axis=1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel, w * cfg.route_scale


def router_logits(kind, t, n, seed):
    """``[t, n]`` float32 logits: ``random`` normal; ``ties`` from four
    values, so equal scores inside a group and equal sums between groups are
    the rule; ``flat`` one value a row (a padding row scores every expert
    alike), a zero row among them."""
    rng = np.random.default_rng(seed)
    if kind == "random":
        return rng.standard_normal((t, n)).astype(np.float32)
    if kind == "ties":
        return rng.choice(np.float32([-1.0, -0.5, 0.5, 1.0]), size=(t, n))
    rows = rng.standard_normal((t, 1)).astype(np.float32)
    rows[0] = 0.0
    return np.broadcast_to(rows, (t, n)).copy()


@pytest.mark.parametrize("bias", ["bias", "no_bias"])
@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
@pytest.mark.parametrize("kind", ["random", "ties", "flat"])
@pytest.mark.parametrize("n_group,per,topk_group,top_k",
                         [(4, 4, 2, 4), (8, 24, 4, 8), (8, 64, 4, 8)])
def test_the_unsorted_group_choice_is_the_sorted_one_element_for_element(
        n_group, per, topk_group, top_k, kind, score, bias):
    """ISSUE 48: two maximum passes and a rank by comparison pick the experts
    and weights the sort and the scatter picked, ties included (``top_k``'s
    order: larger first, the lower index first among equals).  The router is
    the identity, so the logits are the input's own numbers."""
    n = n_group * per
    cfg = dataclasses.replace(tiny(n_experts=n, experts_held=n, n_group=n_group,
                                   topk_group=topk_group, top_k=top_k), route_score=score)
    layer = {"router": jnp.eye(n, dtype=jnp.float32)}
    if bias == "bias":  # a few values too, so that a bias does not break every tie
        layer["router_bias"] = jnp.asarray(
            np.random.default_rng(7).choice(np.float32([0.0, 0.125, 0.25]), size=n))
    m = jnp.asarray(router_logits(kind, 160, n, seed=n + top_k))
    sel, w = jax.jit(lambda x: afmoe.route(x, layer, cfg))(m)
    sel0, w0 = jax.jit(lambda x: sorted_group_route(x, layer, cfg))(m)
    assert np.array_equal(sel, sel0) and np.array_equal(w, w0)
    if kind != "random":  # the input did make ties: in a group's two best and between sums
        pick = np.asarray(jax.nn.sigmoid(m) if score == "sigmoid" else jax.nn.softmax(m, axis=-1))
        pick = (pick + np.asarray(layer.get("router_bias", 0.0))).reshape(-1, n_group, per)
        two = np.sort(pick, axis=-1)[..., -2:]
        sums = two.sum(-1)
        assert (two[..., 0] == two[..., 1]).any()
        assert any(len(set(row.tolist())) < n_group for row in sums)


def reference_expert_part(cfg, layer, m, first, held):
    """Shared expert + the held experts' weighted terms, by the reference."""
    sel, w = ref_mod.route(m, layer["router"], top_k=cfg.top_k, n_group=cfg.n_group,
                           topk_group=cfg.topk_group, route_scale=cfg.route_scale,
                           route_norm=cfg.route_norm)
    out = ref_mod._swiglu(m, layer["s_gate"], layer["s_up"], layer["s_down"], False)
    for e in range(first, first + held):
        out = out + ref_mod.expert_term(m, sel, w, e, layer["e_gate"][e], layer["e_up"][e],
                                        layer["e_down"][e])
    return out


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Each chip's routed part (two chips a routing group), plus the shared
    expert counted once, is the whole layer of the uncut reference; a share
    alone equals the reference given the same share."""
    cfg = tiny(n_experts=32, experts_held=32, n_group=8, topk_group=4, top_k=8)
    layer = axk1.init_params(jax.random.PRNGKey(11), cfg)["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(12), (40, cfg.d_model), jnp.float32)
    live = jnp.ones((40,), bool)
    whole = reference_expert_part(cfg, layer, m, 0, cfg.n_experts)
    shared = ref_mod._swiglu(m, layer["s_gate"], layer["s_up"], layer["s_down"], False)
    total, seen = shared, 0
    for rank in range(16):
        c = dataclasses.replace(cfg, first_expert=2 * rank, experts_held=2)
        cut = {k: layer[k][2 * rank:2 * rank + 2] for k in ("e_gate", "e_up", "e_down")}
        part, counts = afmoe.expert_layer(m, {**layer, **cut}, c, live)
        np.testing.assert_allclose(part, reference_expert_part(cfg, layer, m, 2 * rank, 2),
                                   atol=1e-4)
        total = total + (part - shared)
        seen += int(counts.sum())
    np.testing.assert_allclose(total, whole, atol=2e-4)
    assert seen == 40 * cfg.top_k  # every assignment was some chip's


# ------------------------------------------------- the engine, the prefix cache
async def run_blocking(fn, *args):
    return await asyncio.get_running_loop().run_in_executor(None, fn, *args)


async def ask(eng, prompt, n, job_id, key="conv"):
    out = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=list(prompt), max_new_tokens=n, stream=False, session_key=key),
        job_id=job_id), timeout=240)
    return out["tokens"]


def held_to_reference(cfg, params, prompt, out):
    seq = list(prompt) + list(out)
    g = gaps(cfg, params, seq[:-1], seq[1:])[len(prompt) - 1:]
    assert g.max() < GAP, float(g.max())


async def test_later_turns_are_served_from_prefix_pages_and_equal_the_full_forward():
    """Three turns of one conversation: each later turn is the whole history
    plus new tokens, maps the finished turn's full latent pages (generated
    tokens included) and prefills only what is new; every turn's tokens are
    held to the reference's full forward over the whole history."""
    cfg = tiny(experts_held=6, first_expert=4)
    params = axk1.init_params(jax.random.PRNGKey(7), cfg)
    be = backend_for(cfg, params, max_seqs=3, budget=9, pages=120)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=3, max_new_tokens_cap=64)
    assert eng.prefix is not None and eng.tiering is None  # shares, cannot hibernate
    rng = np.random.default_rng(1)
    draw = lambda n: [int(t) for t in rng.integers(1, cfg.vocab_size, n)]  # noqa: E731
    history, fed_by_turn = draw(37), []
    for turn, (n_new, n_out) in enumerate([(0, 11), (21, 9), (14, 6)]):
        history = history + draw(n_new)
        before = eng.stats.prefill_tokens
        out = await ask(eng, history, n_out, f"t{turn}")
        fed_by_turn.append(eng.stats.prefill_tokens - before)
        held_to_reference(cfg, params, history, out)
        history = history + out
    st = eng.stats
    assert st.prefix_hits == 2 and st.prefix_misses == 1
    # turn 1 found positions [0, 37 + 11 - 1) written: 5 full pages of 8
    # (the last generated token is never fed); turn 2 found 37 + 11 + 21 + 9 - 1
    assert st.prefix_hit_tokens == 40 + 72
    assert fed_by_turn == [37, 37 + 11 + 21 - 40, 78 + 14 - 72]
    assert st.cow_copies == 0  # a hit ends on a page boundary BELOW the new tokens
    # a second conversation with the same opening shares its pages too
    other = history[:24] + draw(9)
    held_to_reference(cfg, params, other, await ask(eng, other, 5, "o", key="other"))
    assert eng.stats.prefix_hits == 3 and eng.stats.prefix_hit_tokens == 40 + 72 + 24
    await eng.stop()
    eng.allocator.check_consistency()


async def test_a_hit_that_ends_on_the_prompts_end_copies_the_latent_page():
    """The same prompt again, a whole number of pages long: the hit covers
    all of it, so the last token is fed again INTO a shared page; the engine
    copies that latent page first (copy-on-write over the one arena) and both
    conversations' tokens equal the reference."""
    cfg = tiny()
    params = axk1.init_params(jax.random.PRNGKey(9), cfg)
    be = backend_for(cfg, params, max_seqs=3, budget=9, pages=120)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=3, max_new_tokens_cap=64)
    rng = np.random.default_rng(2)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 4 * PS)]
    first = await ask(eng, prompt + [5], 10, "a", key="a")  # registers the prompt's 4 pages
    arena_before = np.asarray(be._arenas[0])
    again = await ask(eng, prompt, 10, "b", key="b")
    st = eng.stats
    assert st.prefix_hits == 1 and st.prefix_hit_tokens == 4 * PS - 1
    assert st.cow_copies == 1
    held_to_reference(cfg, params, prompt + [5], first)
    held_to_reference(cfg, params, prompt, again)
    # the shared pages were not written: the cache's copy of them is as it was
    node_pages = [n.page for n in eng.prefix.match(prompt)]
    assert len(node_pages) == 4
    after = np.asarray(be._arenas[0])
    np.testing.assert_array_equal(after[:, node_pages], arena_before[:, node_pages])
    await eng.stop()
    eng.allocator.check_consistency()


async def test_the_counters_equal_a_host_recount():
    """Mixed rows through the engine with a prefix hit among them: the
    walk's computed and live query slots, the bytes behind the rows, the
    prefix cache's tokens and the expert layer's four, each against a
    recount from what every step fed and returned."""
    cfg = tiny(experts_held=6, first_expert=4)
    params = axk1.init_params(jax.random.PRNGKey(7), cfg)
    be = backend_for(cfg, params, max_seqs=3, budget=9, pages=120)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=3, max_new_tokens_cap=64)
    seen = []
    inner = be.step

    def tapped(entries):
        out = inner(entries)
        seen.append(([(e.start, len(e.tokens)) for e in entries], be.last_aux.copy(),
                     be.last_attn_rows, be.last_attn_live))
        return out
    be.step = tapped
    rng = np.random.default_rng(3)
    draw = lambda n: [int(t) for t in rng.integers(1, cfg.vocab_size, n)]  # noqa: E731
    p0 = draw(60)
    out0 = await ask(eng, p0, 8, "first", key="k0")
    outs = await asyncio.gather(ask(eng, p0 + out0 + draw(30), 12, "second", key="k0"),
                                ask(eng, draw(6), 20, "short", key="k1"),
                                ask(eng, draw(100), 5, "long", key="k2"))
    assert [len(o) for o in outs] == [12, 20, 5]
    await eng.stop()
    st = eng.stats
    assert st.prefix_hits == 1 and st.prefix_hit_tokens == (60 + 8 - 1) // PS * PS == 64
    assert st.prefill_tokens == 60 + (98 - 64) + 6 + 100
    bt = PS * attention.attn_block_pages(PS, be.pages_per_seq, cfg.latent_width * 4,
                                     cfg.n_heads, 1, cfg.kv_rank)
    assert be.attn_block_tokens == bt
    w, g = attention.attn_tile_slots(cfg.n_heads), attention.ATTN_GROUP_TILES
    computed = live = held = 0
    for rows, _, _, _ in seen:
        tiles = sorted((min(s + k + w, s + n) - 1 for s, n in rows for k in range(0, n, w)),
                       reverse=True)
        computed += sum(g * w * (max(tiles[a:a + g]) // bt + 1) for a in range(0, len(tiles), g))
        live += sum(p // bt + 1 for s, n in rows for p in range(s, s + n))
        held += sum(-(-(s + n) // PS) for s, n in rows)
    assert st.attn_slots_computed == computed == sum(r[1] for _, _, r, _ in seen)
    assert st.attn_slots_live == live == sum(x for _, _, _, x in seen)
    assert 0 < live < computed
    assert be.page_bytes == cfg.n_layers * PS * cfg.latent_width * 4
    assert st.kv_bytes_behind_rows == held * be.page_bytes
    layers = cfg.n_expert_layers
    assert st.model["moe_assignments"] == sum(n for rows, _, _, _ in seen for _, n in rows) * cfg.top_k * layers
    assert st.model["moe_assignments_here"] == sum(int(c.sum()) for _, c, _, _ in seen)
    assert st.model["moe_experts_touched"] == sum(int((c > 0).sum()) for _, c, _, _ in seen)
    assert 0 < st.model["moe_assignments_here"] < st.model["moe_assignments"]


async def test_what_cannot_carry_a_latent_page_refuses():
    cfg = tiny()
    spec = spec_for(cfg)
    assert spec.window is None and spec.kv_whole_row and not spec.kv_by_head
    assert (spec.family, spec.n_arenas, spec.arenas) == ("axk1", 1, (((128,),),))
    llama_spec = spec_for(llama.LlamaConfig.tiny())
    assert llama_spec.kv_by_head and llama_spec.arenas == (((2, 16), (2, 16)),)
    assert spec_for(afmoe.AfmoeConfig()).n_arenas == 4 and spec_for(afmoe.AfmoeConfig()).kv_by_head
    be = backend_for(cfg, None)
    be.step([StepEntry(tokens=[3, 4], start=0, pages=[1, 2])])
    assert be._k_pages is be._arenas[0] and len(be._arenas) == 1
    be.copy_page(1, 2)  # copying needs no record: it maps over the kind's one arena
    np.testing.assert_array_equal(np.asarray(be._arenas[0][:, 2]), np.asarray(be._arenas[0][:, 1]))
    for call in (lambda: be.export_kv([1], 0, 8), lambda: be.import_kv([1], [{}])):
        with pytest.raises(UnsupportedForModel, match="K and V records by head"):
            call()
    from cordum_tpu.serving.shard import ShardedServingBackend

    with pytest.raises(UnsupportedForModel):
        ShardedServingBackend(cfg, rank=0, tp=2)
    eng = ServingEngine(be, run_blocking=run_blocking, hibernate_after_s=30.0)
    assert eng.prefix is not None and eng.tiering is None and eng.kv_whole_row
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
    be.release_arenas()
    assert be._k_pages is None and be._arenas == [None]


def test_trinitys_tiny_configuration_serves_the_tokens_it_served_before():
    """One router for both sparse families: ``afmoe.route`` with groups is
    the only selection code.  The afmoe family's tiny configuration, a share
    of its experts held, decodes the tokens it decoded on the commit before
    the router knew groups (recorded there: same weights, prompt and steps)."""
    cfg = afmoe.AfmoeConfig(
        vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, d_expert=32,
        n_layers=3, n_dense_layers=1, layer_types=(afmoe.SLIDING, afmoe.SLIDING, afmoe.FULL),
        window=32, n_experts=16, first_expert=4, experts_held=6, top_k=2, max_seq_len=256,
        dtype=jnp.float32)
    be = ServingBackend(cfg, num_pages=160, page_size=PS, max_seqs=4, max_batch_tokens=16,
                        params=afmoe.init_params(jax.random.PRNGKey(26), cfg))
    prompt = [int(t) for t in np.random.default_rng(26).integers(0, 96, 50)]
    pages, ring = list(range(1, 1 + be.pages_per_seq)), list(range(1, 1 + be.ring_pages))
    for lo in range(0, 50, 12):
        (nxt,) = be.step([StepEntry(tokens=prompt[lo:lo + 12], start=lo, pages=pages,
                                    window_pages=ring, sample=lo + 12 >= 50)])
    got = []
    for i in range(40):
        got.append(nxt)
        (nxt,) = be.step([StepEntry(tokens=[nxt], start=50 + i, pages=pages, window_pages=ring)])
    assert got == [55, 6, 18, 22, 43, 91, 43, 91, 31, 89, 55, 6, 17, 83, 35, 35, 78, 20, 7, 30,
                   52, 73, 38, 35, 76, 4, 76, 4, 76, 53, 73, 38, 34, 94, 68, 58, 91, 15, 85, 46]
    assert axk1.expert_layer is afmoe.expert_layer  # called, not copied
