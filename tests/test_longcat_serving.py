"""The LongCat-Flash family on the serving path (ISSUE 32): the
shortcut-connected block (two latent-attention sublayers and two dense FFNs
round one expert branch), two latents a layer in one arena, the rank
scalings, identity experts in the shared router and expert layer, the
counters, the prefix cache over the two-latent arena, and what a latent page
refuses.

The oracle is the benchmark's plain float32 reference in the PUBLISHED form
(``benchmarks/families/longcat_reference.py``: K and V by head, never
absorbed; it imports nothing of the program); the program runs in float32
here, so its choice at every position is held to the REFERENCE'S logits: the
reference's best logit minus its logit of the program's token is 0 up to
rounding."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import longcat_reference as ref_mod
from cordum_tpu.models import afmoe, axk1, llama, longcat
from cordum_tpu.serving.backend import ServingBackend, StepEntry
from cordum_tpu.serving.engine import GenRequest, ServingEngine
from cordum_tpu.serving.modelspec import UnsupportedForModel, spec_for

GAP = 2e-3  # float32 program against float32 "highest" reference, logits of size ~1
PS = 8


def tiny(**kw):
    base = dict(vocab_size=96, d_model=64, n_heads=4, q_rank=32, kv_rank=16, nope_dim=16,
                rope_dim=8, v_dim=16, d_ff=128, d_expert=32, n_layers=2, n_experts=16,
                n_identity=8, first_expert=0, experts_held=16, top_k=4, max_seq_len=256,
                dtype=jnp.float32)
    base.update(kw)
    return longcat.LongcatConfig(**base)


def doc_of(cfg):
    """The configuration-file keys the reference reads, from a program config."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "q_lora_rank": cfg.q_rank, "kv_lora_rank": cfg.kv_rank,
            "qk_nope_head_dim": cfg.nope_dim, "qk_rope_head_dim": cfg.rope_dim,
            "v_head_dim": cfg.v_dim, "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "mla_scale_q_lora": cfg.scale_q, "mla_scale_kv_lora": cfg.scale_kv,
            "moe_topk": cfg.top_k, "routed_scaling_factor": cfg.route_scale,
            "num_experts_routed": cfg.n_experts + cfg.n_identity,
            "zero_expert_num": cfg.n_identity, "first_expert": cfg.first_expert}


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
                                  "short-and-long-rows-in-one-step", "a-share-of-the-experts"])
def test_paged_prefill_and_decode_equal_the_published_reference(case):
    """Chunked prefill then decode through the latent pages of BOTH
    sublayers, absorbed, equals the reference's full forward in the
    published form (logits: the reference's own best against the program's
    pick)."""
    cfg = tiny()
    rng = np.random.default_rng(5)
    if case == "short-and-long-rows-in-one-step":
        lens, chunks = [90, 9, 50, 33], [[6, 3, 6, 2] * 4, [3], [5] * 6, [1, 4, 4]]
    elif case == "chunks-straddle-pages":
        lens, chunks = [100], [[12, 7, 12, 5, 12, 12, 3, 12, 9]]  # 12 + 7 crosses pages 1-2
    elif case == "a-share-of-the-experts":
        cfg = tiny(first_expert=4, experts_held=6)
        lens, chunks = [60, 20], [[10] * 4, [5, 6]]
    else:
        lens, chunks = [70], [[1] * 30]
    params = longcat.init_params(jax.random.PRNGKey(3), cfg)
    be = backend_for(cfg, params)
    seqs = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in lens]
    preds = feed(be, seqs, chunks)
    assert be.compiled_programs() == 1
    # ONE arena, a row a SUBLAYER: (c | kr) a token and sublayer, nothing by head
    assert [a.shape for a in be._arenas] == [(2 * cfg.n_layers, 160, PS, cfg.latent_width)]
    assert (cfg.latent_dim, cfg.latent_width) == (cfg.kv_rank + cfg.rope_dim, 128)
    assert be.page_bytes == 2 * cfg.n_layers * PS * cfg.latent_width * 4
    for seq, p in zip(seqs, preds):
        assert len(p) == len(seq)
        g = gaps(cfg, params, seq, p)
        assert g.max() < GAP, (case, float(g.max()), int(g.argmax()))


def sublayer_out(cfg, sub, a, positions):
    """``mla_sublayer`` over one row whose pages are 1, 2, ..."""
    t = a.shape[0]
    c_pages = longcat.init_arenas(cfg, 2 + -(-t // PS), PS)[0]
    tables = np.zeros((2, 32), np.int32)
    tables[0, :-(-t // PS)] = 1 + np.arange(-(-t // PS))
    rows = axk1.walk_rows(c_pages, positions, jnp.asarray(tables), jnp.zeros((t,), jnp.int32),
                          cfg, jnp.float32)
    o, c_pages = axk1.mla_sublayer(a, sub, c_pages, 1, rows, cfg,
                                   lambda x, pos: longcat.rope(x, pos, cfg),
                                   q_scale=cfg.q_scale, kv_scale=cfg.kv_scale)
    return o, c_pages


def reference_attention(cfg, sub, x):
    """``x + MLA(N_in(x))`` by the reference, published form."""
    return ref_mod.Reference(doc_of(cfg), 256)._attn[False](
        x, {k: sub[k] for k in ref_mod.SUB_KEYS})


def test_absorbed_equals_published_at_float32():
    """One sublayer over one sequence: the walk over the scaled ``(c | kr)``
    with the query and the output folded through Wkvb, against the
    reference's keys and values expanded by head."""
    cfg = tiny()
    sub = longcat.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["sub"][1]
    t = 40
    x = jax.random.normal(jax.random.PRNGKey(1), (t, cfg.d_model), jnp.float32)
    a = llama.rms_norm(x, sub["norm_in"], cfg.norm_eps)
    o, c_pages = sublayer_out(cfg, sub, a, jnp.arange(t, dtype=jnp.int32))
    np.testing.assert_allclose(x + o, reference_attention(cfg, sub, x), atol=2e-5, rtol=2e-5)
    # the cache holds the latent ALREADY scaled, the rotated part unscaled, in row 1 only
    ckr = a @ sub["wkva"]
    c = cfg.kv_scale * llama.rms_norm(ckr[:, :cfg.kv_rank], sub["kv_norm"], cfg.norm_eps)
    stored = np.asarray(c_pages[1, 1:]).reshape(-1, cfg.latent_width)[:t]
    np.testing.assert_allclose(stored[:, :cfg.kv_rank], c, atol=1e-6)
    np.testing.assert_allclose(
        stored[:, cfg.kv_rank:cfg.latent_dim],
        longcat.rope(ckr[:, cfg.kv_rank:], jnp.arange(t), cfg), atol=1e-6)
    assert not np.asarray(c_pages[0]).any() and not stored[:, cfg.latent_dim:].any()


@pytest.mark.parametrize("off", ["scale_q", "scale_kv"])
def test_each_rank_scaling_is_applied(off):
    """sqrt(64 / 32) on the query, sqrt(64 / 16) = 2 on the kv latent: a
    configuration without one of them is another model, and each is the
    reference's under the same flag."""
    cfg = tiny()
    assert (cfg.q_scale, cfg.kv_scale) == (2 ** 0.5, 2.0)
    bare = dataclasses.replace(cfg, **{off: False})
    assert (bare.q_scale, bare.kv_scale) == ((1.0, 2.0) if off == "scale_q" else (2 ** 0.5, 1.0))
    sub = longcat.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["sub"][0]
    # an up-projection behind a scaling is drawn for it (1/sqrt(d_model), not 1/sqrt(rank)):
    # the scaled query and the expanded latent then have unit variance
    assert abs(float(jnp.std(sub["wqb"])) * 8 - 1) < 0.05 > abs(float(jnp.std(sub["wkvb"])) * 8 - 1)
    bare_sub = longcat.init_params(jax.random.PRNGKey(0), bare)["layers"][0]["sub"][0]
    rank = cfg.q_rank if off == "scale_q" else cfg.kv_rank
    assert abs(float(jnp.std(bare_sub["wqb" if off == "scale_q" else "wkvb"])) * rank ** 0.5 - 1) < 0.05
    t = 24
    x = jax.random.normal(jax.random.PRNGKey(2), (t, cfg.d_model), jnp.float32)
    a = llama.rms_norm(x, sub["norm_in"], cfg.norm_eps)
    pos = jnp.arange(t, dtype=jnp.int32)
    with_it, without = sublayer_out(cfg, sub, a, pos)[0], sublayer_out(bare, sub, a, pos)[0]
    assert float(jnp.abs(with_it - without).max()) > 1e-2
    np.testing.assert_allclose(x + without, reference_attention(bare, sub, x), atol=2e-5, rtol=2e-5)
    # 1 is no operation at all: A.X-K1's program has no multiply to skip
    text = str(jax.make_jaxpr(lambda a_: sublayer_out(
        dataclasses.replace(cfg, scale_q=False, scale_kv=False), sub, a_, pos)[0])(a))
    assert text.count(" mul ") + 2 == str(jax.make_jaxpr(
        lambda a_: sublayer_out(cfg, sub, a_, pos)[0])(a)).count(" mul ")


# ------------------------------------------------------ the router, the experts
def pr31_route(m, layer, cfg):
    """``afmoe.route`` as it was before it knew softmax scores and identity
    experts (PR 31), verbatim."""
    scores = jax.nn.sigmoid(jnp.matmul(
        m.astype(jnp.float32), layer["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    pick = scores + layer["router_bias"] if "router_bias" in layer else scores
    if cfg.n_group > 1:
        with jax.named_scope("moe_group_select"):
            t, n = pick.shape
            by_group = pick.reshape(t, cfg.n_group, n // cfg.n_group)
            best2, _ = jax.lax.top_k(by_group, 2)
            _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), cfg.topk_group)  # [T, topk_group]
            keep = jnp.zeros((t, cfg.n_group), bool).at[
                jnp.arange(t)[:, None], kept].set(True)
            pick = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(t, n)
    _, sel = jax.lax.top_k(pick, cfg.top_k)
    w = jnp.take_along_axis(scores, sel, axis=1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel, w * cfg.route_scale


@pytest.mark.parametrize("family", ["afmoe-one-group", "axk1-groups"])
def test_sigmoid_without_identity_experts_is_the_router_it_was_bit_for_bit(family):
    if family == "afmoe-one-group":
        cfg = afmoe.AfmoeConfig()
        layer = afmoe.init_params(jax.random.PRNGKey(1), cfg)["layers"][1]
    else:
        cfg = axk1.Axk1Config()
        layer = axk1.init_params(jax.random.PRNGKey(1), cfg)["layers"][1]
    assert (cfg.route_score, cfg.n_identity) == ("sigmoid", 0)
    m = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.d_model), jnp.float32)
    sel, w = afmoe.route(m, layer, cfg)
    sel0, w0 = pr31_route(m, layer, cfg)
    assert np.array_equal(sel, sel0) and np.array_equal(w, w0)
    # one group traces to the text it had; the group-limited choice changed ON PURPOSE with
    # ISSUE 48 (no sort, no scatter) and is held to this form's numbers, above and in
    # tests/test_axk1_serving.py, ties included
    same = lambda f: str(jax.make_jaxpr(lambda x: f(x, layer, cfg))(m))  # noqa: E731
    assert (same(afmoe.route) == same(pr31_route)) == (cfg.n_group == 1)
    # and the expert layer round it computes a shared expert and returns bare counts
    _, counts = afmoe.expert_layer(m, layer, cfg, jnp.ones((64,), bool))
    assert counts.shape == (cfg.experts_held,)


def test_softmax_weights_are_not_normalised_and_carry_the_factor():
    """``w_i = 6 p_i`` of a softmax over the router's WHOLE width (real and
    identity experts alike); the bias moves picks and no weight; the
    reference picks the same."""
    cfg = tiny()
    layer = longcat.init_params(jax.random.PRNGKey(4), cfg)["layers"][0]
    m = jax.random.normal(jax.random.PRNGKey(5), (200, cfg.d_model), jnp.float32)
    sel, w = (np.asarray(x) for x in afmoe.route(m, layer, cfg))
    p = np.asarray(jax.nn.softmax(jnp.matmul(m, layer["router"],
                                             precision=jax.lax.Precision.HIGHEST), axis=-1))
    assert p.shape == (200, cfg.n_experts + cfg.n_identity)
    np.testing.assert_allclose(p.sum(1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(w, 6.0 * np.take_along_axis(p, sel, 1), rtol=1e-6)
    assert 0.5 < w.sum(1).min() and w.sum(1).max() < 6.0 and np.ptp(w.sum(1)) > 0.5
    bias = np.asarray(layer["router_bias"])
    for t in range(200):
        assert sorted(sel[t].tolist()) == sorted(np.argsort(-(p[t] + bias))[:cfg.top_k].tolist())
    # a large bias decides every pick and still no weight
    loud = {**layer, "router_bias": jnp.zeros_like(layer["router_bias"]).at[20:24].set(1.0)}
    sel2, w2 = (np.asarray(x) for x in afmoe.route(m, loud, cfg))
    assert set(sel2.ravel().tolist()) == {20, 21, 22, 23}
    np.testing.assert_allclose(w2, 6.0 * np.take_along_axis(p, sel2, 1), rtol=1e-6)
    rsel, rw = ref_mod.route(m, layer["router"], layer["router_bias"], top_k=cfg.top_k,
                             route_scale=cfg.route_scale)
    assert np.array_equal(np.sort(sel, 1), np.sort(np.asarray(rsel), 1))
    np.testing.assert_allclose(np.sort(w, 1), np.sort(np.asarray(rw), 1), rtol=1e-5)
    with pytest.raises(ValueError):
        afmoe.AfmoeConfig(route_score="tanh")
    with pytest.raises(ValueError):
        axk1.Axk1Config(n_identity=4)  # identity experts lie in no routing group


def test_an_identity_pick_adds_w_times_m_and_leaves_the_experts_counts_alone():
    """A router biased onto identity experts alone: the layer returns exactly
    ``(sum of w) x m`` and no held expert gets a row; biased onto real
    experts alone the identity part is exactly zero."""
    cfg = tiny()
    layer = longcat.init_params(jax.random.PRNGKey(6), cfg)["layers"][0]
    m = jax.random.normal(jax.random.PRNGKey(7), (30, cfg.d_model), jnp.float32)
    live = jnp.ones((30,), bool).at[25:].set(False)
    only = lambda ids: {**layer, "router_bias": jnp.zeros((24,)).at[jnp.asarray(ids)].set(1.0)}  # noqa: E731
    out, counts = afmoe.expert_layer(m, only([16, 18, 21, 23]), cfg, live)
    sel, w = afmoe.route(m, only([16, 18, 21, 23]), cfg)
    assert set(np.asarray(sel).ravel().tolist()) == {16, 18, 21, 23}
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.sum(w, 1, keepdims=True) * m))
    assert counts.tolist() == [0] * 16 + [25 * 4, 0, 0]  # live tokens' picks; 0 real a token
    # three identity picks and real expert 5: one row each for expert 5, the rest as before
    mixed = only([5, 18, 21, 23])
    out2, counts2 = afmoe.expert_layer(m, mixed, cfg, live)
    sel2, w2 = afmoe.route(m, mixed, cfg)
    assert counts2.tolist() == [0] * 5 + [25] + [0] * 10 + [25 * 3, 1, 1]
    w5 = jnp.sum(jnp.where(sel2 == 5, w2, 0.0), 1, keepdims=True)
    e5 = (jax.nn.silu(m @ layer["e_gate"][5]) * (m @ layer["e_up"][5])) @ layer["e_down"][5]
    want = (jnp.sum(w2, 1, keepdims=True) - w5) * m + w5 * e5
    np.testing.assert_allclose(np.asarray(out2)[:25], np.asarray(want)[:25], atol=1e-5)
    # real experts alone: the identity part is exactly zero and there is NO shared expert
    out3, counts3 = afmoe.expert_layer(m, only([1, 2, 3, 4]), cfg, live)
    assert counts3.tolist() == [0, 25, 25, 25, 25] + [0] * 11 + [0, 4, 4]
    assert "s_gate" not in layer and not np.asarray(out3)[25:].any()  # padding slots route nowhere


def reference_branch(cfg, layer, m, first, held):
    """Identity part + the held real experts' weighted terms, by the reference."""
    sel, w = ref_mod.route(m, layer["router"], layer["router_bias"], top_k=cfg.top_k,
                           route_scale=cfg.route_scale)
    out = ref_mod.identity_term(m, sel, w, cfg.n_experts)
    for e in range(first, first + held):
        out = out + ref_mod.expert_term(m, sel, w, e, layer["e_gate"][e], layer["e_up"][e],
                                        layer["e_down"][e])
    return out


def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """Each of 32 chips' held-experts part, plus the identity part counted
    ONCE (every chip computes it alike for its own tokens), is the whole
    branch of the uncut reference; a share alone equals the reference given
    the same share."""
    cfg = tiny(n_experts=64, experts_held=64, n_identity=32, top_k=6)
    layer = longcat.init_params(jax.random.PRNGKey(11), cfg)["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(12), (40, cfg.d_model), jnp.float32)
    live = jnp.ones((40,), bool)
    whole = reference_branch(cfg, layer, m, 0, cfg.n_experts)
    sel, w = afmoe.route(m, layer, cfg)
    identity = ref_mod.identity_term(m, sel, w, cfg.n_experts)
    assert float(jnp.abs(identity).max()) > 0.01  # some picks ARE identity experts
    total, seen, zero = identity, 0, set()
    for rank in range(32):
        c = dataclasses.replace(cfg, first_expert=2 * rank, experts_held=2)
        cut = {k: layer[k][2 * rank:2 * rank + 2] for k in ("e_gate", "e_up", "e_down")}
        part, counts = afmoe.expert_layer(m, {**layer, **cut}, c, live)
        np.testing.assert_allclose(part, reference_branch(cfg, layer, m, 2 * rank, 2), atol=1e-4)
        total = total + (part - identity)
        seen += int(counts[:2].sum())
        zero.add(int(counts[2]))
    np.testing.assert_allclose(total, whole, atol=2e-4)
    (zero_picks,) = zero  # every chip counts the same identity picks
    assert seen + zero_picks == 40 * cfg.top_k  # every pick was some chip's, or nobody's work
    assert 0 < zero_picks < 40 * cfg.top_k


def forward_rows(cfg, params, seq, ragged=longcat.ragged_step):
    """One step that feeds ``seq`` as one row from position 0: the per-slot
    argmax and the arena."""
    t = len(seq)
    (c_pages,) = longcat.init_arenas(cfg, 2 + -(-t // PS), PS)
    tables = np.zeros((2, 32), np.int32)
    tables[0, :-(-t // PS)] = 1 + np.arange(-(-t // PS))
    return ragged(params, c_pages, jnp.asarray(seq, jnp.int32), jnp.arange(t, dtype=jnp.int32),
                  jnp.asarray(tables), jnp.zeros((t,), jnp.int32), jnp.zeros((1,), jnp.int32), cfg)


def test_the_expert_branch_reads_m0_and_is_added_at_the_layers_end(monkeypatch):
    """The program's layer is the reference's; a branch fed ``m1`` instead of
    ``m0``, or added before the second sublayer reads the stream, is another
    model at tiny size (both checked through the reference's logits)."""
    cfg = tiny()
    params = longcat.init_params(jax.random.PRNGKey(13), cfg)
    seq = [int(t) for t in np.random.default_rng(13).integers(0, cfg.vocab_size, 24)]
    out, _ = forward_rows(cfg, params, seq)
    assert gaps(cfg, params, seq, np.asarray(out)[:24]).max() < GAP
    ref = ref_mod.Reference(doc_of(cfg), 256)
    x = jax.random.normal(jax.random.PRNGKey(14), (24, cfg.d_model), jnp.float32)
    w = params["layers"][0]
    right = ref.layer(x, w)

    def moved(x, *, read_m1, add_early):
        e = None
        for j, sub in enumerate(w["sub"]):
            if add_early and j == 1:
                x, e = x + e, jnp.zeros_like(x)
            x = ref._attn[False](x, {k: sub[k] for k in ref_mod.SUB_KEYS})
            m = ref._pre(x, sub["norm_post"])
            if j == int(read_m1):
                e = ref.expert_branch(m, w)
            x = x + ref._ffn[False](m, sub["w_gate"], sub["w_up"], sub["w_down"])
        return x + e
    np.testing.assert_allclose(moved(x, read_m1=False, add_early=False), right, atol=1e-6)
    assert float(jnp.abs(moved(x, read_m1=True, add_early=False) - right).max()) > 1e-2
    assert float(jnp.abs(moved(x, read_m1=False, add_early=True) - right).max()) > 1e-3
    # the program's first layer, run alone, is ``right`` too: embed -> one layer -> no head
    one = dataclasses.replace(cfg, n_layers=1)
    calls = []
    real = afmoe.expert_layer

    def spy(m, layer, c, live):
        calls.append(np.asarray(m))
        return real(m, layer, c, live)
    monkeypatch.setattr(longcat, "expert_layer", spy)
    forward_rows(one, {**params, "layers": [w]}, seq)
    x0 = params["embed"][jnp.asarray(seq)]
    x1 = ref._attn[False](x0, {k: w["sub"][0][k] for k in ref_mod.SUB_KEYS})
    (m_seen,) = calls  # ONE branch a layer, and what it read is m0
    np.testing.assert_allclose(m_seen, ref._pre(x1, w["sub"][0]["norm_post"]), atol=1e-5)


def test_axk1s_tiny_configuration_serves_the_tokens_it_served_before():
    """One latent-attention sublayer for both families (``axk1.mla_sublayer``,
    called by ``axk1.ragged_step`` and ``longcat.ragged_step``): A.X-K1's tiny
    configuration, a share of its experts held, decodes the tokens it decoded
    on the commit before the sublayer was a function and the router knew
    softmax (recorded there: same weights, prompt and steps), in both dtypes."""
    want = {
        jnp.float32: [74, 85, 72, 64, 68, 74, 50, 90, 52, 25, 50, 25, 25, 11, 56, 18, 78, 75, 90,
                      52, 5, 64, 23, 17, 15, 5, 25, 94, 40, 90, 90, 52, 90, 90, 90, 90, 90, 52, 90,
                      90],
        jnp.bfloat16: [74, 85, 72, 64, 68, 74, 50, 90, 52, 25, 50, 20, 21, 40, 18, 19, 33, 90, 52,
                       90, 90, 90, 90, 10, 90, 52, 90, 90, 21, 7, 56, 80, 82, 3, 61, 25, 54, 64, 68,
                       7]}
    for dt, tokens in want.items():
        cfg = axk1.Axk1Config(
            vocab_size=96, d_model=64, n_heads=4, q_rank=32, kv_rank=32, nope_dim=16, rope_dim=8,
            v_dim=16, d_ff=128, d_expert=32, n_layers=3, n_dense_layers=1, n_experts=16,
            first_expert=4, experts_held=6, top_k=4, n_group=4, topk_group=2, max_seq_len=256,
            dtype=dt, rope_factor=32.0, rope_original_len=64, rope_beta_fast=32.0,
            rope_beta_slow=1.0)
        be = ServingBackend(cfg, num_pages=160, page_size=PS, max_seqs=4, max_batch_tokens=16,
                            params=axk1.init_params(jax.random.PRNGKey(32), cfg))
        prompt = [int(t) for t in np.random.default_rng(32).integers(0, 96, 50)]
        pages = list(range(1, 1 + be.pages_per_seq))
        for lo in range(0, 50, 12):
            (nxt,) = be.step([StepEntry(tokens=prompt[lo:lo + 12], start=lo, pages=pages,
                                        sample=lo + 12 >= 50)])
        got = []
        for i in range(40):
            got.append(nxt)
            (nxt,) = be.step([StepEntry(tokens=[nxt], start=50 + i, pages=pages)])
        assert got == tokens, dt
    assert longcat.mla_sublayer is axk1.mla_sublayer and longcat.expert_layer is afmoe.expert_layer


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


async def test_a_prefix_hit_over_the_two_latent_arena_yields_a_full_prefills_tokens():
    """A later turn maps the finished turn's pages, which hold BOTH
    sublayers' latents of every layer, and prefills only what is new; a hit
    that ends on the prompt's end copies the page in every arena row first."""
    cfg = tiny(first_expert=4, experts_held=6)
    params = longcat.init_params(jax.random.PRNGKey(7), cfg)
    be = backend_for(cfg, params, max_seqs=3, budget=9, pages=120)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=3, max_new_tokens_cap=64)
    assert eng.prefix is not None and eng.tiering is None  # shares, cannot hibernate
    rng = np.random.default_rng(1)
    draw = lambda n: [int(t) for t in rng.integers(1, cfg.vocab_size, n)]  # noqa: E731
    history, fed_by_turn = draw(37), []
    for turn, (n_new, n_out) in enumerate([(0, 11), (21, 9)]):
        history = history + draw(n_new)
        before = eng.stats.prefill_tokens
        out = await ask(eng, history, n_out, f"t{turn}")
        fed_by_turn.append(eng.stats.prefill_tokens - before)
        held_to_reference(cfg, params, history, out)
        history = history + out
    st = eng.stats
    assert (st.prefix_hits, st.prefix_misses, st.prefix_hit_tokens) == (1, 1, 40)
    assert fed_by_turn == [37, 37 + 11 + 21 - 40] and st.cow_copies == 0
    # the same whole-pages prompt again: the last token is fed INTO a shared page
    prompt = draw(4 * PS)
    first = await ask(eng, prompt + [5], 6, "a", key="a")
    arena_before = np.asarray(be._arenas[0])
    again = await ask(eng, prompt, 6, "b", key="b")
    assert eng.stats.cow_copies == 1 and eng.stats.prefix_hits == 2
    held_to_reference(cfg, params, prompt + [5], first)
    held_to_reference(cfg, params, prompt, again)
    node_pages = [n.page for n in eng.prefix.match(prompt)]
    assert len(node_pages) == 4 and arena_before.shape[0] == 4  # four arena rows: 2 x 2 layers
    np.testing.assert_array_equal(np.asarray(be._arenas[0])[:, node_pages],
                                  arena_before[:, node_pages])
    await eng.stop()
    eng.allocator.check_consistency()


async def test_the_counters_equal_a_hand_count():
    """Rows through the engine: the expert layer's four, the identity picks
    and the spread of real picks a token, each against a recount from the
    router run by hand on what the program routed; the walk's host count is
    one walk's (a kind of page), whatever the arena's rows."""
    cfg = tiny(first_expert=4, experts_held=6)
    params = longcat.init_params(jax.random.PRNGKey(7), cfg)
    be = backend_for(cfg, params, max_seqs=3, budget=9, pages=120)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=3, max_new_tokens_cap=64)
    seen, routed = [], []
    inner, real = be.step, afmoe.expert_layer

    def tapped(entries):
        out = inner(entries)
        seen.append((sum(len(e.tokens) for e in entries), be.last_aux.copy(),
                     dict(be.last_counters)))
        return out
    be.step = tapped
    rng = np.random.default_rng(3)
    draw = lambda n: [int(t) for t in rng.integers(1, cfg.vocab_size, n)]  # noqa: E731
    outs = await asyncio.gather(ask(eng, draw(30), 12, "a", key="k0"),
                                ask(eng, draw(6), 20, "b", key="k1"),
                                ask(eng, draw(50), 5, "c", key="k2"))
    assert [len(o) for o in outs] == [12, 20, 5]
    await eng.stop()
    st = eng.stats
    layers, k = cfg.n_layers, cfg.top_k
    live = sum(n for n, _, _ in seen)
    assert st.model["moe_assignments"] == live * k * layers
    assert st.model["moe_assignments_here"] == sum(int(a[:, :6].sum()) for _, a, _ in seen)
    assert st.model["moe_experts_touched"] == sum(int((a[:, :6] > 0).sum()) for _, a, _ in seen)
    assert st.model["moe_max_expert_load"] == sum(int(a[:, :6].max(1).sum()) for _, a, _ in seen)
    assert st.model["moe_zero_assignments"] == sum(int(a[:, 6].sum()) for _, a, _ in seen)
    assert st.model["moe_real_picks_max"] == sum(int(a[:, 7].sum()) for _, a, _ in seen)
    assert st.model["moe_real_picks_min"] == sum(int(a[:, 8].sum()) for _, a, _ in seen)
    assert 0 < st.model["moe_assignments_here"] < st.model["moe_assignments"]
    # identity experts are 8 of the router's 24: about a third of the picks
    assert 0.2 < st.model["moe_zero_assignments"] / st.model["moe_assignments"] < 0.5
    assert st.model["moe_real_picks_min"] < st.model["moe_real_picks_max"] <= k * layers * st.steps
    # one step's three by hand: route the program's own m0 of a fed chunk
    del routed[:]
    seq = draw(9)

    def spy(m, layer, c, lv):
        routed.append((np.asarray(m), layer, np.asarray(lv)))
        return real(m, layer, c, lv)
    longcat.expert_layer = spy
    try:
        out, _ = forward_rows(cfg, params, seq)
    finally:
        longcat.expert_layer = real
    aux = np.asarray(out)[9:].reshape(layers, 6 + afmoe.IDENTITY_COUNTS)
    for (m, layer, lv), row in zip(routed, aux):
        sel = np.asarray(afmoe.route(jnp.asarray(m), layer, cfg)[0])[lv]
        reals = (sel < cfg.n_experts).sum(1)
        assert row[:6].tolist() == [int((sel == 4 + i).sum()) for i in range(6)]
        assert row[6:].tolist() == [int((sel >= cfg.n_experts).sum()), reals.max(), reals.min()]
    got = afmoe.step_counters(cfg, aux, 9)
    assert got["moe_zero_assignments"] == int(aux[:, 6].sum())
    assert (got["moe_real_picks_max"], got["moe_real_picks_min"]) == (aux[:, 7].sum(),
                                                                      aux[:, 8].sum())
    assert be.page_bytes == 2 * layers * PS * cfg.latent_width * 4
    assert st.kv_bytes_behind_rows % be.page_bytes == 0 and st.attn_slots_live > 0


async def test_what_cannot_carry_a_latent_page_refuses_the_family():
    cfg = tiny()
    spec = spec_for(cfg)
    assert spec.window is None and spec.kv_whole_row and not spec.kv_by_head
    assert (spec.family, spec.n_arenas, spec.arenas) == ("longcat", 1, (((128,),),))
    assert spec.aux_shape == (2, 16 + 3) and spec.value_dim == cfg.kv_rank
    be = backend_for(cfg, None)
    be.step([StepEntry(tokens=[3, 4], start=0, pages=[1, 2])])
    assert be._k_pages is be._arenas[0] and len(be._arenas) == 1
    be.copy_page(1, 2)  # maps over the kind's one arena: every sublayer's row of the page
    np.testing.assert_array_equal(np.asarray(be._arenas[0][:, 2]), np.asarray(be._arenas[0][:, 1]))
    assert np.asarray(be._arenas[0][:, 1, :2]).any(axis=(1, 2)).all()  # all four rows were written
    for call in (lambda: be.export_kv([1], 0, 8), lambda: be.import_kv([1], [{}])):
        with pytest.raises(UnsupportedForModel, match="K and V records by head"):
            call()
    from cordum_tpu.serving.shard import ShardedServingBackend

    with pytest.raises(UnsupportedForModel):
        ShardedServingBackend(cfg, rank=0, tp=2)
    eng = ServingEngine(be, run_blocking=run_blocking, hibernate_after_s=30.0)
    assert eng.prefix is not None and eng.tiering is None and eng.kv_whole_row
    live = asyncio.ensure_future(eng.submit(
        GenRequest(prompt=[1, 2, 3], max_new_tokens=30, stream=False), job_id="live"))
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
    assert len((await asyncio.wait_for(live, timeout=120))["tokens"]) == 30
    await eng.stop()
    with pytest.raises(ValueError):
        tiny(first_expert=12, experts_held=6)  # the held lie among the REAL experts
