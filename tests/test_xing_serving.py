"""The Xing4.0 family on the serving path (ISSUE 49): a residual of four
hyper-connected streams a token (``models/hyper.py``) round A.X-K1's latent
attention and expert layer, through the paged latent cache, the engine and
the prefix cache; the counters and the kernel role it states through the
seam; and the controls that must fail.

The oracle is the benchmark's plain float32 reference
(``benchmarks/families/xing_reference.py``: the published, unabsorbed
attention, the maps as plain array expressions; it imports nothing of the
program); the program runs in float32 here, so its choice at every position
is held to the REFERENCE'S logits: the reference's best logit minus its logit
of the program's token is 0 up to rounding (``GAP``, A.X-K1's tolerance and
for its reason: float32 against float32 "highest", logits of size ~1)."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.families import xing_reference as ref_mod
from cordum_tpu.models import attention, axk1, hyper, xing
from cordum_tpu.serving.engine import ServingEngine
from cordum_tpu.serving.modelspec import UnsupportedForModel, spec_for
from tests.test_axk1_serving import GAP, PS, YARN, ask, backend_for, feed, run_blocking
from tests.test_axk1_serving import doc_of as axk1_doc_of


def tiny(**kw):
    base = dict(vocab_size=96, d_model=64, n_heads=4, q_rank=32, kv_rank=32, nope_dim=16,
                rope_dim=8, v_dim=16, d_ff=128, d_expert=32, n_layers=3, n_dense_layers=1,
                n_experts=16, first_expert=0, experts_held=16, top_k=4, max_seq_len=256,
                dtype=jnp.float32, **YARN)
    base.update(kw)
    return xing.XingConfig(**base)


def doc_of(cfg):
    """The configuration-file keys the reference reads, from a program config."""
    return {**axk1_doc_of(cfg), "hc_mult": cfg.hc_mult, "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
            "hc_eps": cfg.hc_eps, "mhc_h_res_clamp_min": cfg.hc_clamp_min,
            "mhc_h_res_clamp_max": cfg.hc_clamp_max}


def gaps(cfg, params, seq, preds, **kw):
    """Reference's best logit minus its logit of the program's prediction
    after every position of ``seq``."""
    ref = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len, **kw)
    top, _, got = ref.logits_of(params, seq, [int(t) for t in preds])
    return top - got


def biased(params, seed=11):
    """The weights with a selection bias that decides some picks."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(params["layers"]))
    return {**params, "layers": [
        {**w, "router_bias": 0.1 * jax.random.normal(k, w["router_bias"].shape)}
        if "router_bias" in w else w for w, k in zip(params["layers"], keys)]}


@pytest.mark.parametrize("case", ["chunks-straddle-pages", "one-token-chunks-then-decode",
                                  "short-and-long-rows-in-one-step", "a-selection-bias"])
def test_paged_prefill_and_decode_equal_the_reference(case):
    """Chunked prefill then decode through the latent pages, four streams a
    token through 2 x 3 pairs of maps, equals the reference's full forward."""
    cfg = tiny()
    rng = np.random.default_rng(5)
    if case in ("short-and-long-rows-in-one-step", "a-selection-bias"):
        lens, chunks = [150, 9, 70, 33], [[6, 3, 6, 2] * 6, [3], [5] * 9, [1, 4, 4]]
    elif case == "chunks-straddle-pages":
        lens, chunks = [170], [[12, 7, 12, 5, 12, 12, 3, 12, 12, 9, 12, 12, 12]]
    else:
        lens, chunks = [120], [[1] * 40]
    params = xing.init_params(jax.random.PRNGKey(3), cfg)
    if case == "a-selection-bias":
        unbiased, params = params, biased(params)
    be = backend_for(cfg, params)
    seqs = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in lens]
    preds = feed(be, seqs, chunks)
    assert be.compiled_programs() == 1
    assert [a.shape for a in be._arenas] == [(cfg.n_layers, 160, PS, cfg.latent_width)]
    assert be.kernels == {"walk": "", "expert": "", "residual": ""}  # the CPU holds the jax.numpy forms
    for seq, p in zip(seqs, preds):
        assert len(p) == len(seq)
        g = gaps(cfg, params, seq, p)
        assert g.max() < GAP, (case, float(g.max()), int(g.argmax()))
    if case == "a-selection-bias":
        # the bias decides picks (the unbiased reference is told apart) and
        # enters no weight: a bias equal on every expert changes nothing
        assert max(gaps(cfg, unbiased, s, p).max() for s, p in zip(seqs, preds)) > 50 * GAP
        flat = {**unbiased, "layers": [
            {**w, "router_bias": jnp.full_like(w["router_bias"], 0.3)} if "router_bias" in w else w
            for w in unbiased["layers"]]}
        assert max(gaps(cfg, flat, s, p).max() for s, p in zip(
            seqs, feed(backend_for(cfg, unbiased), seqs, chunks))) < GAP


def test_the_static_maps_alone_fail_where_the_sound_program_passes():
    """The control of the new mechanism: the reference with the three
    ``alpha`` at 0 (the maps a program would compute had it left the
    token-dependent part out) reads the sound program's tokens hundreds of
    times past the tolerance, on every row."""
    cfg = tiny()
    params = xing.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(6)
    seqs = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in (90, 40)]
    preds = feed(backend_for(cfg, params), seqs, [[8] * 12, [7] * 6])
    for seq, p in zip(seqs, preds):
        assert gaps(cfg, params, seq, p).max() < GAP
        g = gaps(cfg, params, seq, p, static_maps=True)
        assert g.mean() > 100 * GAP and g.max() > 500 * GAP, (float(g.mean()), float(g.max()))


def test_the_int8_control_fails_where_the_sound_program_passes():
    """The other control: the reference's int8 products put another token
    first at some positions, which the float32 reference reads as a gap far
    past the tolerance (what ``run.py --control 1`` compares on the chip)."""
    cfg = tiny()
    params = xing.init_params(jax.random.PRNGKey(3), cfg)
    seq = [int(t) for t in np.random.default_rng(7).integers(0, cfg.vocab_size, 96)]
    ref = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len)
    _, arg_low, _ = ref.logits_of(params, seq, seq, lower_precision=True)
    top, arg, got = ref.logits_of(params, seq, [int(t) for t in arg_low])
    assert (top - got).max() > 20 * GAP and (arg != arg_low).any()


def test_one_stream_with_its_maps_at_one_is_axk1s_block(monkeypatch):
    """``hc_mult`` 1 and the three maps forced to 1 (``alpha`` 0, ``b_pre``
    40: a sigmoid of 1.0 in float32, ``b_post`` 0: twice a half; ``hc_eps``
    0, or a 1 x 1 Sinkhorn matrix settles at 1 - ``hc_eps`` and every close
    shrinks the stream by a millionth) is A.X-K1's block at the same weights:
    the stream behind the last layer equals ``axk1.ragged_step``'s residual
    to float32 rounding.  Ties the family to the shared sublayers."""
    cfg = tiny(hc_mult=1, hc_eps=0.0, n_group=4, topk_group=2, route_scale=2.5)
    base = axk1.Axk1Config(**{f.name: getattr(cfg, f.name)
                              for f in dataclasses.fields(axk1.Axk1Config)})
    params = xing.init_params(jax.random.PRNGKey(4), cfg)
    ones = {"alpha": jnp.zeros((3,), jnp.float32), "bias": jnp.asarray([40.0, 0.0, 2.0])}
    params = {**params, "layers": [
        {k: ({**v, **ones} if k in xing.MAPS else v) for k, v in w.items() if k != "router_bias"}
        for w in params["layers"]]}
    monkeypatch.setattr(axk1, "sampled", lambda x, *rest: x)  # the residual, not its argmax
    t, pages, width = 12, 9, 4
    arena = axk1.init_arenas(cfg, pages, PS)[0]
    tokens = jnp.arange(t, dtype=jnp.int32) + 3
    positions = jnp.asarray([0, 1, 2, 3, 4, 5, 6, 0, 1, 2, 0, 0], jnp.int32)
    token_seq = jnp.asarray([0] * 7 + [1] * 3 + [2] * 2, jnp.int32)  # row 2 is the padding row
    tables = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0], [0, 0, 0, 0]], jnp.int32)
    out_idx = jnp.zeros((2,), jnp.int32)
    x_hc, _ = xing.ragged_step(params, arena, tokens, positions, tables, token_seq, out_idx, cfg)
    x_one, _ = axk1.ragged_step(params, arena, tokens, positions, tables, token_seq, out_idx, base)
    assert x_hc.shape == x_one.shape == (t, cfg.d_model) and tables.shape[1] == width
    np.testing.assert_allclose(x_hc, x_one, atol=5e-6)
    assert float(jnp.abs(x_one).max()) > 1.0


@pytest.fixture
def kernel_maps(monkeypatch):
    """Both maps through their Pallas kernels, interpreted, the walk and the
    grouped products in their ``jax.numpy`` forms: the test steers the
    choices (the program has no such option), and the jitted walk's traces of
    this test are dropped behind it."""
    def opened(x, p, hc):
        return hyper.open_kernel(x, p["phi"], p["alpha"], p["bias"], hc)

    monkeypatch.setattr(hyper, "mhc_open", opened)
    monkeypatch.setattr(hyper, "mhc_close", hyper.close_kernel)
    monkeypatch.setattr(jax.lax, "platform_dependent", lambda *args, default, tpu: default(*args))
    attention.paged_attention.clear_cache()
    with pltpu.force_tpu_interpret_mode():
        yield
    attention.paged_attention.clear_cache()


def test_a_step_through_the_kernels_equals_the_reference(kernel_maps):
    """Streams 128 wide, so the kernels fit: chunked prefill then decode
    with both maps of every sublayer computed by the kernels.  The kernel's
    projection is two bfloat16 passes (2^-17 of a number), which moves a
    logit by 1e-4 here: ten times the tolerance of the plain form."""
    cfg = tiny(d_model=128, n_layers=2)
    assert hyper.fits(cfg.hc_mult, cfg.d_model)
    params = xing.init_params(jax.random.PRNGKey(9), cfg)
    seq = [int(t) for t in np.random.default_rng(9).integers(0, cfg.vocab_size, 40)]
    be = backend_for(cfg, params)
    (preds,) = feed(be, [seq], [[12, 12, 9]])
    assert gaps(cfg, params, seq, preds).max() < 10 * GAP
    assert be.last_counters["mhc_slots"] == 128 * cfg.n_sublayers  # 16 slots brought to one whole tile


def test_the_specification_states_counters_and_kernels_through_the_seam():
    """A fourth kernel role beside walk, expert and state, and two counters
    beside the expert layer's, all under the family's names: the backend and
    the engine hand them on and know none of them."""
    cfg = tiny()
    spec = spec_for(cfg)
    assert spec.family == "xing" and spec.kv_whole_row and spec.kv_positional and not spec.kv_by_head
    assert spec.aux_shape == (cfg.n_expert_layers + 1, cfg.experts_held)
    assert dict(spec.kernels("cpu", 1)) == {"walk": "", "expert": "", "residual": ""}
    assert spec.kernels("tpu", 1)["residual"] == ""  # 64 wide: not whole lane tiles
    wide = dataclasses.replace(cfg, d_model=3584, d_expert=1024)
    assert dict(spec_for(wide).kernels("tpu", 1)) == {
        "walk": "latent_walk", "expert": "expert_mlp", "residual": "mhc_open+mhc_close"}
    aux = np.zeros(spec.aux_shape, np.int64)
    aux[0, :3], aux[-1, 0] = (2, 1, 5), 96
    counters, attrs = spec.count_aux(aux, 5, {"residual": "mhc_open+mhc_close"})
    assert counters["mhc_slots"] == 96 and counters["mhc_live"] == 5 * cfg.n_sublayers == 30
    assert counters["moe_assignments_here"] == 8 and counters["moe_experts_touched"] == 3
    assert attrs["residual_kernel"] == "mhc_open+mhc_close" and attrs["mhc_live"] == "30"
    for name in ("engine.py", "backend.py"):
        text = open(f"cordum_tpu/serving/{name}").read()
        assert "xing" not in text and "mhc" not in text and "hyper" not in text


async def test_the_engine_serves_it_with_the_prefix_cache_and_counts_the_maps():
    """Mixed rows through the engine, a later turn over prefix-cache pages
    among them: every answer is the reference's, and the maps' counters are a
    recount of what the steps fed."""
    cfg = tiny()
    params = xing.init_params(jax.random.PRNGKey(7), cfg)
    be = backend_for(cfg, params, max_seqs=3, budget=9, pages=120)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=3, max_new_tokens_cap=64)
    fed = []
    inner = be.step

    def tapped(entries):
        out = inner(entries)
        fed.append((sum(len(e.tokens) for e in entries), dict(be.last_counters)))
        return out
    be.step = tapped
    rng = np.random.default_rng(3)
    draw = lambda n: [int(t) for t in rng.integers(1, cfg.vocab_size, n)]  # noqa: E731
    p0 = draw(60)
    out0 = await ask(eng, p0, 8, "first", key="k0")
    later = p0 + out0 + draw(30)
    prompts = [later, draw(6), draw(100)]
    outs = await asyncio.gather(ask(eng, later, 12, "second", key="k0"),
                                ask(eng, prompts[1], 20, "short", key="k1"),
                                ask(eng, prompts[2], 5, "long", key="k2"))
    await eng.stop()
    st = eng.stats
    assert st.prefix_hits == 1 and st.prefix_hit_tokens == (60 + 8 - 1) // PS * PS
    for prompt, out in zip([p0] + prompts, [out0] + list(outs)):
        seq = list(prompt) + list(out)
        g = gaps(cfg, params, seq[:-1], seq[1:])[len(prompt) - 1:]
        assert g.max() < GAP, float(g.max())
    assert st.model["mhc_live"] == sum(n for n, _ in fed) * cfg.n_sublayers
    assert st.model["mhc_slots"] == len(fed) * be.max_batch_tokens * cfg.n_sublayers
    assert all(c["mhc_live"] == n * cfg.n_sublayers for n, c in fed)
    assert 0 < st.model["mhc_live"] < st.model["mhc_slots"]
    assert st.model["moe_assignments_here"] == st.model["moe_assignments"] > 0  # the set is whole
    with pytest.raises(UnsupportedForModel):
        await eng.hibernate_session("second")
    eng.allocator.check_consistency()
