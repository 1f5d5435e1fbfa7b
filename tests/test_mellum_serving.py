"""The Mellum family on the serving path (ISSUE 46): window and full attention
layers that BOTH rotate, each kind under its own table (plain on the window
kind, YaRN on the full kind), over rings and whole-row pages; every layer an
expert layer whose expert set is held whole; the counters and kernel roles it
states through the seam; and what the family refuses.

The oracle is the benchmark's plain float32 reference
(``benchmarks/families/mellum_reference.py``, which imports nothing of the
program and makes both rotations from the closed forms); the program runs in
float32 here, so its choice at every position is held to the REFERENCE'S
logits: the reference's best logit minus its logit of the program's token is
0 up to rounding."""
import asyncio
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mellum_reference as ref_mod
from cordum_tpu.models import afmoe, attention, axk1, expert_mlp, head_walk, mellum, rotary
from cordum_tpu.serving.backend import StepEntry
from cordum_tpu.serving.engine import GenRequest, ServingEngine
from cordum_tpu.serving.modelspec import UnsupportedForModel, spec_for
# the afmoe family's hand-driven rows (whole-row pages and a ring a row), its feed and its
# tolerance serve this family as they are: the two kinds of page are the same
from tests.test_afmoe_serving import GAP, PS, Rows, backend_for, feed, run_blocking

#: the published numbers of both kinds (``rope_parameters`` of the catalog row)
PUBLISHED_FULL = dict(theta=500000.0, factor=16.0, original_len=8192, beta_fast=32.0,
                      beta_slow=1.0, attention_factor=1.2772588722239782)


def tiny(**kw):
    """Four layers (s s s f), a window of 32, YaRN over an original context of
    64 on the full kind: with 8 frequencies a head that is ``low`` 0 and
    ``high`` 3, so frequency 0 is extrapolated, 1 and 2 blended, 3-7
    interpolated, and every row of the tests is longer than 64."""
    base = dict(vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_expert=32,
                n_layers=4, layer_types=(mellum.SLIDING,) * 3 + (mellum.FULL,), window=32,
                n_experts=16, first_expert=0, experts_held=16, top_k=4, max_seq_len=256,
                rope_sliding=mellum.Rotation(theta=10000.0),
                rope_full=mellum.Rotation(theta=10000.0, factor=4.0, original_len=64,
                                          attention_factor=1.1386),
                dtype=jnp.float32)
    base.update(kw)
    return mellum.MellumConfig(**base)


def rope_parameters(cfg):
    s, f = cfg.rope_sliding, cfg.rope_full
    return {"sliding_attention": {"rope_type": "default", "rope_theta": s.theta},
            "full_attention": {"rope_type": "yarn", "rope_theta": f.theta, "factor": f.factor,
                               "original_max_position_embeddings": f.original_len,
                               "beta_fast": f.beta_fast, "beta_slow": f.beta_slow,
                               "attention_factor": f.attention_factor}}


def doc_of(cfg):
    """The configuration-file keys the reference reads, from a program config."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.norm_eps, "layer_types": list(cfg.layer_types),
            "sliding_window": cfg.window, "num_experts_per_tok": cfg.top_k,
            "norm_topk_prob": cfg.route_norm, "first_expert": cfg.first_expert,
            "rope_parameters": rope_parameters(cfg)}


def gaps(cfg, params, seq, preds):
    """Reference's best logit minus its logit of the program's prediction
    after every position of ``seq``."""
    ref = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len)
    top, _, got = ref.logits_of(params, seq, [int(t) for t in preds])
    return top - got


@pytest.mark.parametrize("case", ["chunks-straddle-the-edge", "one-token-chunks-then-decode",
                                  "short-and-long-rows-in-one-step"])
def test_prefill_in_chunks_then_decode_through_both_kinds_of_page_equal_the_reference(case):
    """Every row but the short ones is longer than its ring (it wraps, more
    than once) and than YaRN's original context of 64, so the full layer's
    three bands and the window layers' plain table are all compared, at
    positions on both sides of 64."""
    cfg = tiny()
    params = mellum.init_params(jax.random.PRNGKey(3), cfg)
    be = backend_for(cfg, params)
    assert be.window == 32
    rng = np.random.default_rng(5)
    if case == "short-and-long-rows-in-one-step":
        lens, chunks = [150, 9, 70, 33], [[6, 3, 6, 2] * 6, [3], [5] * 9, [1, 4, 4]]
    elif case == "chunks-straddle-the-edge":
        lens, chunks = [170], [[12, 7, 12, 5, 12, 12, 3, 12, 12, 9, 12, 12, 12]]
    else:
        lens, chunks = [120], [[1] * 40]
    assert max(lens) > be.ring_pages * PS > cfg.window  # the longest row laps its ring
    seqs = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in lens]
    preds = feed(be, Rows(be, len(seqs)), seqs, chunks)
    assert be.compiled_programs() == 1
    assert [a.shape[0] for a in be._arenas] == [1, 1, 3, 3]  # full K, V; window K, V
    assert be.kernels == {"walk": "", "ring": "", "expert": ""}  # the CPU holds the jax.numpy forms
    for seq, p in zip(seqs, preds):
        assert len(p) == len(seq)
        g = gaps(cfg, params, seq, p)
        assert g.max() < GAP, (case, float(g.max()), int(g.argmax()))


@pytest.mark.parametrize("left_out", ["yarn", "attention_factor", "window-rope", "q-norm"])
def test_nothing_of_the_rotations_can_be_left_out_unseen(left_out):
    """A program that rotates its full layers plainly, drops YaRN's factor on
    cos and sin, rotates its window layers under the full kind's table, or
    skips the per-head norm of q, is told from the reference by the same
    comparison that passes the sound one."""
    cfg = tiny()
    params = mellum.init_params(jax.random.PRNGKey(3), cfg)
    if left_out == "yarn":
        broken = dataclasses.replace(cfg, rope_full=dataclasses.replace(cfg.rope_full, factor=1.0))
    elif left_out == "attention_factor":
        broken = dataclasses.replace(
            cfg, rope_full=dataclasses.replace(cfg.rope_full, attention_factor=1.0))
    elif left_out == "window-rope":
        broken = dataclasses.replace(cfg, rope_sliding=cfg.rope_full)
    else:
        broken = cfg
        params = {**params, "layers": [{**ly, "q_norm": ly["q_norm"] * 1.5}
                                       for ly in params["layers"]]}
    be = backend_for(broken, params)
    seq = [int(t) for t in np.random.default_rng(9).integers(0, cfg.vocab_size, 140)]
    (preds,) = feed(be, Rows(be, 1), [seq], [[12] * 9])
    sound = mellum.init_params(jax.random.PRNGKey(3), cfg)
    assert gaps(cfg, sound, seq, preds).max() > 10 * GAP


def closed_form(dim, theta, factor=1.0, original=0, beta_fast=32.0, beta_slow=1.0):
    """ISSUE 46's equations in float64: ``(inv_freq, low, high)``."""
    extrap = theta ** (-2.0 * np.arange(dim // 2) / dim)
    if factor <= 1:
        return extrap, None, None
    corr = lambda r: dim * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))  # noqa: E731
    low, high = max(math.floor(corr(beta_fast)), 0), min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extrap / factor * ramp + extrap * (1 - ramp), low, high


def test_both_tables_equal_the_closed_forms_at_the_published_numbers():
    full, sliding = mellum.Rotation(**PUBLISHED_FULL), mellum.Rotation(theta=500000.0)
    want, low, high = closed_form(128, 500000.0, 16.0, 8192)
    assert (low, high) == (18, 35)  # ISSUE 46: HF's clip to [0, 127] is idle here
    got = np.asarray(full.inv_freq(128), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert np.allclose(got[:19], want[:19]) and np.allclose(got[35:] * 16, closed_form(128, 5e5)[0][35:])
    assert full.attention_factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    np.testing.assert_allclose(np.asarray(sliding.inv_freq(128), np.float64),
                               closed_form(128, 500000.0)[0], rtol=2e-6)
    assert sliding.attention_factor == 1.0
    # the tiny table of these tests holds all three bands
    tiny_full = tiny().rope_full
    _, low, high = closed_form(16, tiny_full.theta, tiny_full.factor, tiny_full.original_len)
    assert (low, high) == (0, 3)
    # the reference makes the same tables from the file's keys, by its own code
    doc = {"head_dim": 128, "rope_parameters": rope_parameters(
        dataclasses.replace(tiny(), rope_full=full, rope_sliding=sliding))}
    inv, ratio = ref_mod.rotation_of(doc, "full_attention")
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-12)
    assert ratio == full.attention_factor and ref_mod.rotation_of(doc, "sliding_attention")[1] == 1.0


def test_the_shared_function_leaves_axk1s_table_as_it_was():
    """``axk1.yarn_inv_freq`` is ``rotary.yarn_inv_freq`` at the latent
    family's rotated dimension: the published A.X-K1 numbers (64 rotated
    dimensions, factor 40 over 4096) against the closed form, and the jax
    array bit for bit the one the shared function returns."""
    cfg = axk1.Axk1Config(rope_dim=64, rope_theta=10000.0, rope_factor=40.0,
                          rope_original_len=4096)
    got = axk1.yarn_inv_freq(cfg)
    want, low, high = closed_form(64, 10000.0, 40.0, 4096)
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=2e-6)
    assert np.array_equal(np.asarray(got), np.asarray(rotary.yarn_inv_freq(64, 10000.0, 40.0, 4096)))
    assert axk1.rotate is rotary.rotate and axk1.yarn_mscale is rotary.yarn_mscale
    plain = axk1.yarn_inv_freq(axk1.Axk1Config(rope_dim=64))
    np.testing.assert_allclose(np.asarray(plain, np.float64), closed_form(64, 10000.0)[0], rtol=2e-6)


def reference_expert_part(cfg, layer, m, first, held):
    """The held experts' weighted terms, by the reference (no shared expert)."""
    sel, w = ref_mod.route(m, layer["router"], top_k=cfg.top_k, norm_topk_prob=cfg.route_norm)
    out = jnp.zeros_like(m)
    for e in range(first, first + held):
        out = out + ref_mod.expert_term(m, sel, w, e, layer["e_gate"][e], layer["e_up"][e],
                                        layer["e_down"][e])
    return out, sel, w


@pytest.mark.parametrize("ranks", [1, 4, 16])
def test_the_shares_add_up_to_the_whole_layer(ranks):
    """The expert layer stays the one that is told which experts it holds:
    the parts ``ranks`` chips' shares give add up to the uncut reference's
    whole layer (nothing is computed on every chip alike: no shared expert),
    a share alone equals the reference given the same share, and the weights
    are normalised over all ``top_k`` selected, held here or not."""
    cfg = tiny()
    layer = mellum.init_params(jax.random.PRNGKey(11), cfg)["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(12), (40, cfg.d_model), jnp.float32)
    live = jnp.ones((40,), bool)
    whole, sel, w = reference_expert_part(cfg, layer, m, 0, cfg.n_experts)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 1.0, rtol=1e-6)  # norm_topk_prob
    assert len(set(np.asarray(sel)[0].tolist())) == cfg.top_k
    held = cfg.n_experts // ranks
    total, seen = 0.0, 0
    for rank in range(ranks):
        c = dataclasses.replace(cfg, first_expert=held * rank, experts_held=held)
        cut = {k: layer[k][held * rank:held * (rank + 1)] for k in ("e_gate", "e_up", "e_down")}
        part, counts = afmoe.expert_layer(m, {**layer, **cut}, c, live)
        alone, _, _ = reference_expert_part(cfg, layer, m, held * rank, held)
        np.testing.assert_allclose(part, alone, atol=1e-4)
        total = total + part
        seen += int(counts.sum())
    np.testing.assert_allclose(total, whole, atol=2e-4)
    assert seen == 40 * cfg.top_k  # every assignment was some chip's


def test_the_specification_states_counters_and_kernels_through_the_seam():
    """``count_aux`` under afmoe's names (the existing readers read them) and
    the ``kernels`` roles ``walk``, ``ring`` (the window kind's walk: a role
    of its own since ISSUE 47, by which the host counts that kind) and
    ``expert``, by each kernel's own ``holds_kernel``: none on the CPU, all on
    one TPU device, the by-head walk's two not over a mesh of more."""
    cfg = tiny()
    spec = spec_for(cfg)
    assert (spec.family, spec.n_arenas, spec.window) == ("mellum", 4, 32)
    assert spec.aux_shape == (cfg.n_layers, cfg.experts_held) and spec.kv_by_head
    assert spec.kv_positional and not spec.kv_whole_row and spec.value_dim == cfg.head_dim
    assert spec.kernels("cpu", 1) == {"walk": "", "ring": "", "expert": ""}
    on_chip = spec.kernels("tpu", 1)
    assert on_chip == {"walk": head_walk.KERNEL_NAME, "ring": head_walk.KERNEL_NAME,
                       "expert": expert_mlp.KERNEL_NAME}
    assert head_walk.holds_kernel("tpu", True, 1) and expert_mlp.holds_kernel(
        "tpu", cfg.d_model, cfg.d_expert, 4)
    assert spec.kernels("tpu", 4) == {"walk": "", "ring": "", "expert": expert_mlp.KERNEL_NAME}
    counts = np.zeros((cfg.n_layers, cfg.experts_held), np.int32)
    counts[0, 3], counts[2, 5], counts[2, 6] = 7, 2, 1
    counters, attrs = spec.count_aux(counts, 5, on_chip)
    assert counters == {"moe_assignments": 5 * cfg.top_k * cfg.n_layers, "moe_assignments_here": 10,
                        "moe_experts_touched": 3, "moe_max_expert_load": 9, "moe_kernel_items": 3}
    assert attrs == {"moe_here": "10", "moe_touched": "3", "expert_kernel": "expert_mlp",
                     "moe_items": "3"}
    counters, attrs = spec.count_aux(counts, 5, spec.kernels("cpu", 1))
    assert "moe_kernel_items" not in counters and attrs["expert_kernel"] == "none"


async def test_engine_serves_mixed_rows_bounded_and_counted(monkeypatch):
    """Through the engine: short and long rows share steps, the window kind's
    pages a session never pass the ring, both allocators stay consistent, the
    family's counters reach ``ServingStats.model`` under afmoe's names, and
    with the whole expert set here every assignment is here.  The ``step``
    span names the ring kind's kernel beside the whole-row kind's (ISSUE 47),
    and where the backend reports the TPU's kernels the host counts each
    kind's tiles by their own ends: fewer rows gathered for the same step."""
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.obs.tracer import Tracer
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.serving import engine as engine_mod

    monkeypatch.setattr(engine_mod, "STEP_SAMPLE_PERIOD_NS", 0)  # every cycle a ``step`` trace
    bus, spans = LoopbackBus(), []

    async def on_span(subject, pkt):
        spans.append(pkt.span)

    await bus.subscribe(subj.TRACE_SPAN, on_span)
    cfg = tiny()
    params = mellum.init_params(jax.random.PRNGKey(7), cfg)
    be = backend_for(cfg, params, max_seqs=3, budget=9, pages=100)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=3, max_new_tokens_cap=64,
                        tracer=Tracer("worker", bus))
    assert eng.prefix is None and eng.tiering is None  # sharing is off for this family
    seen = []
    inner = be.step

    def tapped(entries):
        out = inner(entries)
        seen.append((sum(len(e.tokens) for e in entries), be.last_aux.copy(),
                     max(len(e.window_pages) for e in entries), be.last_window_blocks))
        return out
    be.step = tapped
    rng = np.random.default_rng(16)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in (140, 6, 61, 20)]
    n_new = [40, 12, 30, 8]
    outs = await asyncio.wait_for(asyncio.gather(*(
        eng.submit(GenRequest(prompt=p, max_new_tokens=n, stream=False), job_id=f"j{i}")
        for i, (p, n) in enumerate(zip(prompts, n_new)))), timeout=240)
    # the same first request again, counted as a backend on the TPU counts (the CPU's program
    # still walks as ``jax.numpy``: the count is the host's, by the specification's own rule)
    be.kernels = be.spec.kernels(head_walk.PLATFORM, 1)
    again = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=prompts[0], max_new_tokens=n_new[0], stream=False), job_id="again"),
        timeout=240)
    await eng.stop()
    await bus.drain()
    assert again["tokens"] == outs[0]["tokens"]
    steps = sorted((s for s in spans if s.name == "step"), key=lambda s: s.start_us)
    cpu = [s for s in steps if s.attrs["ring_kernel"] == "none"]
    tpu = [s for s in steps if s.attrs["ring_kernel"] == head_walk.KERNEL_NAME]
    assert cpu and tpu and len(cpu) + len(tpu) == len(steps)
    assert all(s.attrs["walk_kernel"] == s.attrs["ring_kernel"] for s in steps)
    # by the group rule all eight tiles of a group are counted to its longest walk; by the
    # kernels' rule a lone decode row's one tile is counted by its own walk in each kind
    assert all(int(s.attrs["kv_rows"]) % attention.ATTN_GROUP_TILES == 0 for s in cpu)
    lone = [s for s in tpu if s.attrs["live_tokens"] == "1"]
    assert len(lone) >= n_new[0] - 1 and all(
        int(s.attrs["kv_rows"]) == int(s.attrs["kv_blocks"].split("/")[0]) + int(s.attrs["window_blocks"])
        for s in lone)
    prompts, n_new, outs = prompts + [prompts[0]], n_new + [n_new[0]], outs + [again]
    for p, o in zip(prompts, outs):
        seq = p + o["tokens"]
        g = gaps(cfg, params, seq[:-1], seq[1:])[len(p) - 1:]
        assert g.max() < GAP, float(g.max())
    st, ring = eng.stats, be.ring_pages
    assert max(n for _, _, n, _ in seen) <= ring
    assert st.kv_pages_held_window <= 3 * ring < st.kv_pages_held_full
    assert st.window_pages_reused == sum(
        max(0, -(-(len(p) + n - 1) // PS) - ring) for p, n in zip(prompts, n_new)) > 0
    eng.allocator.check_consistency()
    eng.window_allocator.check_consistency()
    assert eng.allocator.used_pages == 0 and eng.window_allocator.used_pages == 0
    assert st.model["moe_assignments"] == sum(t for t, _, _, _ in seen) * cfg.top_k * cfg.n_layers
    assert st.model["moe_assignments_here"] == st.model["moe_assignments"]  # all 16 are here
    assert st.model["moe_experts_touched"] == sum(int((c > 0).sum()) for _, c, _, _ in seen)
    assert st.model["moe_max_expert_load"] == sum(int(c.max(axis=1).sum()) for _, c, _, _ in seen)
    assert st.window_blocks_walked == sum(b for _, _, _, b in seen)
    assert st.attn_blocks_walked > 0


async def test_what_the_family_cannot_do_is_refused_by_its_window():
    """The prefix cache, hibernation, migration and the tensor-parallel gang
    assume one kind of whole-row page: each refuses the family by
    ``ModelSpec.window``, loudly, and none serves it wrong."""
    cfg = tiny()
    spec = spec_for(cfg)
    for require in (spec.require_whole_row, spec.require_page_records):
        with pytest.raises(UnsupportedForModel, match="ModelSpec.window"):
            require("a feature")
    be = backend_for(cfg, None)
    for call in (lambda: be.copy_page(1, 2), lambda: be.export_kv([1], 0, 8),
                 lambda: be.import_kv([1], [{}])):
        with pytest.raises(UnsupportedForModel, match="ModelSpec.window"):
            call()
    with pytest.raises(ValueError, match="window_pages"):  # a ring-less entry is not served
        be.step([StepEntry(tokens=[1], start=0, pages=[1])])
    from cordum_tpu.serving.shard import ShardedServingBackend

    with pytest.raises(UnsupportedForModel, match="ModelSpec.window"):
        ShardedServingBackend(cfg, rank=0, tp=2)
    # asked for by name or not, the engine builds no prefix cache and no tiering over rings
    eng = ServingEngine(be, run_blocking=run_blocking, prefix_cache=True, hibernate_after_s=30.0)
    assert eng.prefix is None and eng.tiering is None and not eng.kv_whole_row
    live = asyncio.ensure_future(eng.submit(
        GenRequest(prompt=[1, 2, 3], max_new_tokens=40, stream=False), job_id="live"))
    while eng.active_sessions() == 0:
        await asyncio.sleep(0.01)
    assert eng.describe_session("live") is None  # never offered for migration
    assert eng.pick_rebalance_sessions(4) == []
    with pytest.raises(UnsupportedForModel, match="ModelSpec.window"):
        await eng.hibernate_session("live")
    with pytest.raises(UnsupportedForModel, match="ModelSpec.window"):
        await eng.export_pages("live", 0, 8)
    with pytest.raises(UnsupportedForModel, match="ModelSpec.window"):
        await eng.install_session(GenRequest(prompt=[1]), job_id="x", state={}, records=[])
    assert len((await asyncio.wait_for(live, timeout=120))["tokens"]) == 40
    await eng.stop()
