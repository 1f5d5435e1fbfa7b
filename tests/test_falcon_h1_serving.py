"""The Falcon-H1 family on the serving path (ISSUE 42): a Mamba-2 state-space
mixer and grouped-query attention side by side in EVERY layer, so a session
holds K/V pages and a recurrent state at once; the slot's life in the engine,
and that nothing of the mathematics (the grouped norm, a multiplier, the
convolution's bias) can be left out unseen.  What a model with state refuses
is ``tests/test_bailing_serving.py``'s, a case a family.

The oracle is the benchmark's plain float32 reference
(``benchmarks/families/falcon_h1_reference.py``: the whole forward pass over a
whole sequence, the recurrence a plain scan from a zero state, no cache; it
imports nothing of the program); the program runs in float32 here, so its
choice at every position is held to the REFERENCE'S logits: the reference's
best logit minus its logit of the program's token is 0 up to rounding."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import falcon_h1_reference as ref_mod
from cordum_tpu.models import attention, falcon_h1, kda, llama, ssd
from cordum_tpu.serving.backend import ServingBackend, StepEntry
from cordum_tpu.serving.engine import GenRequest, ServingEngine
from tests.test_bailing_serving import (ROW_TABLES, SLOTS, T_BUF, entry, holds_the_jnp_form,
                                        rows_of, run_blocking)

# float32 program against the float32 "highest" reference, logits of spread
# about 0.5 here: both round at 1e-7 relative, the recurrence and the softmax
# carry that through a hundred positions and two layers: 1e-4 holds tenfold
GAP = 1e-4
PS = 8
#: the published multipliers' kind: none is 1 but the attention's input (as published)
MULTIPLIERS = dict(
    embedding_multiplier=5.66, lm_head_multiplier=1 / 128, attention_in_multiplier=1.0,
    attention_out_multiplier=0.0375, key_multiplier=0.011, ssm_in_multiplier=0.25,
    ssm_multipliers=(0.354, 0.25, 0.177, 0.5, 0.354), ssm_out_multiplier=0.0884,
    mlp_multipliers=(0.177, 0.0112))


def tiny(**kw):
    base = dict(vocab_size=96, d_model=64, n_layers=2, n_heads=5, n_kv_heads=1, head_dim=16,
                d_ff=128, ssm_heads=8, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
                max_seq_len=256, dtype=jnp.float32, **MULTIPLIERS)
    base.update(kw)
    return falcon_h1.FalconH1Config(**base)


def doc_of(cfg):
    """The configuration-file keys the reference reads, from a program config."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "mamba_d_ssm": cfg.d_ssm, "mamba_n_heads": cfg.ssm_heads,
            "mamba_d_head": cfg.ssm_head_dim, "mamba_d_state": cfg.ssm_state,
            "mamba_n_groups": cfg.ssm_groups,
            "embedding_multiplier": cfg.embedding_multiplier,
            "lm_head_multiplier": cfg.lm_head_multiplier,
            "attention_in_multiplier": cfg.attention_in_multiplier,
            "attention_out_multiplier": cfg.attention_out_multiplier,
            "key_multiplier": cfg.key_multiplier, "ssm_in_multiplier": cfg.ssm_in_multiplier,
            "ssm_multipliers": list(cfg.ssm_multipliers),
            "ssm_out_multiplier": cfg.ssm_out_multiplier,
            "mlp_multipliers": list(cfg.mlp_multipliers)}


def seeded(cfg, key=3):
    """Seeded weights with every gain and the skip off 1, so that leaving one
    out shows."""
    params = falcon_h1.init_params(jax.random.PRNGKey(key), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(key + 1), 8 * cfg.n_layers + 1))
    wobble = lambda w: w * (1.0 + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype))  # noqa: E731
    layers = [{**w, **{k: wobble(w[k]) for k in ("norm_in", "norm_ff", "ssm_norm", "d_skip")}}
              for w in params["layers"]]
    return {**params, "layers": layers, "final_norm": wobble(params["final_norm"])}


def backend_for(cfg, params, *, max_seqs=4, budget=12, pages=160):
    return ServingBackend(cfg, num_pages=pages, page_size=PS, max_seqs=max_seqs,
                          max_batch_tokens=max_seqs + budget, params=params)


def gaps(cfg, params, seq, preds):
    ref = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len)
    top, _, got = ref.logits_of(params, seq, [int(t) for t in preds])
    return top - got


def feed(be, seqs, chunks, slots=None):
    """Teacher-force ``seqs`` through the state slots and the K/V pages:
    ``chunks[i]`` are the chunk lengths of row i's prefill; what is left
    decodes one token a step, all rows riding the same steps.  Returns each
    row's prediction after every position."""
    preds = [[] for _ in seqs]
    fed = [0] * len(seqs)
    plans = [list(c) for c in chunks]
    while any(f < len(s) for f, s in zip(fed, seqs)):
        entries, who = [], []
        for i, seq in enumerate(seqs):
            if fed[i] >= len(seq):
                continue
            n = min(plans[i].pop(0) if plans[i] else 1, len(seq) - fed[i])
            entries.append(entry(be, i, seq[fed[i]:fed[i] + n], fed[i],
                                 slot=None if slots is None else slots[i]))
            who.append((i, n))
        for (i, n), out in zip(who, be.step(entries)):
            preds[i].extend(out if isinstance(out, list) else [out])
            fed[i] += n
    return preds


def random_seqs(cfg, lens, seed=5):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in lens]


@pytest.mark.parametrize("case", ["short-and-long-rows-in-one-step", "one-token-chunks-then-decode",
                                  "chunks-straddle-pages", "a-dirty-slot"])
def test_chunked_prefill_and_decode_through_slots_and_pages_equal_the_reference(case):
    """Chunked prefill then decode, every layer's mixer through its state slot
    and its attention through the pages, equals the reference's ONE forward
    pass (logits: the reference's own best against the program's pick)."""
    cfg = tiny()
    params = seeded(cfg)
    be = backend_for(cfg, params)
    slots = None
    if case == "short-and-long-rows-in-one-step":
        lens, chunks = [90, 9, 50, 33], [[6, 3, 6, 2] * 4, [3], [5] * 6, [1, 4, 4]]
    elif case == "chunks-straddle-pages":
        lens, chunks = [100], [[12, 7, 12, 5, 12, 12, 3, 12, 9]]
    elif case == "a-dirty-slot":
        # another row's state and tail are left in slot 2 and pages 1..; the
        # rows admitted behind it start from zeros by their positions alone
        feed(be, random_seqs(cfg, [40], seed=9), [[7] * 4], slots=[2])
        lens, chunks, slots = [30, 60], [[4, 9], [11, 5, 11]], [2, 4]
    else:
        lens, chunks = [70], [[1] * 30]
    seqs = random_seqs(cfg, lens)
    preds = feed(be, seqs, chunks, slots)
    assert be.compiled_programs() == 1
    # K and V by head in every layer, then the state and the tails in SLOTS
    assert [a.shape for a in be._arenas] == [
        (2, 160, PS, 1, 16), (2, 160, PS, 1, 16),
        (2, 5, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim), (2, 5, 3, cfg.conv_dim)]
    assert be._arenas[2].dtype == jnp.float32  # the state, whatever the weights' dtype
    assert (be.state_slots, be.kv_positional, be.kv_by_head, be.kv_whole_row) == (
        5, False, True, True)
    assert be.state_bytes == 2 * (16 * 8 * 8 * 4 + 3 * cfg.conv_dim * 4)
    assert be.page_bytes == 2 * 2 * PS * 16 * 4
    assert be.kernels == {"walk": "", "state": ""}  # the CPU holds the jax.numpy forms
    for seq, p in zip(seqs, preds):
        assert len(p) == len(seq)
        g = gaps(cfg, params, seq, p)
        assert g.max() < GAP, (case, float(g.max()), int(g.argmax()))


#: what can be left out of the program, and what the program then is
LEFT_OUT = {
    "embedding_multiplier": dict(embedding_multiplier=1.0),
    "attention_in_multiplier": dict(attention_in_multiplier=1.0),
    "attention_out_multiplier": dict(attention_out_multiplier=1.0),
    "key_multiplier": dict(key_multiplier=1.0),
    "ssm_in_multiplier": dict(ssm_in_multiplier=1.0),
    "ssm_out_multiplier": dict(ssm_out_multiplier=1.0),
    "mlp_gate_multiplier": dict(mlp_multipliers=(1.0, MULTIPLIERS["mlp_multipliers"][1])),
    "mlp_out_multiplier": dict(mlp_multipliers=(MULTIPLIERS["mlp_multipliers"][0], 1.0)),
    **{f"ssm_multipliers[{i}]-{span}": dict(ssm_multipliers=tuple(
        1.0 if j == i else m for j, m in enumerate(MULTIPLIERS["ssm_multipliers"])))
       for i, span in enumerate("zxBCt")},
}


@pytest.mark.parametrize("what", [*LEFT_OUT, "grouped-norm", "conv-bias", "skip-D", "nothing"])
def test_nothing_of_the_mathematics_can_be_left_out_unseen(what, monkeypatch):
    """The program with one multiplier at 1, the norm over all channels at
    once, no convolution bias or no skip is NOT the reference's model: its
    picks fall under the reference's best by far more than rounding.  (The
    attention's input multiplier is 1 as published; here it is 0.5, so that
    leaving it out shows.)"""
    true = tiny(attention_in_multiplier=0.5)
    params = seeded(true)
    cfg, served = true, params
    if what in LEFT_OUT:
        cfg = dataclasses.replace(true, **LEFT_OUT[what])
    elif what == "grouped-norm":
        one_group = ssd.group_norm
        monkeypatch.setattr(ssd, "group_norm", lambda y, w, groups, eps: one_group(y, w, 1, eps))
    elif what in ("conv-bias", "skip-D"):
        name = "conv_b" if what == "conv-bias" else "d_skip"
        served = {**params, "layers": [{**w, name: jnp.zeros_like(w[name])}
                                       for w in params["layers"]]}
    seqs = random_seqs(true, [48])
    preds = feed(backend_for(cfg, served), seqs, [[9, 7, 9]])
    worst = float(gaps(true, params, seqs[0], preds[0]).max())
    if what == "nothing":
        assert worst < GAP
    else:
        assert worst > 50 * GAP, (what, worst)


def test_the_logits_multiplier_scales_the_logits_and_never_the_pick():
    """``lm_head_multiplier`` is positive, so the served token (an argmax) is
    the same with it left out; what holds it is the logits' size: the
    reference's, which the benchmark's gaps are measured in."""
    cfg = tiny()
    params = seeded(cfg)
    seqs = random_seqs(cfg, [40])
    with_it = feed(backend_for(cfg, params), seqs, [[8] * 5])
    without = feed(backend_for(dataclasses.replace(cfg, lm_head_multiplier=1.0), params),
                   seqs, [[8] * 5])
    assert with_it == without
    top, _, _ = ref_mod.Reference(doc_of(cfg), 256).logits_of(params, seqs[0], seqs[0])
    top1, _, _ = ref_mod.Reference(doc_of(dataclasses.replace(cfg, lm_head_multiplier=1.0)),
                                   256).logits_of(params, seqs[0], seqs[0])
    np.testing.assert_allclose(top1 / 128, top, rtol=1e-5)


@pytest.mark.parametrize("control,least,most", [("", 0.0, 1e-5), ("bf16", 1e-3, 1.0)])
def test_a_served_rows_state_is_the_references_scan(control, least, most):
    """What a row's slot holds after chunks and decode steps is the reference's
    state behind the same tokens (float32: to rounding); the benchmark's
    broken program, the state rounded to bfloat16 behind every step, is not."""
    from benchmarks.tests.control_falconh1 import broken

    cfg = tiny()
    params = seeded(cfg)
    seq = random_seqs(cfg, [57])[0]
    with broken(control):
        be = backend_for(cfg, params)
        feed(be, [seq], [[9, 4, 9, 9, 2]], slots=[3])
    got = np.asarray(be._arenas[2][:, 3]).transpose(0, 2, 3, 1)  # [L, heads, d_head, d_state]
    want = ref_mod.Reference(doc_of(cfg), 256).ssm_states(params, seq)
    for g, w in zip(got, want):
        err = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        assert least <= err < most, (control, err)


# ---------------------------------------------------------------------------
# the recurrence alone
# ---------------------------------------------------------------------------


def ssd_inputs(t, h, p, n, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(0.1 * rng.standard_normal((t, h, p)), jnp.float32),
            jnp.asarray(rng.uniform(0.2, 1.0, (t, h)), jnp.float32),
            jnp.asarray(rng.standard_normal((t, h, n)), jnp.float32),
            jnp.asarray(rng.standard_normal((t, h, n)), jnp.float32))


@pytest.mark.parametrize("split", [1, 7, 16, 17, 64])
def test_a_rows_state_and_tail_are_the_same_however_its_tokens_are_split_over_steps(split):
    """64 tokens of one row fed ``split`` at a time through the whole mixer
    (projection, convolution with its tail, recurrence): the state and the
    tail behind them, and every output, are those of ONE pass."""
    cfg = tiny(n_layers=1)
    layer = seeded(cfg)["layers"][0]
    u = jnp.asarray(np.random.default_rng(4).standard_normal((64, cfg.d_model)), jnp.float32)

    def run(step):
        state, tail = falcon_h1.init_state(cfg, 4)
        state = state + 7.0  # a dirty slot: position 0 starts from zeros all the same
        outs = []
        for lo in range(0, 64, step):
            n = min(step, 64 - lo)
            rows, _ = rows_of([(1, n, 2, lo)], 64, 3)
            ub = jnp.zeros((64, cfg.d_model), jnp.float32).at[:n].set(u[lo:lo + n])
            out, state, tail = ssd.mixer(ub, layer, state, tail, 0, rows, cfg)
            outs.append(out[:n])
        return jnp.concatenate(outs), state[0, 2], tail[0, 2]

    one, whole = run(split), run(64)
    for a, b in zip(one, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("table", sorted(ROW_TABLES))
def test_the_kernel_is_the_recurrence_of_the_jnp_form(table):
    """The Pallas kernel against ``rows_jnp`` over the row tables its pipeline
    can get wrong (``tests/test_bailing_serving.py``'s ``ROW_TABLES``, the
    same pipeline under another token body): decode rows and chunks in one
    step, fresh rows, rows that feed nothing, more fed rows than buffers,
    none at all; it never writes a slot no row names."""
    h, p, n = 8, 128, 16
    state = jnp.asarray(np.random.default_rng(2).standard_normal((2, SLOTS, n, h, p)), jnp.float32)
    holds_the_jnp_form(ssd.rows_jnp, ssd.rows_kernel, ssd_inputs(T_BUF, h, p, n, seed=1), state,
                       ROW_TABLES[table])


def test_the_recurrence_is_the_references_scan():
    """``recurrence`` over one row from a zero state against the reference's
    ``ssd_scan`` (a group's B and C under its heads, the skip added outside)."""
    t, h, p, n, g = 20, 8, 8, 16, 2
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((t, h, p)), jnp.float32)
    b, c = (jnp.asarray(rng.standard_normal((t, g, n)), jnp.float32) for _ in range(2))
    dt = jnp.asarray(rng.uniform(0.001, 0.3, (t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    rows, _ = rows_of([(0, t, 1, 0)], t, 2)
    o, state = ssd.recurrence(x, b, c, dt, a, jnp.zeros((1, 3, n, h, p), jnp.float32), 0, rows)
    s_ref, y_ref = ref_mod.ssd_scan(x, b, c, dt, a, jnp.zeros((h,), jnp.float32))
    np.testing.assert_allclose(np.asarray(o), np.asarray(y_ref), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(state[0, 1]).transpose(1, 2, 0), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_walk_at_five_query_heads_a_key_head_is_plain_attention(dtype, tol):
    """``attention.paged_attention`` at 20 / 4 heads (the first odd ratio: a tile's
    8 slots x 5 heads fill no whole multiple of 16 sublanes) against
    ``llama._attention`` over the whole row: a chunk of 21 slots at depth 30
    and two decode rows, K and V read through the pages."""
    kvh, rep, hd, ps, pages = 4, 5, 16, 4, 40
    cfg = llama.LlamaConfig(d_model=kvh * rep * hd, n_heads=kvh * rep, n_kv_heads=kvh)
    rng = np.random.default_rng(8)
    lens = [51, 17, 9]  # each row's length; its last 21 / 1 / 1 positions are fed
    fed = [21, 1, 1]
    k_all = [jnp.asarray(rng.standard_normal((n, kvh, hd)), dtype) for n in lens]
    v_all = [jnp.asarray(rng.standard_normal((n, kvh, hd)), dtype) for n in lens]
    q_all = [jnp.asarray(rng.standard_normal((n, kvh * rep, hd)), dtype) for n in lens]
    k_pages = jnp.zeros((1, pages, ps, kvh, hd), dtype)
    v_pages = jnp.zeros((1, pages, ps, kvh, hd), dtype)
    tables = np.zeros((4, 13), np.int32)
    at = 1
    for i, n in enumerate(lens):
        ids = np.arange(at, at + -(-n // ps))
        tables[i, :len(ids)] = ids
        at += len(ids)
        where = (0, ids[np.arange(n) // ps], np.arange(n) % ps)
        k_pages, v_pages = k_pages.at[where].set(k_all[i]), v_pages.at[where].set(v_all[i])
    q = jnp.concatenate([qa[n - f:] for qa, n, f in zip(q_all, lens, fed)])
    token_seq = np.repeat(np.arange(3), fed).astype(np.int32)
    positions = np.concatenate([np.arange(n - f, n) for n, f in zip(lens, fed)]).astype(np.int32)
    got = attention.paged_attention(q, k_pages, v_pages, 0, jnp.asarray(tables),
                                jnp.asarray(token_seq), jnp.asarray(positions), 2)
    want = jnp.concatenate([
        llama._attention(qa[None], ka[None], va[None], cfg)[0, n - f:]
        for qa, ka, va, n, f in zip(q_all, k_all, v_all, lens, fed)])
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def test_eighty_sessions_turn_over_sixteen_slots_without_a_stale_state():
    """Eighty requests through an engine of sixteen sessions: every served
    token is the reference's best at its position (a stale state or tail in a
    reused slot would not be), a session holds a slot from admission to
    retirement, and none is left held; the family's counters reach the stats
    through ``count_aux`` alone."""
    cfg = tiny()
    params = seeded(cfg)
    be = backend_for(cfg, params, max_seqs=16, budget=24, pages=16 * 8 + 1)
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, rng.integers(3, 40))]
               for _ in range(80)]

    async def drive():
        eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=16, max_new_tokens_cap=8)
        assert eng.prefix is None and eng.tiering is None and not eng.kv_positional
        assert not eng.speculative and eng.state_allocator.capacity == 16
        outs = await asyncio.gather(*(
            eng.submit(GenRequest(prompt=p, max_new_tokens=5, stream=False), job_id=f"j{i}")
            for i, p in enumerate(prompts)))
        await eng.stop()
        return eng, outs

    eng, outs = asyncio.run(drive())
    ref = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len)
    for p, out in zip(prompts, outs):
        assert len(out["tokens"]) == 5
        seq = p + out["tokens"]
        top, _, got = ref.logits_of(params, seq[:-1], seq[1:])
        assert (top - got)[len(p) - 1:].max() < GAP
    st = eng.stats
    assert st.state_slots_peak == 16 and eng.state_allocator.used == 0
    eng.state_allocator.check_consistency()
    eng.allocator.check_consistency()
    # the program's own counters (a layer's): every fed token went through the
    # scan, every session started from zeros exactly once
    counted = st.model  # under the family's own names (``falcon_h1.step_counters``)
    assert counted["state_tokens_scanned"] == st.prefill_tokens + st.decoded_tokens - 80
    assert counted["state_tokens_scanned"] == st.state_chunk_tokens + st.state_decode_rows
    assert counted["state_rows_fresh"] == 80 and counted["state_rows_advanced"] == st.occupancy_sum
    assert set(counted) == {"state_rows_advanced", "state_tokens_scanned", "state_rows_fresh",
                            "state_rows_prefetched"}  # no other family's counter
    assert counted["moe_assignments"] == 0 and st.prefix_hits == 0 and st.drafted_tokens == 0


async def test_the_step_span_and_the_startup_record_say_how_the_recurrence_engages(monkeypatch):
    """``StepBackend.kernels``' ``state`` role, the ``step`` span's
    ``state_kernel`` / ``state_fresh`` / ``state_prefetched`` / ``state_rows``
    / ``state_tokens`` and the ``startup.kernels`` phase: ``none`` on the CPU (the arenas'
    platform holds the ``jax.numpy`` form), the kernel's name under a backend
    that reports it (as one on the TPU does); no expert layer's attribute.
    ``state_prefetched`` counts the rows the kernel's pipeline reads ahead:
    none while one session decodes alone, the carried rows behind the step's
    first fed row once two decode side by side."""
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.obs import startup
    from cordum_tpu.obs.tracer import Tracer
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.serving import engine as engine_mod
    from cordum_tpu.serving.backend import StepBackend

    assert StepBackend.kernels == {} and not hasattr(StepBackend, "state_kernel")
    monkeypatch.setattr(engine_mod, "STEP_SAMPLE_PERIOD_NS", 0)  # every cycle a ``step`` trace
    cfg = tiny(n_layers=1)
    metrics, bus, spans = Metrics(), LoopbackBus(), []

    async def on_span(subject, pkt):
        spans.append(pkt.span)

    await bus.subscribe(subj.TRACE_SPAN, on_span)
    be = ServingBackend(cfg, num_pages=97, page_size=PS, max_seqs=3, max_batch_tokens=3 + 9,
                        params=seeded(cfg), metrics=metrics)
    eng = ServingEngine(be, run_blocking=run_blocking, tracer=Tracer("worker", bus),
                        max_sessions=3, max_new_tokens_cap=16)
    eng.worker_id = "w-s"

    async def generate(job):
        return await asyncio.wait_for(eng.submit(
            GenRequest(prompt=list(range(1, 20)), max_new_tokens=4, stream=False), job_id=job,
            trace_id=f"tr-{job}", parent_span_id=f"ex-{job}"), timeout=240)

    await generate("a")
    assert be.kernels == {"walk": "", "state": ""}  # no expert layer: no such role
    phase = [p for p in startup.phases() if p.name == "startup.kernels"]
    # K and V by head: the walk's kernel is asked for too, and the CPU holds neither
    assert len(phase) == 1 and phase[0].attrs == {"walk": "none", "state": "none"}
    assert [p.name for p in startup.phases() if p.id == phase[0].parent] == ["startup.state"]
    assert not [p for p in startup.phases() if p.name.endswith("_kernel")]
    # as a backend whose arenas live on the TPU reports, by the specification's own rule
    be.kernels = {**be.kernels, "state": be.spec.kernels(ssd.PLATFORM, 1)["state"]}
    await generate("b")
    assert eng.stats.model["state_rows_prefetched"] == 0  # a step of one fed row reads nothing ahead
    await asyncio.gather(generate("c"), generate("d"))
    await eng.stop()
    await bus.drain()
    steps = sorted((s for s in spans if s.name == "step"), key=lambda s: s.start_us)
    assert steps and all({"state_kernel", "state_fresh", "state_prefetched", "state_rows",
                          "state_tokens"} <= set(s.attrs)
                         and "moe_here" not in s.attrs for s in steps)
    assert {s.attrs["state_kernel"] for s in steps} == {"none", ssd.KERNEL_NAME}
    assert sum(int(s.attrs["state_fresh"]) for s in steps) == 4 == eng.stats.model["state_rows_fresh"]
    ahead = [int(s.attrs["state_prefetched"]) for s in steps]
    assert sum(ahead) == eng.stats.model["state_rows_prefetched"] > 0
    for s, n in zip(steps, ahead):  # the carried rows, less the first fed row where it is one
        carried = int(s.attrs["state_rows"]) - int(s.attrs["state_fresh"])
        assert max(carried - 1, 0) <= n <= carried and n < int(s.attrs["state_rows"])
    assert ssd.holds_kernel("tpu") and not ssd.holds_kernel("cpu")
