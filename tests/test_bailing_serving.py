"""The Bailing-hybrid family on the serving path (ISSUE 40): Kimi-delta
linear-attention layers whose recurrent state lives in per-session SLOTS
beside the latent pages of the family's latent-attention layers, the slot's
life in the engine, the expert-parallel share, and what a model with state
refuses.

The oracle is the benchmark's plain float32 reference
(``benchmarks/families/bailing_reference.py``: the recurrence token by token
from a zero state, K and V of the latent layer by head, no cache; it imports
nothing of the program); the program runs in float32 here, so its choice at
every position is held to the REFERENCE'S logits: the reference's best logit
minus its logit of the program's token is 0 up to rounding."""
import asyncio
import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import bailing_reference as ref_mod
from cordum_tpu.models import afmoe, bailing, kda
from cordum_tpu.serving.backend import ServingBackend, StepEntry
from cordum_tpu.serving.engine import GenRequest, ServingEngine
from cordum_tpu.serving.modelspec import UnsupportedForModel, spec_for

GAP = 2e-3  # float32 program against float32 "highest" reference, logits of size ~1
PS = 8
KEPT = (1, 4, 5)  # published indices under layer_group_size 6, two leading dense layers


def tiny(**kw):
    base = dict(vocab_size=96, d_model=64, n_heads=4, kda_dk=16, kda_dv=16, kv_rank=16,
                nope_dim=16, rope_dim=8, v_dim=16, d_ff=128, d_expert=32,
                layer_kinds=("kda", "kda", "mla"), dense_layers=(0,), n_experts=16,
                first_expert=0, experts_held=16, top_k=4, n_group=4, topk_group=2,
                max_seq_len=256, dtype=jnp.float32)
    base.update(kw)
    return bailing.BailingConfig(**base)


def doc_of(cfg, kept=KEPT):
    """The configuration-file keys the reference reads, from a program config."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "head_dim": cfg.kda_dk, "kda_lower_bound": cfg.kda_lower_bound,
            "kv_lora_rank": cfg.kv_rank, "qk_nope_head_dim": cfg.nope_dim,
            "qk_rope_head_dim": cfg.rope_dim, "v_head_dim": cfg.v_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "num_experts_per_tok": cfg.top_k, "n_group": cfg.n_group,
            "topk_group": cfg.topk_group, "routed_scaling_factor": cfg.route_scale,
            "norm_topk_prob": cfg.route_norm, "first_expert": cfg.first_expert,
            "kept_layers": list(kept), "layer_group_size": 6, "first_k_dense_replace": 2}


def backend_for(cfg, params, *, max_seqs=4, budget=12, pages=160):
    return ServingBackend(cfg, num_pages=pages, page_size=PS, max_seqs=max_seqs,
                          max_batch_tokens=max_seqs + budget, params=params)


def gaps(cfg, params, seq, preds):
    ref = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len)
    top, _, got = ref.logits_of(params, seq, [int(t) for t in preds])
    return top - got


def entry(be, i, tokens, start, slot=None):
    per = be.pages_per_seq
    return StepEntry(tokens=list(tokens), start=start,
                     pages=list(range(1 + i * per, 1 + (i + 1) * per)),
                     sample=True, draft=len(tokens) - 1,
                     state_slot=1 + i if slot is None else slot)


def feed(be, seqs, chunks):
    """Teacher-force ``seqs`` through the state slots and the latent pages:
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
            entries.append(entry(be, i, seq[fed[i]:fed[i] + n], fed[i]))
            who.append((i, n))
        for (i, n), out in zip(who, be.step(entries)):
            preds[i].extend(out if isinstance(out, list) else [out])
            fed[i] += n
    return preds


@pytest.mark.parametrize("case", ["short-and-long-rows-in-one-step", "one-token-chunks-then-decode",
                                  "chunks-straddle-pages", "a-share-of-the-experts"])
def test_chunked_prefill_and_decode_through_slots_and_pages_equal_the_reference(case):
    """Chunked prefill then decode, the KDA layers through their state slots
    and the latent layer through its pages, equals the reference's full
    forward (logits: the reference's own best against the program's pick)."""
    cfg = tiny()
    rng = np.random.default_rng(5)
    if case == "short-and-long-rows-in-one-step":
        lens, chunks = [90, 9, 50, 33], [[6, 3, 6, 2] * 4, [3], [5] * 6, [1, 4, 4]]
    elif case == "chunks-straddle-pages":
        lens, chunks = [100], [[12, 7, 12, 5, 12, 12, 3, 12, 9]]
    elif case == "a-share-of-the-experts":
        cfg = tiny(first_expert=4, experts_held=6)
        lens, chunks = [60, 20], [[10] * 4, [5, 6]]
    else:
        lens, chunks = [70], [[1] * 30]
    params = bailing.init_params(jax.random.PRNGKey(3), cfg)
    be = backend_for(cfg, params)
    seqs = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in lens]
    preds = feed(be, seqs, chunks)
    assert be.compiled_programs() == 1
    # ONE latent arena (a row a latent layer), then the state and the tails in SLOTS
    assert [a.shape for a in be._arenas] == [
        (1, 160, PS, cfg.latent_width), (2, 5, cfg.kda_dk, cfg.n_heads, cfg.kda_dv),
        (2, 5, cfg.conv_width - 1, 3 * cfg.n_heads * cfg.kda_dk)]
    assert be._arenas[1].dtype == jnp.float32  # the state, whatever the weights' dtype
    assert (be.state_slots, be.kv_positional, be.kv_by_head) == (5, False, False)
    assert be.state_bytes == 2 * (16 * 4 * 16 * 4 + 3 * 192 * 4)
    assert be.page_bytes == PS * cfg.latent_width * 4
    for seq, p in zip(seqs, preds):
        assert len(p) == len(seq)
        g = gaps(cfg, params, seq, p)
        assert g.max() < GAP, (case, float(g.max()), int(g.argmax()))


@pytest.mark.parametrize("control,least", [("zero", 50 * GAP), ("bf16", 2 * GAP)])
def test_a_stale_or_rounded_state_is_told_from_the_sound_one(control, least):
    """The two broken programs of the benchmark's check, built where they
    are used (``benchmarks/tests/control_bailing.py`` ``broken``; the program
    has no such option): a state zeroed at every step decodes other tokens
    than the reference's by a random token's margin, one kept in bfloat16 by
    a rounding's (over 90 tokens; it grows with the row)."""
    from benchmarks.tests.control_bailing import broken

    cfg = tiny()
    params = bailing.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(5)
    seq = [int(t) for t in rng.integers(0, cfg.vocab_size, 90)]
    with broken(control):
        (p,) = feed(backend_for(cfg, params), [seq], [[6, 3, 6, 2] * 4])
    assert kda.state_rows.__module__ == kda.kda_sublayer.__module__ == kda.__name__  # put back
    assert gaps(cfg, params, seq, p).max() > least


@pytest.mark.parametrize("control,least,most", [("", 0.0, 1e-5), ("bf16", 1e-3, 1.0)])
def test_a_served_rows_state_is_the_references_scan(control, least, most):
    """The number the served tokens cannot show on the chip, where the
    routing's near-ties mask a rounded state (``control_bailing.py state``):
    the state a row's slot holds after chunks and decode steps against the
    reference's scan over the same tokens, ``|S - S_ref| / |S_ref|`` a KDA
    layer.  Float32 here: the sound program is the scan up to rounding, the
    one that keeps its state in bfloat16 a hundred times further."""
    from benchmarks.tests.control_bailing import broken, served_state

    cfg = tiny()
    params = bailing.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(7)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 70)]
    with broken(control):
        got, fed = served_state(cfg, params, {"page_size": PS, "max_sessions": 4,
                                              "prefill_budget": 12}, prompt, 20)
    assert fed[:70] == prompt and len(fed) == 90 and got.shape == (2, 4, 16, 16)
    want = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len).kda_states(params, fed)
    err = max(float(np.linalg.norm(g - w) / np.linalg.norm(w)) for g, w in zip(got, want))
    assert least <= err < most, err


def kda_inputs(t, h, dk, dv, g_fixed=None, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))  # noqa: E731
    q, k, v = f(t, h, dk), f(t, h, dk), f(t, h, dv)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 * jax.nn.sigmoid(f(t, h, dk)) if g_fixed is None else jnp.full((t, h, dk), g_fixed)
    beta = jax.nn.sigmoid(f(t, h, 1))
    return q, k, beta * k, jnp.exp(g), v


def run_split(split, q, k, kb, eg, v, slot=2, slots=4, form=kda.rows_jnp):
    """A row's ``len(q)`` tokens through ``form`` in steps of ``split``
    tokens, positions from 0, from a slot that held garbage."""
    t, h, dk = q.shape
    dv = v.shape[2]
    state = jnp.full((1, slots, dk, h, dv), 7.0, jnp.float32)  # a reused slot's leavings
    out, at = [], 0
    while at < t:
        n = min(split, t - at)
        pos = jnp.arange(at, at + n, dtype=jnp.int32)
        rows = kda.state_rows(pos, jnp.zeros((n,), jnp.int32), jnp.asarray([slot, 0], jnp.int32))
        o, state = form(q[at:at + n], k[at:at + n], kb[at:at + n], eg[at:at + n], v[at:at + n],
                        state, 0, rows)
        out.append(o)
        at += n
    return jnp.concatenate(out), state[0, slot]


@pytest.mark.parametrize("split", [1, 7, 16, 17, 64])
def test_a_rows_state_is_the_same_however_its_tokens_are_split_over_steps(split):
    """64 tokens with the log-decay pinned AT the bound (g = -5 in every
    channel: a factorised chunk form passes float32 after 18 of them), fed
    1, 7, 16, 17 or 64 a step: the same outputs and the same final state as
    the reference's scan from zero, nothing inf or nan; and a random decay
    beside it."""
    for g_fixed in (-5.0, None):
        q, k, kb, eg, v = kda_inputs(64, 4, 16, 16, g_fixed)
        beta = kb[..., :1] / jnp.where(k[..., :1] == 0, 1, k[..., :1])
        want_s, want = ref_mod.kda_scan(q, k, v, jnp.log(eg), beta[..., 0])
        o, s = run_split(split, q, k, kb, eg, v)
        assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
        np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=1e-5, atol=1e-6)
        # the slot's layout is [d_k, heads, d_v], the reference's [heads, d_k, d_v]
        np.testing.assert_allclose(np.asarray(s).transpose(1, 0, 2), np.asarray(want_s),
                                   rtol=1e-5, atol=1e-6)
        o64, s64 = run_split(64, q, k, kb, eg, v)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s64), rtol=1e-5, atol=1e-7)


#: the row tables the kernel's pipeline can get wrong, over five table rows and
#: the padding row, seven slots, 24 buffer slots: a fed row is (table row,
#: tokens, slot, first position)
ROW_TABLES = {
    "decode_rows_and_chunks": [(0, 7, 3, 0), (1, 1, 5, 11), (3, 9, 2, 4), (4, 1, 6, 0)],
    "one_fed_row": [(2, 3, 4, 9)],
    "empty_rows_before_between_behind": [(1, 2, 3, 5), (3, 1, 6, 2)],
    "fresh_between_carried": [(0, 1, 1, 8), (1, 4, 2, 0), (2, 1, 3, 3)],
    "last_table_row_fed": [(0, 1, 1, 3), (4, 5, 2, 6)],
    "every_row_fed": [(0, 1, 1, 4), (1, 2, 2, 0), (2, 1, 3, 7), (3, 3, 4, 2), (4, 1, 5, 9)],
    "every_row_fresh": [(0, 2, 6, 0), (1, 1, 5, 0), (2, 3, 4, 0), (3, 1, 3, 0), (4, 1, 2, 0)],
    "no_row_fed": [],
    "chunk_between_decode_rows": [(0, 1, 1, 20), (1, 1, 2, 31), (2, 17, 3, 5), (3, 1, 4, 12),
                                  (4, 1, 5, 2)],
}
T_BUF, S_ROWS, SLOTS = 24, 5, 7


def rows_of(plan, t=T_BUF, s_rows=S_ROWS):
    """``StateRows`` of a step and its live buffer slots: ``plan`` = (table
    row, tokens, slot, first position) a fed row."""
    token_seq, positions = np.full(t, s_rows, np.int32), np.zeros(t, np.int32)
    slot_of = np.zeros(s_rows + 1, np.int32)
    at = 0
    for row, n, slot, start in plan:
        token_seq[at:at + n], positions[at:at + n], slot_of[row] = row, start + np.arange(n), slot
        at += n
    return kda.state_rows(jnp.asarray(positions), jnp.asarray(token_seq), jnp.asarray(slot_of)), at


def holds_the_jnp_form(jnp_form, kernel_form, operands, state, plan):
    """``kernel_form`` (a recurrence's Pallas kernel, interpreted here; lowered
    for the TPU on the chip) against ``jnp_form`` over the step ``plan``
    describes: the same ``o`` and zeros behind the live slots, the same state
    in every named slot, every other slot (the null slot and the other layer
    among them) left bit for bit as it was."""
    from jax.experimental.pallas import tpu as pltpu

    rows, at = rows_of(plan)
    assert int(rows.fed) == len(plan) and rows.work.tolist()[:len(plan)] == [p[0] for p in plan]
    o1, s1 = jnp_form(*operands, state, 1, rows)
    with pltpu.force_tpu_interpret_mode():
        o2, s2 = kernel_form(*operands, state, 1, rows)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2[:, 1:]), np.asarray(s1[:, 1:]), rtol=1e-5, atol=1e-6)
    assert (np.asarray(o2[at:]) == 0).all()  # buffer slots no row feeds read zeros
    untouched = np.asarray(s2) == np.asarray(state)
    unnamed = sorted(set(range(state.shape[1])) - {p[2] for p in plan})
    assert untouched[0].all() and untouched[1, unnamed].all()
    changed = [p[2] for p in plan if not untouched[1, p[2]].all()]
    assert changed == [p[2] for p in plan]  # and every fed row's slot was written


@pytest.mark.parametrize("table", sorted(ROW_TABLES))
def test_the_kernel_is_the_recurrence_of_the_jnp_form(table):
    """The Pallas kernel against ``rows_jnp`` over the row tables its pipeline
    can get wrong (``ROW_TABLES``): decode rows and chunks in one step, fresh
    rows, rows that feed nothing before, between and behind the fed ones, more
    fed rows than the pipeline has buffers, none at all; it never writes a
    slot no row names."""
    h, dk, dv = 8, 16, 128
    state = jnp.asarray(np.random.default_rng(2).standard_normal((2, SLOTS, dk, h, dv)),
                        jnp.float32)
    holds_the_jnp_form(kda.rows_jnp, kda.rows_kernel, kda_inputs(T_BUF, h, dk, dv, seed=1), state,
                       ROW_TABLES[table])


def test_the_first_table_describes_what_it_says():
    rows, at = rows_of(ROW_TABLES["decode_rows_and_chunks"])
    assert rows.n.tolist() == [7, 1, 0, 9, 1, 0] and rows.fresh.tolist()[:2] == [True, False]
    assert rows.work.tolist() == [0, 1, 3, 4, 0, 0] and int(rows.fed) == 4 and at == 18
    # carried fed rows behind the first fed row: rows 1 and 3 (row 4 is fresh)
    assert int(kda.rows_prefetched(rows)) == 2
    assert int(kda.rows_prefetched(rows_of([])[0])) == 0
    assert int(kda.rows_prefetched(rows_of(ROW_TABLES["one_fed_row"])[0])) == 0
    assert int(kda.rows_prefetched(rows_of(ROW_TABLES["every_row_fed"])[0])) == 3


@pytest.mark.parametrize("wanted,fit,depth", [(2, 3, 2), (2, 2, 2), (3, 7, 3), (3, 2, 2), (4, 4, 4)])
def test_the_pipeline_takes_the_buffers_its_budget_holds(wanted, fit, depth, monkeypatch):
    """How many state buffers the kernel's pipeline gets is read from the
    state's bytes and the kernel's VMEM budget (at most ``BUFFERS``), the same
    results whatever it comes to; a budget that holds fewer than two is
    refused."""
    from cordum_tpu.models import row_pipeline

    h, dk, dv = 8, 16, 128
    operands = kda_inputs(T_BUF, h, dk, dv, seed=3)
    held = 2 * 4 * T_BUF * h * (4 * dk + 2 * dv)  # five operands and the output, twice
    monkeypatch.setattr(row_pipeline, "BUFFERS", wanted)
    monkeypatch.setattr(kda, "VMEM_BUDGET_BYTES", held + fit * 4 * dk * h * dv + 100)
    assert row_pipeline.depth_for(4 * dk * h * dv, held, kda.VMEM_BUDGET_BYTES) == depth
    state = jnp.asarray(np.random.default_rng(4).standard_normal((2, SLOTS, dk, h, dv)),
                        jnp.float32)
    holds_the_jnp_form(kda.rows_jnp, kda.rows_kernel, operands, state,
                       ROW_TABLES["chunk_between_decode_rows"])
    monkeypatch.setattr(kda, "VMEM_BUDGET_BYTES", held + 4 * dk * h * dv + 100)
    with pytest.raises(ValueError, match="two row states"):
        kda.rows_kernel(*operands, state, 1, rows_of(ROW_TABLES["one_fed_row"])[0])


def test_a_reused_slot_starts_from_zero():
    """A slot is never cleared by the host: a row whose first fed position is
    0 starts from zeros whatever the slot's last owner left (state and
    convolution tail), so a second sequence through a used slot decodes the
    reference's tokens; continuing the FIRST row's state under the second
    row's tokens does not."""
    cfg = tiny()
    params = bailing.init_params(jax.random.PRNGKey(3), cfg)
    be = backend_for(cfg, params)
    rng = np.random.default_rng(9)
    a, b = ([int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in (40, 30))
    feed(be, [a], [[7] * 6])
    dirty = np.asarray(be._arenas[1][:, 1])
    assert np.abs(dirty).max() > 0
    (p,) = feed(be, [b], [[5] * 6])  # row 0 again: the same slot 1, the same pages
    assert gaps(cfg, params, b, p).max() < GAP
    assert not np.array_equal(np.asarray(be._arenas[1][:, 1]), dirty)


def test_the_shares_of_expert_parallel_4_add_up_to_the_uncut_layer():
    """The four chips' parts of an expert layer (each its quarter of the
    experts = two routing groups whole), with the shared expert counted once,
    add up to the uncut layer, in the program and against the reference's
    uncut expert part."""
    cfg = tiny(n_experts=32, experts_held=32, n_group=8, topk_group=4, top_k=8)
    params = bailing.init_params(jax.random.PRNGKey(4), cfg)
    layer = params["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.d_model), jnp.float32)
    live = jnp.ones((24,), bool)
    whole, counts = afmoe.expert_layer(m, layer, cfg, live)
    assert int(counts.sum()) == 24 * cfg.top_k
    shared = jnp.matmul(jax.nn.silu(m @ layer["s_gate"]) * (m @ layer["s_up"]), layer["s_down"])
    total = shared
    for rank in range(4):
        part_cfg = dataclasses.replace(cfg, first_expert=8 * rank, experts_held=8)
        part_layer = {**layer, **{k: layer[k][8 * rank:8 * rank + 8]
                                  for k in ("e_gate", "e_up", "e_down")}}
        part, n = afmoe.expert_layer(m, part_layer, part_cfg, live)
        assert n.tolist() == counts[8 * rank:8 * rank + 8].tolist()
        total = total + (part - shared)  # every chip computes the shared expert alike
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=2e-5, atol=2e-5)
    ref = ref_mod.Reference({**doc_of(cfg), "first_expert": 0}, cfg.max_seq_len)
    np.testing.assert_allclose(np.asarray(ref.expert_part(m, layer)), np.asarray(whole),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the slot's life in the engine, and what refuses the family
# ---------------------------------------------------------------------------


async def run_blocking(fn, *args):
    return await asyncio.get_running_loop().run_in_executor(None, fn, *args)


def ref_greedy(cfg, params, prompt, n):
    """Greedy decoding by the reference: one full forward a token."""
    ref = ref_mod.Reference(doc_of(cfg), cfg.max_seq_len)
    seq = list(prompt)
    for _ in range(n):
        _, arg, _ = ref.logits_of(params, seq, [0] * len(seq))
        seq.append(int(arg[-1]))
    return seq[len(prompt):]


def test_the_engine_serves_it_and_turns_slots_over():
    """Six requests through an engine of three sessions: every answer is the
    reference's greedy one, a session holds a slot from admission to
    retirement, slots are reused and none is left held; the prefix cache is
    off by capability and the counters count."""
    cfg = tiny()
    params = bailing.init_params(jax.random.PRNGKey(3), cfg)
    be = backend_for(cfg, params, max_seqs=3, budget=8)
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)] for n in (19, 5, 33, 12, 8, 26)]

    async def drive():
        eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=3, max_new_tokens_cap=8)
        assert eng.prefix is None and eng.tiering is None and not eng.kv_positional
        assert eng.state_allocator.capacity == 3
        outs = await asyncio.gather(*(
            eng.submit(GenRequest(prompt=p, max_new_tokens=6, stream=False), job_id=f"j{i}")
            for i, p in enumerate(prompts)))
        await eng.stop()
        return eng, outs

    eng, outs = asyncio.run(drive())
    for p, out in zip(prompts, outs):
        assert out["tokens"] == ref_greedy(cfg, params, p, 6)
    st = eng.stats
    assert st.state_slots_peak == 3 and eng.state_allocator.used == 0
    eng.state_allocator.check_consistency()
    eng.allocator.check_consistency()
    assert st.state_chunk_tokens + st.state_decode_rows == st.prefill_tokens + st.decoded_tokens - 6
    assert st.state_decode_rows >= 6 * 5 and st.model["moe_assignments"] > 0
    assert st.prefix_hits == 0 and st.drafted_tokens == 0


def state_family(family):
    """``(cfg, params)`` of a tiny model of a family that keeps recurrent
    state: this file's, or the one whose every layer holds pages AND a state."""
    if family == "bailing":
        cfg = tiny()
        return cfg, bailing.init_params(jax.random.PRNGKey(3), cfg)
    from cordum_tpu.models import falcon_h1

    cfg = falcon_h1.FalconH1Config(dtype=jnp.float32)
    return cfg, falcon_h1.init_params(jax.random.PRNGKey(3), cfg)


STATE_FAMILIES = ["bailing", "falcon_h1"]


@pytest.mark.parametrize("family", STATE_FAMILIES)
def test_a_step_entry_without_a_slot_is_refused(family):
    cfg, params = state_family(family)
    be = backend_for(cfg, params)
    with pytest.raises(ValueError, match="state_slot"):
        be.step([entry(be, 0, [1, 2, 3], 0, slot=0)])
    with pytest.raises(ValueError, match="state_slot"):
        be.step([entry(be, 0, [1, 2, 3], 0, slot=5)])


@pytest.mark.parametrize("family", STATE_FAMILIES)
def test_a_step_that_names_a_slot_twice_is_refused(family):
    """A slot is one session's and a session one row of a step: the
    recurrence kernels' pipeline reads a row's state before the rows ahead of
    it are written back (``models/row_pipeline.py``)."""
    cfg, params = state_family(family)
    be = backend_for(cfg, params)
    with pytest.raises(ValueError, match="one state_slot"):
        be.step([entry(be, 0, [1, 2, 3], 0, slot=2), entry(be, 1, [4], 0, slot=2)])


@pytest.mark.parametrize("family", STATE_FAMILIES)
@pytest.mark.parametrize("feature", ["prefix cache", "speculation", "hibernation", "migration",
                                     "gang"])
def test_what_shares_refeeds_or_carries_positions_refuses_the_family(feature, family):
    """Each by the ONE capability (``kv_positional``), loudly: the second
    family's pages alone would pass every other test (K and V by head, whole
    rows), so ``kv_positional`` is asked first."""
    cfg, params = state_family(family)
    spec = spec_for(cfg)
    assert not spec.kv_positional and spec.kv_whole_row
    assert spec.kv_by_head == (family == "falcon_h1")
    be = backend_for(cfg, params)

    async def engine(**kw):
        return ServingEngine(be, run_blocking=run_blocking, **kw)

    with pytest.raises(UnsupportedForModel, match="kv_positional"):
        if feature == "prefix cache":
            with pytest.raises(UnsupportedForModel, match="kv_positional"):
                be.copy_page(1, 2)
            asyncio.run(engine(prefix_cache=True))
        elif feature == "speculation":
            asyncio.run(engine(speculative=True))
        elif feature == "hibernation":
            with pytest.raises(UnsupportedForModel, match="kv_positional"):
                asyncio.run(engine(hibernate_after_s=5.0))

            async def hibernate():
                eng = await engine()
                try:
                    await eng.hibernate_session("nobody")
                finally:
                    await eng.stop()
            asyncio.run(hibernate())
        elif feature == "migration":
            with pytest.raises(UnsupportedForModel, match="kv_positional"):
                be.export_kv([1], 0, 4)
            with pytest.raises(UnsupportedForModel, match="kv_positional"):
                be.import_kv([1], [{"i": 0}])

            async def export():
                eng = await engine()
                try:
                    assert eng.pick_rebalance_sessions() == []
                    await eng.export_pages("nobody", 0, 4)
                finally:
                    await eng.stop()
            asyncio.run(export())
        else:
            from cordum_tpu.serving.shard import ShardedServingBackend

            ShardedServingBackend(cfg, tp=2, num_pages=16, page_size=PS)


@pytest.mark.parametrize("family", STATE_FAMILIES)
def test_the_workers_defaults_meet_the_capability(caplog, family):
    """``make_serving_engine`` hands its defaults (None) on and the engine
    resolves them: the drafter and the prefix cache stay off with ONE log
    line that names the capability; either asked for by name is refused."""
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.kv import MemoryKV
    from cordum_tpu.infra.memstore import MemoryStore
    from cordum_tpu.worker.handlers import TPUCompute, make_serving_engine
    from cordum_tpu.worker.runtime import Worker

    cfg, params = state_family(family)

    async def build(**kw):
        worker = Worker(bus=LoopbackBus(sync=True), store=MemoryStore(MemoryKV()),
                        worker_id="w", pool="tpu", topics=["job.tpu.>"], capabilities=["tpu"])
        return make_serving_engine(TPUCompute(), worker, cache_pages=32, page_size=PS,
                                   max_sessions=2, model=cfg, params=params, **kw)

    with caplog.at_level(logging.INFO):
        eng = asyncio.run(build())
    assert eng.prefix is None and not eng.speculative
    said = [r.getMessage() for r in caplog.records if "kv_positional" in r.getMessage()]
    assert len(said) == 1 and "the prefix cache and the drafter stay off" in said[0]
    for asked in ({"prefix_cache": True}, {"speculative": True}):
        with pytest.raises(UnsupportedForModel, match="kv_positional"):
            asyncio.run(build(**asked))
    eng = asyncio.run(build(prefix_cache=False, speculative=False))
    assert eng.prefix is None and not eng.speculative
