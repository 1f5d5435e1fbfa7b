"""Speculative decoding inside the ragged step (ISSUE 19, docs/SERVING.md
§Speculative decoding): the n-gram/prompt-lookup drafter, accept-longest-
prefix verification semantics (token-exact vs the sequential oracle on
both the fake and the real fp32 paged backend, drafts crossing page
boundaries and CoW prefix pages), write-position rollback arena
bit-identity, the spec-disabled legacy-path identity guard, adaptive-k
throttling, burst stream-offset exactly-once regressions (worker sink,
scheduler fold, SDK dedupe, failover resume replay), and the capacity
surface (occupancy beacon key, `cordumctl capacity` accept column, the
ServingPlacer's speculable preference)."""
import asyncio
import random

from cordum_tpu.controlplane.scheduler.placer import ServingPlacer
from cordum_tpu.infra.metrics import Metrics
from cordum_tpu.serving.backend import StepEntry
from cordum_tpu.serving.engine import (
    DEFAULT_DRAFT_K,
    GenRequest,
    ServingEngine,
)
from cordum_tpu.serving.pager import PageAllocator
from cordum_tpu.sdk.client import merge_stream_packet

from .fakes import MOD, FakeBackend, fake_ref, run_blocking


# ---------------------------------------------------------------------------
# scripted drafters
# ---------------------------------------------------------------------------


def perfect_drafter(history, k):
    """The fake's exact continuation (its sample over the whole history),
    so every draft verifies."""
    h = list(history)
    for _ in range(k):
        h.append(FakeBackend.sample(h))
    return h[len(history):]


def garbage_drafter(history, k):
    """Never-correct drafts: every proposal is the true continuation
    plus one, so every draft is rejected and each step degrades to a
    single verified token (the worst-case rollback path)."""
    return [(t + 1) % MOD for t in perfect_drafter(history, k)]


def cut2_drafter(history, k):
    """Correct for the first two positions, garbage after — exercises
    partial accept + rollback in the same row."""
    plan = perfect_drafter(history, k)
    return [t if i < 2 else (t + 1) % MOD for i, t in enumerate(plan)]


# ---------------------------------------------------------------------------
# n-gram drafter units
# ---------------------------------------------------------------------------


def test_ngram_draft_proposes_template_continuation():
    motif = [5, 9, 14, 23]
    history = motif * 3 + motif[:2]  # mid-motif: the tail bigram repeats
    draft = ServingEngine._ngram_draft(history, 4)
    # the continuation after the most recent earlier [14, 23, 5]... match
    # is the motif's next tokens
    assert draft == [14, 23, 5, 9]


def test_ngram_draft_most_recent_occurrence_wins():
    # the trigram [1, 2, 3] occurs twice with different continuations;
    # the LATER one (-> 9) must win over the earlier (-> 7)
    history = [1, 2, 3, 7, 0, 1, 2, 3, 9, 4, 1, 2, 3]
    assert ServingEngine._ngram_draft(history, 1) == [9]


def test_ngram_draft_no_repetition_returns_empty():
    assert ServingEngine._ngram_draft(list(range(40)), 4) == []
    assert ServingEngine._ngram_draft([7], 4) == []


def test_ngram_draft_respects_k():
    history = [1, 2, 3, 4, 5, 6, 1, 2, 3]
    assert len(ServingEngine._ngram_draft(history, 2)) <= 2


# ---------------------------------------------------------------------------
# engine semantics on the fake backend
# ---------------------------------------------------------------------------


async def _run_engine(backend, prompts, max_new, **eng_kw):
    eng = ServingEngine(backend, run_blocking=run_blocking,
                        max_new_tokens_cap=max_new, **eng_kw)
    results = await asyncio.gather(*[
        eng.submit(GenRequest(prompt=p, max_new_tokens=max_new, stream=False),
                   job_id=f"j{i}")
        for i, p in enumerate(prompts)
    ])
    outs = [r["tokens"] for r in results]
    await eng.stop()
    return outs, eng


async def test_spec_engine_token_identical_and_fewer_steps():
    """Perfectly drafted sessions produce EXACTLY the sequential tokens in
    far fewer backend steps — speculation is a schedule change, not a math
    change."""
    prompts = [[5, 9, 17, 3], [100, 42], [7, 3, 11]]
    base_be = FakeBackend()
    base_outs, base_eng = await _run_engine(base_be, prompts, 12,
                                            speculative=False)
    spec_be = FakeBackend()
    spec_outs, spec_eng = await _run_engine(spec_be, prompts, 12,
                                            speculative=True, draft_k=4,
                                            drafter=perfect_drafter)
    for p, out in zip(prompts, spec_outs):
        assert out == fake_ref(p, 12)
    assert spec_outs == base_outs
    assert spec_be.steps < base_be.steps
    assert spec_eng.stats.spec_steps > 0
    assert spec_eng.stats.accepted_tokens == spec_eng.stats.drafted_tokens > 0
    assert spec_eng.stats.rolled_back_tokens == 0
    assert spec_eng.spec_accept_ewma > 0.5
    # both engines count the same generated tokens
    assert spec_eng.stats.decoded_tokens == base_eng.stats.decoded_tokens


async def test_spec_engine_garbage_drafts_roll_back_token_identical():
    """Every draft rejected: output still exactly sequential (the bonus
    token carries each step), every proposal counted as rolled back."""
    prompts = [[5, 9, 17, 3], [8, 1]]
    outs, eng = await _run_engine(FakeBackend(), prompts, 10,
                                  speculative=True, draft_k=4,
                                  drafter=garbage_drafter)
    for p, out in zip(prompts, outs):
        assert out == fake_ref(p, 10)
    assert eng.stats.rolled_back_tokens > 0
    assert eng.stats.accepted_tokens == 0
    # per-session EWMAs decayed: the engine stopped proposing long drafts
    assert eng.spec_accept_ewma < 0.5


async def test_spec_engine_partial_accept_rolls_back_tail():
    """A row that verifies 2 of k drafts advances exactly 3 tokens (2
    accepted + the bonus) and rolls back the rest — still token-exact."""
    prompt = [5, 9, 17, 3]
    outs, eng = await _run_engine(FakeBackend(), [prompt], 12,
                                  speculative=True, draft_k=4,
                                  drafter=cut2_drafter)
    assert outs[0] == fake_ref(prompt, 12)
    assert eng.stats.accepted_tokens > 0
    assert eng.stats.rolled_back_tokens > 0


async def test_spec_flag_off_never_drafts_on_capable_backend():
    be = FakeBackend()
    outs, eng = await _run_engine(be, [[5, 9, 17, 3]], 8, speculative=False)
    assert eng.speculative is False
    assert outs[0] == fake_ref([5, 9, 17, 3], 8)
    assert all(draft == 0 for step in be.seen for *_, draft in step)


async def test_adaptive_k_ramps_down_on_rejection():
    """The per-session acceptance EWMA throttles proposal length: a
    session starts at full draft_k and decays toward single-token probes
    while its drafts keep rejecting; k never exceeds remaining - 1."""
    seen: list[tuple[int, int]] = []  # (k asked of the drafter, room left)
    prompt, max_new = [5, 9, 17, 3], 16

    def capture(history, k):
        seen.append((k, max_new - (len(history) - len(prompt))))
        return garbage_drafter(history, k)

    outs, _ = await _run_engine(FakeBackend(), [prompt], max_new,
                                speculative=True, draft_k=4, drafter=capture)
    assert outs[0] == fake_ref(prompt, max_new)
    assert seen[0][0] == 4  # optimistic start: EWMA seeds at 1.0
    assert seen[-1][0] == 1  # decayed to probes after steady rejection
    assert all(k <= room - 1 for k, room in seen)  # the overshoot clamp


async def test_spec_burst_never_overshoots_max_new():
    """Fully accepted bursts land EXACTLY max_new tokens — the k <=
    remaining - 1 clamp means a burst can never write past the admitted
    page footprint."""
    for max_new in (3, 7, 12):
        outs, _ = await _run_engine(FakeBackend(), [[5, 9, 17, 3]],
                                    max_new, speculative=True, draft_k=4,
                                    drafter=perfect_drafter)
        assert outs[0] == fake_ref([5, 9, 17, 3], max_new)
        assert len(outs[0]) == max_new


async def test_eos_inside_burst_truncates_exactly():
    prompt = [5, 9]
    seq = fake_ref(prompt, 12)
    eos = seq[5]
    expected = seq[:seq.index(eos) + 1]
    eng = ServingEngine(FakeBackend(), run_blocking=run_blocking,
                        max_new_tokens_cap=12, speculative=True, draft_k=4,
                        drafter=perfect_drafter)
    r = await eng.submit(GenRequest(prompt=prompt, max_new_tokens=12,
                                    stream=False, eos_token=eos),
                         job_id="e1")
    await eng.stop()
    assert r["tokens"] == expected


async def test_spec_metrics_counters():
    metrics = Metrics()
    await _run_engine(FakeBackend(), [[5, 9, 17, 3]], 10,
                      speculative=True, draft_k=4, drafter=cut2_drafter,
                      metrics=metrics)
    drafted = metrics.serving_spec_drafted.value()
    accepted = metrics.serving_spec_accepted.value()
    rolled = metrics.serving_spec_rolled_back.value()
    assert drafted > 0 and accepted > 0 and rolled > 0
    assert drafted == accepted + rolled


# ---------------------------------------------------------------------------
# real fp32 paged backend: oracle exactness + arena bit-identity
# ---------------------------------------------------------------------------


def _llama_env():
    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend

    cfg = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=128,
                            dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    backend = LlamaServingBackend(
        cfg, num_pages=64, page_size=8, params_provider=lambda: params
    )
    return cfg, params, backend


def _oracle_cut_drafter(refs, rng):
    """Drafter scripted from precomputed oracle sequences: the true
    continuation up to a random cut, garbage after — controlled accept
    lengths against the real model."""

    def drafter(history, k):
        for seq in refs:
            if len(seq) > len(history) and seq[:len(history)] == history:
                cont = seq[len(history):len(history) + k]
                cut = rng.randint(0, len(cont))
                return cont[:cut] + [(t + 1) % 256 for t in cont[cut:]]
        return []

    return drafter


async def test_spec_real_backend_property_matches_oracle():
    """Property: speculative decode on the real fp32 paged backend is
    token-exact vs the sequential full-forward oracle across sessions
    whose drafts cross page boundaries (page_size=8, bursts up to 5
    tokens) with randomized accept cut points."""
    from .test_serving import ref_greedy

    cfg, params, be = _llama_env()
    rng = random.Random(7)
    prompts = [[5, 9, 17, 3], [7, 3, 11, 19, 2, 5, 23, 1, 13], [100, 42]]
    n_new = 14
    refs = [p + ref_greedy(cfg, params, p, n_new) for p in prompts]
    eng = ServingEngine(be, run_blocking=run_blocking,
                        max_new_tokens_cap=n_new, speculative=True,
                        draft_k=4, drafter=_oracle_cut_drafter(refs, rng))
    assert eng.speculative is True
    results = await asyncio.gather(*[
        eng.submit(GenRequest(prompt=p, max_new_tokens=n_new, stream=False),
                   job_id=f"real{i}")
        for i, p in enumerate(prompts)
    ])
    stats = eng.stats
    await eng.stop()
    for p, seq, r in zip(prompts, refs, results):
        assert r["tokens"] == seq[len(p):], p
    assert stats.accepted_tokens > 0  # speculation actually engaged
    assert stats.rolled_back_tokens > 0  # ... and rollback was exercised


async def test_spec_with_cow_prefix_pages_matches_oracle():
    """Speculative bursts over copy-on-write shared-prefix pages: a
    second session reusing a cached full-page prefix must still be
    token-exact — the draft write span triggers the CoW guard before any
    shared page is written."""
    from .test_serving import ref_greedy

    cfg, params, be = _llama_env()
    rng = random.Random(11)
    system = [7, 3, 11, 19, 2, 5, 23, 1]  # exactly one 8-slot page
    p1, p2 = system + [13, 4], system + [9, 2]
    n_new = 8
    refs = [p + ref_greedy(cfg, params, p, n_new) for p in (p1, p2)]
    eng = ServingEngine(be, run_blocking=run_blocking,
                        max_new_tokens_cap=n_new, speculative=True,
                        draft_k=4, drafter=_oracle_cut_drafter(refs, rng))
    assert eng.prefix is not None  # the real backend carries copy_page
    out1 = await eng.submit(
        GenRequest(prompt=p1, max_new_tokens=n_new, stream=False),
        job_id="cow1")
    out2 = await eng.submit(
        GenRequest(prompt=p2, max_new_tokens=n_new, stream=False),
        job_id="cow2")
    stats = eng.stats
    await eng.stop()
    assert out1["tokens"] == refs[0][len(p1):]
    assert out2["tokens"] == refs[1][len(p2):]
    assert stats.prefix_hits >= 1  # the second session mapped shared pages
    assert stats.accepted_tokens > 0


async def test_rollback_arena_bit_identical_to_sequential():
    """The write-position rollback invariant, measured at the arena: a
    speculative session's K/V over [0, pos) is byte-identical to a
    sequential session's — rejected-draft garbage beyond pos never
    reaches exported (= reachable) state."""
    from .test_serving import ref_greedy

    cfg, params, be = _llama_env()
    alloc = PageAllocator(be.num_pages, be.page_size)
    prompt = [7, 3, 11, 19, 2, 5, 23, 1, 13]  # crosses a page boundary
    n_new = 10
    ref = ref_greedy(cfg, params, prompt, n_new)
    seq = prompt + ref
    total = len(prompt) + n_new

    # sequential leg
    pages_a = alloc.alloc("seq", alloc.pages_for(total))
    first = be.prefill(prompt, pages_a)
    out_a, pos_a, last = [first], len(prompt), first
    while len(out_a) < n_new:
        (nxt,) = be.decode([(last, pos_a, pages_a)])
        pos_a, last = pos_a + 1, int(nxt)
        out_a.append(last)

    # speculative leg: manual draft rows with random cut points, engine
    # accept semantics, write-position rollback
    rng = random.Random(3)
    pages_b = alloc.alloc("spec", alloc.pages_for(total))
    first = be.prefill(prompt, pages_b)
    out_b, pos_b, last = [first], len(prompt), first
    while len(out_b) < n_new:
        room = n_new - len(out_b)
        k = min(4, room - 1)
        if k < 1:
            (nxt,) = be.decode([(last, pos_b, pages_b)])
            pos_b, last = pos_b + 1, int(nxt)
            out_b.append(last)
            continue
        idx = len(prompt) + len(out_b)
        cont = seq[idx:idx + k]
        cut = rng.randint(0, len(cont))
        draft = cont[:cut] + [(t + 1) % 256 for t in cont[cut:]]
        (preds,) = be.step([StepEntry(
            tokens=[last, *draft], start=pos_b, pages=pages_b, sample=True,
            phase="decode", key="spec", draft=len(draft))])
        preds = [int(t) for t in preds]
        a = 0
        while a < len(draft) and draft[a] == preds[a]:
            a += 1
        burst = draft[:a] + [preds[a]]
        out_b.extend(burst)
        pos_b += len(burst)  # rollback: rejected drafts sit at >= pos_b
        last = burst[-1]

    assert out_a == out_b == ref
    # both legs wrote identical tokens at positions [0, total - 1); the
    # final sampled token is never fed on the sequential leg, so compare
    # up to there — export trims to live positions host-side
    written = total - 1
    rec_a = be.export_kv(pages_a, 0, written)
    rec_b = be.export_kv(pages_b, 0, written)
    assert len(rec_a) == len(rec_b) > 1
    for ra, rb in zip(rec_a, rec_b):
        assert ra["i"] == rb["i"] and ra["used"] == rb["used"]
        assert ra["k"] == rb["k"], f"K pages differ at ordinal {ra['i']}"
        assert ra["v"] == rb["v"], f"V pages differ at ordinal {ra['i']}"


# ---------------------------------------------------------------------------
# burst stream offsets: exactly-once across multi-token packets
# ---------------------------------------------------------------------------


def test_scheduler_record_stream_merges_burst_packets():
    """The scheduler's per-job stream fold (failover resume_tokens source)
    merges multi-token packets by offset: bursts append, replays
    overwrite idempotently, out-of-order duplicates never corrupt."""
    from cordum_tpu.controlplane.scheduler.engine import Engine

    class Stub:
        _stream_tokens: dict = {}

    stub = Stub()
    rec = Engine._record_stream
    rec(stub, "j", 0, [10, 11, 12])  # a 3-token burst
    rec(stub, "j", 3, [13])
    rec(stub, "j", 4, [14, 15])
    assert stub._stream_tokens["j"] == [10, 11, 12, 13, 14, 15]
    # failover replay at offset 0 (the whole prefix re-streams) is a no-op
    rec(stub, "j", 0, [10, 11, 12, 13])
    assert stub._stream_tokens["j"] == [10, 11, 12, 13, 14, 15]
    # an overlapping burst (re-sent tail + fresh tokens) extends exactly
    rec(stub, "j", 5, [15, 16, 17])
    assert stub._stream_tokens["j"] == [10, 11, 12, 13, 14, 15, 16, 17]
    # a gapped packet is dropped (backfilled by the next offset-0 replay)
    rec(stub, "j", 12, [99])
    assert stub._stream_tokens["j"] == [10, 11, 12, 13, 14, 15, 16, 17]


def test_sdk_merge_stream_packet_burst_dedupe():
    """The SDK's offset dedupe assembles an exactly-once sequence from
    multi-token burst packets, including a failed-over worker's replay of
    the streamed prefix at offset 0."""
    n_seen, got = 0, []
    for off, toks in [(0, [1, 2, 3]), (3, [4]), (4, [5, 6, 7])]:
        fresh, n_seen = merge_stream_packet(n_seen, off, toks)
        got.extend(fresh)
    assert got == [1, 2, 3, 4, 5, 6, 7]
    # failover: the new worker replays everything at offset 0 as one
    # burst, then continues — duplicates skipped, the tail lands once
    fresh, n_seen = merge_stream_packet(n_seen, 0, [1, 2, 3, 4, 5, 6, 7, 8])
    got.extend(fresh)
    assert got == [1, 2, 3, 4, 5, 6, 7, 8]
    # overlapping re-send
    fresh, n_seen = merge_stream_packet(n_seen, 6, [7, 8, 9])
    got.extend(fresh)
    assert got == [1, 2, 3, 4, 5, 6, 7, 8, 9]
    # a gap is left for the authoritative terminal tail
    fresh, n_seen = merge_stream_packet(n_seen, 20, [99])
    assert fresh == [] and n_seen == 9
    # legacy packets without an offset assume contiguity
    fresh, n_seen = merge_stream_packet(n_seen, None, [10, 11])
    got.extend(fresh)
    assert got == [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]


async def test_engine_burst_packets_carry_worker_sink_offsets():
    """A speculative engine emits multi-token packets; the worker sink's
    offset formula (n_generated - len(new_tokens)) must describe each
    burst's true position so offset-deduping consumers reassemble the
    exact sequence — including under a simulated duplicate delivery."""
    packets: list[tuple[list[int], int]] = []

    async def sink(new_tokens, n_generated, done):
        packets.append((list(new_tokens), n_generated))

    prompt, max_new = [5, 9, 17, 3], 12
    eng = ServingEngine(FakeBackend(), run_blocking=run_blocking,
                        max_new_tokens_cap=max_new, speculative=True,
                        draft_k=4, drafter=perfect_drafter)
    r = await eng.submit(GenRequest(prompt=prompt, max_new_tokens=max_new),
                         job_id="s1", on_tokens=sink)
    await eng.stop()
    assert r["tokens"] == fake_ref(prompt, max_new)
    assert any(len(toks) > 1 for toks, _ in packets)  # bursts actually flowed
    # the worker sink's offset formula, applied per packet
    offs = [max(0, n_gen - len(toks)) for toks, n_gen in packets]
    n_seen, got = 0, []
    for (toks, _), off in zip(packets, offs):
        fresh, n_seen = merge_stream_packet(n_seen, off, toks)
        got.extend(fresh)
    assert got == r["tokens"]
    # duplicate delivery of every packet (at-least-once bus) still exact
    n_seen, got = 0, []
    for (toks, _), off in zip(packets, offs):
        for _ in range(2):
            fresh, n_seen = merge_stream_packet(n_seen, off, toks)
            got.extend(fresh)
    assert got == r["tokens"]


async def test_resume_tokens_replay_with_speculation():
    """Failover resume on a speculative engine: the resume prefix replays
    at offset 0, speculation continues the tail, and the assembled stream
    equals the uninterrupted sequential run exactly."""
    prompt, max_new = [5, 9, 17, 3], 10
    full = fake_ref(prompt, max_new)
    packets: list[tuple[list[int], int]] = []

    async def sink(new_tokens, n_generated, done):
        packets.append((list(new_tokens), n_generated))

    eng = ServingEngine(FakeBackend(), run_blocking=run_blocking,
                        max_new_tokens_cap=max_new, speculative=True,
                        draft_k=4, drafter=perfect_drafter)
    r = await eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=max_new,
                   resume_tokens=full[:4]),
        job_id="resume1", on_tokens=sink)
    await eng.stop()
    assert r["tokens"] == full
    # a consumer that saw the first worker's stream die after 4 tokens
    # dedupes the replay and ends with the exact sequence
    n_seen, got = 4, list(full[:4])
    for toks, n_gen in packets:
        fresh, n_seen = merge_stream_packet(
            n_seen, max(0, n_gen - len(toks)), toks)
        got.extend(fresh)
    assert got == full


# --------------------------------------------------- CI perf-floor wiring


def test_floor_checker_gates_spec_keys():
    import json
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo / "tools"))
    try:
        import check_bench_floor as mod
    finally:
        sys.path.pop(0)
    floors = json.loads((repo / "bench_floor.json").read_text())
    base = {"spec_decode_speedup": 1.96, "spec_token_identity": 1,
            "spec_compile_count": 1}
    # healthy values: no spec-key violations (other keys flag missing)
    assert not any("spec" in v for v in mod.check(dict(base), floors))
    for key, bad in [("spec_decode_speedup", 1.0),
                     ("spec_token_identity", 0),
                     ("spec_compile_count", 2)]:
        doc = dict(base)
        doc[key] = bad
        assert any(key in v for v in mod.check(doc, floors)), key
    # a missing identity key is itself a violation (the gate cannot be
    # skipped by dropping the metric)
    doc = dict(base)
    doc.pop("spec_token_identity")
    assert any("spec_token_identity" in v for v in mod.check(doc, floors))


# ---------------------------------------------------------------------------
# capacity surface: beacon key, renderer column, placer preference
# ---------------------------------------------------------------------------


def test_capacity_view_spec_accept_presence_is_the_signal():
    from .test_capacity import _decode_beacon, _mk_view

    clock = [0.0]
    view = _mk_view(clock)
    view.ingest(_decode_beacon(
        "w-spec", occ={"active_sessions": 2, "spec_accept_rate": 0.85},
        kv={"pages_total": 64, "pages_free": 30}))
    view.ingest(_decode_beacon(
        "w-plain", occ={"active_sessions": 1},
        kv={"pages_total": 64, "pages_free": 30}))
    assert view.spec_accept("w-spec") == 0.85
    assert view.spec_accept("w-plain") is None  # key absent = disabled
    assert view.spec_accept("w-gone") is None
    clock[0] += 100.0  # stale beacons read as unmeasured
    assert view.spec_accept("w-spec") is None


def test_render_worker_table_accept_column_degrades():
    from cordum_tpu.obs.capacity import render_worker_table

    lines = render_worker_table({
        "w-spec": {"fresh": True, "serving_role": "mixed",
                   "kv_pages": {"pages_total": 64, "pages_free": 30,
                                "pages_in_use": 34},
                   "occupancy": {"active_sessions": 2, "decode_mean": 1.5,
                                 "spec_accept_rate": 0.85}},
        "w-plain": {"fresh": True, "serving_role": "mixed",
                    "kv_pages": {"pages_total": 64, "pages_free": 64,
                                 "pages_in_use": 0},
                    "occupancy": {"active_sessions": 0, "decode_mean": 0.0}},
    })
    assert lines and "accept" in lines[0]
    spec_row = next(ln for ln in lines if ln.startswith("w-spec"))
    plain_row = next(ln for ln in lines if ln.startswith("w-plain"))
    assert "85%" in spec_row
    assert "85%" not in plain_row  # speculation disabled renders "-"
    # every row carries every column: the renderer never KeyErrors on a
    # worker whose beacon predates the accept field
    assert len(spec_row.split()) == len(plain_row.split())


def test_placer_prefers_draft_enabled_workers_for_speculable():
    from .test_disagg import StubView, hb

    class SpecView(StubView):
        def __init__(self):
            super().__init__()
            self.accept: dict[str, float] = {}

        def spec_accept(self, wid):
            return self.accept.get(wid)

    view = SpecView()
    for w in ("w-spec", "w-plain"):
        view.rates[(w, "llm.prefill")] = 100.0
        view.kv[w] = {"pages_total": 100, "pages_free": 100}
    view.accept["w-spec"] = 0.7
    placer = ServingPlacer(view)
    cands = [hb("w-spec"), hb("w-plain")]
    # speculable sessions: the draft-enabled worker wins every time
    assert all(placer.pick(cands, speculable=True) == "w-spec"
               for _ in range(20))
    # ordinary sessions: both workers share the load (equal rates)
    picks = {placer.pick(cands) for _ in range(20)}
    assert picks == {"w-spec", "w-plain"}
    # preference, not a filter: no draft-enabled worker -> still places
    view.accept.clear()
    assert placer.pick(cands, speculable=True) in ("w-spec", "w-plain")


def test_label_speculable_reaches_placer_via_strategy():
    """The strategy passes the LABEL_SPECULABLE hint through to
    placer.pick — a labeled serving job prefers draft-enabled workers."""
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.registry import WorkerRegistry
    from cordum_tpu.controlplane.scheduler.strategy import (
        ThroughputAwareStrategy,
    )
    from cordum_tpu.protocol.types import (
        JobRequest,
        LABEL_OP,
        LABEL_SPECULABLE,
    )

    from .test_disagg import StubView, hb

    class SpecView(StubView):
        def __init__(self):
            super().__init__()
            self.accept: dict[str, float] = {}

        def spec_accept(self, wid):
            return self.accept.get(wid)

    view = SpecView()
    for w in ("w-spec", "w-plain"):
        view.rates[(w, "llm.prefill")] = 100.0
        view.kv[w] = {"pages_total": 100, "pages_free": 100}
    view.accept["w-spec"] = 0.9
    reg = WorkerRegistry()
    pc = parse_pool_config({"topics": {"job.tpu.generate": "tpu"},
                            "pools": {"tpu": {}}})
    strat = ThroughputAwareStrategy(reg, pc, capacity=view,
                                    placer=ServingPlacer(view), native=False)
    for w in ("w-spec", "w-plain"):
        reg.update(hb(w))
    req = JobRequest(job_id="spec-job", topic="job.tpu.generate",
                     labels={LABEL_OP: "llm.generate", LABEL_SPECULABLE: "1"})
    assert strat.pick_subject(req) == "worker.w-spec.jobs"
