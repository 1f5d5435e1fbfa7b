"""Sharded serving gangs (docs/SERVING.md §Sharded serving): per-rank
KV-page record slicing/merging (byte-identity property), TP=2 gang token
streams bit-identical to the single-rank fp32 oracle (greedy and
speculative, exactly ONE compiled ragged program per rank), drain with a
gang member as migration source, the statebus-backed cold tier surviving
a worker restart, gang-aware capacity fusing + placement routing, and
the serving-gang e2e over the live gang scheduler stack."""
import asyncio
import random
import time

import numpy as np
import pytest

from cordum_tpu.serving.engine import GenRequest, ServingEngine, SessionMigrated
from cordum_tpu.serving.pager import PageAllocator
from cordum_tpu.serving.shard import (
    ServingGangGroup,
    ShardedServingBackend,
    entry_from_wire,
    entry_to_wire,
    heads_for_rank,
    merge_rank_records,
    slice_rank_record,
)

from .fakes import FakeBackend, fake_ref, run_blocking
from .test_serving import ref_greedy
from .test_serving_failover import install_into, wait_until


def tiny_cfg():
    import jax.numpy as jnp

    from cordum_tpu.models import llama

    return llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2,
                             n_heads=4, n_kv_heads=2, d_ff=128,
                             max_seq_len=128, dtype=jnp.float32)


def tiny_params(cfg):
    import jax

    from cordum_tpu.models import llama

    return llama.init_params(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# per-rank record format
# ---------------------------------------------------------------------------


def test_heads_for_rank_split():
    assert [heads_for_rank(8, 4, r) for r in range(4)] == [
        (0, 2), (2, 4), (4, 6), (6, 8)]
    assert heads_for_rank(2, 1, 0) == (0, 2)
    with pytest.raises(ValueError):
        heads_for_rank(6, 4, 0)  # not divisible
    with pytest.raises(ValueError):
        heads_for_rank(8, 4, 4)  # rank outside tp


def test_rank_record_slice_merge_roundtrip_property():
    """Any page record sliced per rank and merged back — in any rank
    order, alongside plain records — is BYTE-identical to the original;
    missing or overlapping slices are refused."""
    rng = random.Random(20_06)
    for _ in range(25):
        layers = rng.choice([1, 2, 3])
        used = rng.randint(1, 16)
        kvh = rng.choice([2, 4, 8])
        hd = rng.choice([4, 16])
        tp = rng.choice([t for t in (2, 4, 8) if kvh % t == 0])
        data = np.arange(layers * used * kvh * hd, dtype=np.float32)
        k = (data * 1.5).reshape(layers, used, kvh, hd)
        v = (data - 7.0).reshape(layers, used, kvh, hd)
        rec = {"i": rng.randint(0, 63), "used": used,
               "k": k.tobytes(), "v": v.tobytes(),
               "shape": [layers, used, kvh, hd]}
        slices = [
            slice_rank_record(rec, r, tp, *heads_for_rank(kvh, tp, r))
            for r in range(tp)
        ]
        rng.shuffle(slices)
        plain = {"i": rec["i"] + 64, "used": used, "k": k.tobytes(),
                 "v": v.tobytes(), "shape": [layers, used, kvh, hd]}
        merged = merge_rank_records([plain, *slices])
        assert [m["i"] for m in merged] == sorted([rec["i"], plain["i"]])
        got = next(m for m in merged if m["i"] == rec["i"])
        assert got["k"] == rec["k"] and got["v"] == rec["v"]
        assert got["shape"] == rec["shape"] and got["used"] == used
        if tp > 1:
            with pytest.raises(ValueError):
                merge_rank_records(slices[:-1])  # a rank went missing
            with pytest.raises(ValueError):
                merge_rank_records([*slices, slices[0]])  # overlap


def test_step_entry_wire_codec_roundtrip():
    from cordum_tpu.serving.backend import StepEntry

    e = StepEntry(tokens=[5, 9], start=12, pages=[3, 4], sample=False,
                  phase="prefill", key="s-1", draft=2)
    w = entry_to_wire(e)
    assert all(isinstance(v, (int, bool, str, list)) for v in w.values())
    back = entry_from_wire(w)
    assert (back.tokens, back.start, back.pages, back.sample,
            back.phase, back.key, back.draft) == (
        e.tokens, e.start, e.pages, e.sample, e.phase, e.key, e.draft)


# ---------------------------------------------------------------------------
# TP gang vs single-rank identity (backend level)
# ---------------------------------------------------------------------------


def drive_backend(be, prompt, n_new, reserve=0):
    """prefill + n_new-1 decode steps through the backend's compat
    conveniences, returning (tokens, pages, final_pos).  ``reserve``
    leaves page room for tokens the caller will decode afterwards."""
    alloc = PageAllocator(be.num_pages, be.page_size)
    pages = alloc.alloc("s0", alloc.pages_for(len(prompt) + n_new + reserve))
    first = be.prefill(prompt, pages)
    out, pos, last = [first], len(prompt), first
    for _ in range(n_new - 1):
        (nxt,) = be.decode([(last, pos, pages)])
        pos, last = pos + 1, int(nxt)
        out.append(last)
    # pos is where out[-1] gets written by the NEXT decode — KV holds
    # positions [0, pos) and a continuation feeds (out[-1], pos, pages)
    return out, pages, pos


def test_gang_export_matches_single_rank_and_reimports():
    """A TP=2 gang driven lock-step produces the SAME tokens as a single
    rank; its per-rank export merges byte-identical to the single-rank
    export; and the gang export imports into a fresh single-rank backend
    that then continues decoding identically — drain/failover/hand-off
    interop by construction."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    single = type("_B", (object,), {})  # placeholder to appease linters
    single = __import__(
        "cordum_tpu.serving.backend", fromlist=["LlamaServingBackend"]
    ).LlamaServingBackend(cfg, num_pages=32, page_size=8,
                          params_provider=lambda: params)
    gang = ServingGangGroup(cfg, tp=2, num_pages=32, page_size=8,
                            params_provider=lambda: params)
    prompt = [7, 3, 11, 19, 2, 5, 23, 1, 13]
    n_new = 7
    toks_single, pages_s, end_s = drive_backend(single, prompt, n_new, reserve=5)
    toks_gang, pages_g, end_g = drive_backend(gang, prompt, n_new, reserve=5)
    assert toks_gang == toks_single == ref_greedy(cfg, params, prompt, n_new)
    assert gang.compiled_per_rank() == [1, 1]

    exp_single = single.export_kv(pages_s, 0, end_s)
    exp_gang = gang.export_kv(pages_g, 0, end_g)
    assert len(exp_gang) == 2 * len(exp_single)
    assert all(r["heads"] in ([0, 1], [1, 2]) for r in exp_gang)
    merged = merge_rank_records(exp_gang)
    assert len(merged) == len(exp_single)
    for m, s in zip(merged, exp_single):
        assert (m["i"], m["used"], m["shape"]) == (s["i"], s["used"], s["shape"])
        # the partitioned matmul's accumulation tiling differs from the
        # single-device program in the last few ulp of the arena dtype
        # (float32 here: 1 ulp = 1.2e-7 relative, and a two-layer stack
        # compounds it — the installed XLA shows 1.1e-6 on one element of
        # 512), so the bound is a few ulp, rtol 2e-5 with the same atol for
        # values near zero.  Token argmax is what must match exactly
        # (asserted above).
        for fld in ("k", "v"):
            a = np.frombuffer(m[fld], dtype=np.float32)
            b = np.frombuffer(s[fld], dtype=np.float32)
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    # fresh single-rank backend adopts the RAW per-rank gang export (its
    # base import_kv merges) and continues where the gang stopped
    fresh = __import__(
        "cordum_tpu.serving.backend", fromlist=["LlamaServingBackend"]
    ).LlamaServingBackend(cfg, num_pages=32, page_size=8,
                          params_provider=lambda: params)
    alloc = PageAllocator(fresh.num_pages, fresh.page_size)
    pages_f = alloc.alloc("s0", len(pages_g))
    fresh.import_kv(pages_f, exp_gang)
    # the satellite's byte-identity bar: the TP=2 session exported
    # rank-by-rank and re-imported exports BYTE-identical to the merged
    # single-rank record set — pure data movement, no recompute
    re_exp = fresh.export_kv(pages_f, 0, end_g)
    assert len(re_exp) == len(merged)
    for r, m in zip(re_exp, merged):
        assert (r["used"], r["shape"]) == (m["used"], m["shape"])
        assert r["k"] == m["k"] and r["v"] == m["v"]
    last = toks_gang[-1]
    cont_fresh, cont_gang, cont_single = [], [], []
    pos_f = pos_g = pos_s = end_g
    lf = lg = ls = last
    for _ in range(5):
        (nf,) = fresh.decode([(lf, pos_f, pages_f)])
        (ng,) = gang.decode([(lg, pos_g, pages_g)])
        (ns,) = single.decode([(ls, pos_s, pages_s)])
        cont_fresh.append(int(nf))
        cont_gang.append(int(ng))
        cont_single.append(int(ns))
        lf, lg, ls = int(nf), int(ng), int(ns)
        pos_f, pos_g, pos_s = pos_f + 1, pos_g + 1, pos_s + 1
    # the importer is indistinguishable from the gang it adopted the
    # session from — THE hand-off/drain invariant.  (The gang's arena sits
    # an ulp from the single-device one, so deep continuations may flip a
    # near-tie argmax vs the from-scratch oracle; the single-rank backend
    # itself stays the oracle's bit-exact twin.)
    assert cont_fresh == cont_gang
    assert cont_single == ref_greedy(cfg, params, prompt + toks_single, 5)


def test_follower_rank_skips_sampling():
    """A follower compiles with sample_logits=False: step results are the
    zero buffer (lm_head dead-code-eliminated) while its arena writes stay
    identical — proven by its export matching the sampling rank's slice."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    lead = ShardedServingBackend(cfg, rank=0, tp=2, num_pages=16, page_size=8,
                                 params_provider=lambda: params)
    follow = ShardedServingBackend(cfg, rank=1, tp=2, num_pages=16,
                                   page_size=8, params_provider=lambda: params)
    assert lead.sample_output and not follow.sample_output
    from cordum_tpu.serving.backend import StepEntry

    prompt = [9, 2, 7, 4]
    pages = [1, 2]
    entry = StepEntry(tokens=prompt, start=0, pages=pages, sample=True,
                      phase="prefill")
    (tok,) = lead.step([entry])
    (zero,) = follow.step([entry])
    assert int(tok) == ref_greedy(cfg, params, prompt, 1)[0]
    assert int(zero) == 0  # the DCE'd program returns the zero buffer
    lo, hi = lead.heads
    assert merge_rank_records(
        lead.export_kv(pages, 0, 4) + follow.export_kv(pages, 0, 4)
    )[0]["shape"][2] == cfg.n_kv_heads


# ---------------------------------------------------------------------------
# TP gang under the real engine: greedy + speculative oracle, compile count
# ---------------------------------------------------------------------------


async def test_tp2_engine_greedy_oracle_one_program_per_rank():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    gang = ServingGangGroup(cfg, tp=2, num_pages=32, page_size=8,
                            params_provider=lambda: params)
    eng = ServingEngine(gang, run_blocking=run_blocking,
                        max_new_tokens_cap=32, prefix_cache=False)
    prompts = {
        "g1": [7, 3, 11, 19, 2, 5, 23, 1, 13],
        "g2": [42, 9, 77, 5, 31],
    }
    subs = {
        jid: asyncio.ensure_future(eng.submit(
            GenRequest(prompt=p, max_new_tokens=8, stream=False), job_id=jid))
        for jid, p in prompts.items()
    }
    for jid, p in prompts.items():
        out = await asyncio.wait_for(subs[jid], timeout=180)
        assert out["tokens"] == ref_greedy(cfg, params, p, 8)
    # the acceptance bar: exactly ONE compiled ragged program per rank —
    # prefill chunks, mixed batches and decode all rode the same shapes
    assert gang.compiled_per_rank() == [1, 1]
    await eng.stop()


async def test_tp2_engine_speculative_oracle():
    """Speculative decoding over the gang: draft rows ride the same ragged
    program on every rank (followers replay identical entries), and the
    accepted stream is STILL bit-identical to the fp32 oracle."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    gang = ServingGangGroup(cfg, tp=2, num_pages=32, page_size=8,
                            params_provider=lambda: params)
    eng = ServingEngine(gang, run_blocking=run_blocking,
                        max_new_tokens_cap=32, prefix_cache=False,
                        speculative=True, draft_k=4)
    # a repetitive prompt gives the n-gram drafter something to accept
    prompt = [5, 9, 5, 9, 5, 9, 5, 9, 5, 9]
    out = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=10, stream=False),
        job_id="sp1"), timeout=180)
    assert out["tokens"] == ref_greedy(cfg, params, prompt, 10)
    assert gang.compiled_per_rank() == [1, 1]
    await eng.stop()


async def test_drain_with_gang_member_source_token_identical():
    """A session decoding on a TP=2 gang live-migrates to a SINGLE-rank
    peer mid-decode (the drain path with a gang as source): per-rank
    records ship on the wire, the receiver's base import merges them, and
    the finished stream equals the never-migrated oracle."""
    from cordum_tpu.serving.backend import LlamaServingBackend
    from cordum_tpu.serving.migration import MigrationServer, migrate_session

    cfg = tiny_cfg()
    params = tiny_params(cfg)
    gang = ServingGangGroup(cfg, tp=2, num_pages=32, page_size=8,
                            params_provider=lambda: params)
    a = ServingEngine(gang, run_blocking=run_blocking, max_new_tokens_cap=64,
                      prefix_cache=False)
    be_b = LlamaServingBackend(cfg, num_pages=32, page_size=8,
                               params_provider=lambda: params)
    b = ServingEngine(be_b, run_blocking=run_blocking, max_new_tokens_cap=64)
    results: dict = {}
    srv = MigrationServer(install_into(b, results))
    await srv.start()
    prompt = [7, 3, 11, 19, 2, 5, 23, 1, 13]
    src = asyncio.ensure_future(a.submit(
        GenRequest(prompt=prompt, max_new_tokens=20, stream=False),
        job_id="gm1"))
    await wait_until(
        lambda: (a.export_state("gm1") or {}).get("pos", 0) >= 12,
        timeout_s=180, msg="gang session mid-decode")
    assert await migrate_session(a, "gm1", srv.host, srv.port) is True
    with pytest.raises(SessionMigrated):
        await asyncio.wait_for(src, timeout=10)
    await wait_until(lambda: "gm1" in results, timeout_s=180,
                     msg="single-rank peer finished")
    assert results["gm1"] == ref_greedy(cfg, params, prompt, 20)
    assert a.allocator.used_pages == 0
    await a.stop(), await b.stop(), await srv.stop()


# ---------------------------------------------------------------------------
# statebus-backed cold tier: hibernated sessions survive a restart
# ---------------------------------------------------------------------------


async def test_statebus_cold_tier_restores_after_restart(kv):
    """serving_cold_tier=statebus: a session hibernated on worker
    generation 1 is journaled through the statebus KV; generation 2 (fresh
    engine, empty RAM) loads the journal and restores it token-identically.
    The restore consumes the journal entry."""
    from cordum_tpu.serving.tiering import StatebusColdTier

    def mk_engine():
        be = FakeBackend(num_pages=32, page_size=4, max_context=128,
                         step_delay=0.01)
        eng = ServingEngine(be, run_blocking=run_blocking,
                            max_new_tokens_cap=64)
        eng.tiering.arena = StatebusColdTier(kv, worker_id="w0")
        return eng

    eng1 = mk_engine()
    prompt = [3, 1, 4, 1, 5]
    src = asyncio.ensure_future(eng1.submit(
        GenRequest(prompt=prompt, max_new_tokens=24, stream=False,
                   session_key="hib"),
        job_id="h1"))
    await wait_until(
        lambda: (eng1.export_state("h1") or {}).get("pos", 0) >= 10,
        msg="session mid-decode")
    assert await eng1.hibernate_session("h1") is True
    with pytest.raises(Exception):
        await asyncio.wait_for(src, timeout=5)
    await eng1.tiering.arena.flush()
    assert await kv.keys("serving:cold:w0:") == ["serving:cold:w0:h1"]
    await eng1.stop()  # the "crash": RAM mirror dies with the process

    eng2 = mk_engine()
    assert "h1" not in eng2.tiering.arena
    assert await eng2.tiering.arena.load() == 1
    assert "h1" in eng2.tiering.arena
    fut = await eng2.restore_hibernated("h1")
    toks = await asyncio.wait_for(fut, timeout=20)
    assert toks == fake_ref(prompt, 24)
    await eng2.tiering.arena.flush()
    assert await kv.keys("serving:cold:w0:") == []  # journal consumed
    await eng2.stop()


def test_cold_tier_config_knob():
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.configschema import ConfigError

    pc = parse_pool_config(
        {"pools": {"tpu": {"serving_cold_tier": "statebus"}}})
    assert pc.pools["tpu"].serving_cold_tier == "statebus"
    assert parse_pool_config({"pools": {"tpu": {}}}) \
        .pools["tpu"].serving_cold_tier == ""
    with pytest.raises(ConfigError, match="serving_cold_tier"):
        parse_pool_config({"pools": {"tpu": {"serving_cold_tier": "redis"}}})


def test_bench_floor_gates_tp_keys():
    """bench_floor.json carries the ISSUE 20 contracts: token identity and
    one-program-per-rank are exact, tp_speedup is the 1-core-host collapse
    guard — and a MISSING tp key is itself a violation."""
    import json as _json
    import sys as _sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    _sys.path.insert(0, str(repo / "tools"))
    try:
        import check_bench_floor as mod
    finally:
        _sys.path.pop(0)
    floors = _json.loads((repo / "bench_floor.json").read_text())
    base = {"tp_token_identity": 1, "tp_speedup": 0.51,
            "tp_tokens_per_sec": 15.5, "tp_compile_per_rank": 1}
    assert not any("tp_" in v for v in mod.check(dict(base), floors))
    for key, bad in [("tp_token_identity", 0), ("tp_speedup", 0.1),
                     ("tp_tokens_per_sec", 0.0), ("tp_compile_per_rank", 2)]:
        doc = dict(base)
        doc[key] = bad
        assert any(key in v for v in mod.check(doc, floors)), key
    doc = dict(base)
    doc.pop("tp_token_identity")
    assert any("tp_token_identity" in v for v in mod.check(doc, floors))


# ---------------------------------------------------------------------------
# gang-aware capacity fusing + placement
# ---------------------------------------------------------------------------


def _gang_beacon(instance, *, gang="g1", rank, size=2, members=("wa", "wb"),
                 pages_total=64, pages_free=40, tokens_per_s=0.0, seq=0):
    from cordum_tpu.protocol.types import TelemetrySnapshot

    sg = {"gang_id": gang, "rank": rank, "size": size,
          "members": list(members), "pages_total": pages_total,
          "pages_free": pages_free}
    if rank == 0:
        sg["tokens_per_s"] = tokens_per_s
    block = {"v": 1, "seq": seq, "full": True, "device_kind": "cpu",
             "rows": {}, "serving_gang": sg}
    return TelemetrySnapshot(service="worker", instance=instance, seq=seq,
                             started_at_us=1, interval_s=2.0,
                             health={"role": "worker", "capacity": block})


def test_capacity_view_fuses_serving_gang_rows():
    """One fused row per gang: leader's measured tokens/s, min-of-ranks
    page headroom, members by rank; a beacon without the block clears the
    worker's membership."""
    from cordum_tpu.obs.capacity import CapacityView

    clock = [0.0]
    view = CapacityView(clock=lambda: clock[0])
    view.ingest(_gang_beacon("wa", rank=0, pages_free=40, tokens_per_s=321.5))
    view.ingest(_gang_beacon("wb", rank=1, pages_free=12))
    gangs = view.serving_gangs()
    assert set(gangs) == {"g1"}
    g = gangs["g1"]
    assert g["leader"] == "wa" and g["members"] == {"wa": 0, "wb": 1}
    assert g["tokens_per_s"] == 321.5
    assert g["pages_free_min"] == 12 and g["pages_total_min"] == 64
    assert view.serving_gang("wb")["rank"] == 1
    # the follower's next beacon drops the block: membership clears
    from cordum_tpu.protocol.types import TelemetrySnapshot

    view.ingest(TelemetrySnapshot(
        service="worker", instance="wb", seq=1, started_at_us=1,
        interval_s=2.0,
        health={"role": "worker",
                "capacity": {"v": 1, "seq": 1, "full": True,
                             "device_kind": "cpu", "rows": {}}}))
    assert view.serving_gang("wb") == {}
    assert view.serving_gangs()["g1"]["members"] == {"wa": 0}


def test_placer_excludes_followers_and_routes_to_faster_gang():
    """2-gang skew: follower ranks never take new sessions; the two
    leaders split placements in proportion to their gangs' fused measured
    step throughput (the acceptance-bar routing test)."""
    from cordum_tpu.controlplane.scheduler.placer import ServingPlacer

    from .test_disagg import StubView, hb

    class GangView(StubView):
        def __init__(self):
            super().__init__()
            self.gangs: dict[str, dict] = {}

        def serving_gangs(self):
            return {k: dict(v) for k, v in self.gangs.items()}

    view = GangView()
    for w in ("wa0", "wa1", "wb0", "wb1"):
        view.kv[w] = {"pages_total": 64, "pages_free": 64}
    view.gangs["ga"] = {
        "gang_id": "ga", "size": 2, "leader": "wa0",
        "members": {"wa0": 0, "wa1": 1}, "tokens_per_s": 300.0,
        "pages_free_min": 60, "pages_total_min": 64,
    }
    view.gangs["gb"] = {
        "gang_id": "gb", "size": 2, "leader": "wb0",
        "members": {"wb0": 0, "wb1": 1}, "tokens_per_s": 100.0,
        "pages_free_min": 60, "pages_total_min": 64,
    }
    placer = ServingPlacer(view)
    cands = [hb(w) for w in ("wa0", "wa1", "wb0", "wb1")]
    picks = {w: 0 for w in ("wa0", "wa1", "wb0", "wb1")}
    for _ in range(120):
        picks[placer.pick(cands)] += 1
    assert picks["wa1"] == picks["wb1"] == 0  # followers excluded outright
    assert picks["wa0"] + picks["wb0"] == 120
    assert picks["wa0"] >= 2 * picks["wb0"] > 0  # 3:1 fused-rate skew
    # min-of-ranks headroom gates the gang: the slow gang's tightest rank
    # filling up starves it entirely
    view.gangs["gb"]["pages_free_min"] = 0
    placer2 = ServingPlacer(view)
    assert all(placer2.pick(cands) == "wa0" for _ in range(10))


def test_serving_gang_renders():
    from cordum_tpu.controlplane.scheduler.gang import render_gang_table
    from cordum_tpu.obs.capacity import render_capacity_table

    cap = render_capacity_table({
        "workers": [], "totals": {},
        "serving_gangs": [{
            "gang_id": "g-1", "size": 2, "leader": "wa",
            "members": {"wa": 0, "wb": 1}, "tokens_per_s": 123.4,
            "pages_free_min": 12, "pages_total_min": 64,
        }],
    })
    assert "serving gangs" in cap and "wa:0" in cap and "wb:1" in cap
    assert "123.4" in cap
    tbl = render_gang_table({"gangs": [
        {"gang_id": "g-1", "job_id": "j-1", "state": "RUNNING",
         "kind": "serving", "workers": 2, "ready": 2, "done": 0,
         "age_s": 3.0, "members": ["wa", "wb"]},
        {"gang_id": "g-2", "job_id": "j-2", "state": "DONE",
         "workers": 2, "ready": 2, "done": 2, "age_s": 9.0,
         "members": ["wc", "wd"]},
    ]})
    assert "KIND" in tbl and "serving" in tbl
    assert "spmd" in tbl  # unkinded gangs render the SPMD default


# ---------------------------------------------------------------------------
# serving-gang e2e over the live gang-scheduler stack
# ---------------------------------------------------------------------------


async def test_serving_gang_e2e_token_identical_rank0_streams():
    """A 2-member serving gang over the real stack: all-or-nothing
    reservation, rendezvous, leader engine + follower replay, ONE terminal
    result whose tokens equal the fp32 oracle, rank-0-only stream packets,
    kind=serving in the gangs doc, and a clean ledger after."""
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import (
        LABEL_GANG_KIND,
        LABEL_GANG_WORKERS,
        BusPacket,
        JobRequest,
        STATUS_HINT_STREAM,
    )

    from .test_gang import make_stack, teardown, wait_state

    stack = await make_stack(2, peer_timeout_s=60.0)
    stream_senders = set()

    async def tap(subject, pkt):
        p = pkt.job_progress
        if p is not None and p.status_hint == STATUS_HINT_STREAM:
            stream_senders.add(p.worker_id)

    await stack.bus.subscribe(subj.PROGRESS, tap)
    cfg = tiny_cfg()
    try:
        prompt = [7, 3, 11, 19, 2, 5, 23, 1, 13]
        payload = {"op": "llm.generate",
                   "gang": {"kind": "serving", "workers": 2},
                   "prompts": [prompt], "max_new_tokens": 6,
                   "page_size": 8, "cache_pages": 32}
        ptr = await stack.store.put_context("g-serve", payload)
        req = JobRequest(
            job_id="g-serve", topic="job.gang", tenant_id="default",
            context_ptr=ptr,
            labels={LABEL_GANG_WORKERS: "2", LABEL_GANG_KIND: "serving"},
        )
        await stack.bus.publish(subj.SUBMIT,
                                BusPacket.wrap(req, sender_id="test"))
        assert await wait_state(stack.js, "g-serve", timeout_s=240) == "SUCCEEDED"
        res = await stack.store.get_result("g-serve")
        assert res["kind"] == "serving" and res["mode"] == "serving"
        lead = res["per_rank"]["0"]
        follow = res["per_rank"]["1"]
        # the gang runner builds its model from the payload seed (0) with
        # LlamaConfig.tiny() — the oracle uses the same derivation
        import dataclasses

        import jax
        import jax.numpy as jnp

        from cordum_tpu.models import llama

        ecfg = dataclasses.replace(llama.LlamaConfig.tiny(),
                                   dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), ecfg)
        assert lead["results"][0]["tokens"] == ref_greedy(
            ecfg, params, prompt, 6)
        # one compiled ragged program per rank; the follower replayed every
        # broadcast step and sampled nothing
        assert lead["compiled"] == 1 and follow["compiled"] == 1
        assert follow["steps_replayed"] == lead["steps"] > 0
        assert res["sessions"] == 1 and res["tokens"] == 6
        # rank 0 alone streamed
        assert len(stream_senders) == 1
        # observability: the live doc carried kind=serving while running —
        # the finished record keeps it
        gdoc = stack.gangs.doc()
        assert any(g["kind"] == "serving" for g in gdoc)
        assert stack.gangs.ledger.reserved_workers == {}
        assert stack.gangs.ledger.verify() == 0
        m = stack.eng.metrics
        assert m.serving_gang_steps.value(role="lead") > 0
        assert m.serving_gang_steps.value(role="replay") > 0
    finally:
        await teardown(stack)
