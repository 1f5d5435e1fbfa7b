"""Disaggregated prefill/decode serving (ISSUE 14, docs/SERVING.md
§Disaggregation): role-aware placement (ServingPlacer + strategy
integration + affinity retargeting), the post-prefill page hand-off
(engine hook, worker peer ranking, token-exactness of policy-triggered
migrations including mid-prefill threshold moves, jittered next-best
retry, failure-reason accounting), and the decode rebalancer (skew/
hysteresis/cooldown planning, worker-side cheapest-session moves, the
anti-ping-pong immunity window, cancel-after-hand-off ownership)."""
import asyncio
import random

import pytest

from cordum_tpu.controlplane.scheduler.placer import (
    DecodeRebalancer,
    ServingPlacer,
)
from cordum_tpu.controlplane.scheduler.strategy import ThroughputAwareStrategy
from cordum_tpu.infra.bus import LoopbackBus
from cordum_tpu.infra.config import parse_pool_config
from cordum_tpu.infra.kv import MemoryKV
from cordum_tpu.infra.memstore import MemoryStore
from cordum_tpu.infra.metrics import Metrics
from cordum_tpu.infra.registry import WorkerRegistry
from cordum_tpu.protocol import subjects as subj
from cordum_tpu.protocol.types import (
    BusPacket,
    Heartbeat,
    JobCancel,
    JobRequest,
    LABEL_BATCH_KEY,
    LABEL_MIGRATE_ADDR,
    LABEL_OP,
    LABEL_SESSION_KEY,
    SessionRebalance,
)
from cordum_tpu.serving.engine import GenRequest, ServingEngine
from cordum_tpu.serving.migration import MigrationServer, migrate_session

from .fakes import FakeBackend, fake_ref, run_blocking
from .test_serving_failover import (
    install_into,
    make_serving_worker,
    wait_until,
)


# ---------------------------------------------------------------------------
# a scripted CapacityView stand-in (the placer/rebalancer read interface)
# ---------------------------------------------------------------------------


class StubView:
    def __init__(self):
        self.rates: dict[tuple, float] = {}  # (wid, op) -> tokens/s
        self.kv: dict[str, dict] = {}
        self.occ: dict[str, dict] = {}
        self.roles: dict[str, str] = {}
        self.drain: dict[str, bool] = {}

    def token_rate(self, wid, op):
        return self.rates.get((wid, op), 0.0)

    def rate(self, wid, op):
        return self.rates.get((wid, op), 0.0)

    def kv_pages(self, wid):
        return dict(self.kv.get(wid, {}))

    def decode_occupancy(self, wid):
        return dict(self.occ.get(wid, {}))

    def serving_role(self, wid):
        return self.roles.get(wid, "")

    def draining(self, wid):
        return self.drain.get(wid, False)

    def serving_workers(self):
        return [w for w in self.kv if self.kv[w]]


def hb(wid, **kw):
    kw.setdefault("pool", "tpu")
    kw.setdefault("max_parallel_jobs", 1 << 30)
    return Heartbeat(worker_id=wid, **kw)


# ---------------------------------------------------------------------------
# ServingPlacer
# ---------------------------------------------------------------------------


def test_placer_routes_by_prefill_rate_and_excludes_decode_role():
    """New sessions go to prefill-capable workers in proportion to
    measured prefill tokens/s × page headroom; decode-roled workers are
    excluded while any prefill-capable worker exists."""
    view = StubView()
    view.rates[("w-pre", "llm.prefill")] = 300.0
    view.rates[("w-mix", "llm.prefill")] = 100.0
    view.rates[("w-dec", "llm.prefill")] = 900.0  # fastest — but decode-roled
    view.roles.update({"w-pre": "prefill", "w-mix": "mixed",
                       "w-dec": "decode"})
    for w in ("w-pre", "w-mix", "w-dec"):
        view.kv[w] = {"pages_total": 100, "pages_free": 100}
    placer = ServingPlacer(view)
    cands = [hb("w-pre"), hb("w-mix"), hb("w-dec")]
    picks = {w: 0 for w in ("w-pre", "w-mix", "w-dec")}
    for _ in range(120):
        picks[placer.pick(cands)] += 1
    assert picks["w-dec"] == 0
    assert picks["w-pre"] + picks["w-mix"] == 120
    # smooth WRR converges to the 3:1 rate ratio
    assert picks["w-pre"] >= 2 * picks["w-mix"] > 0


def test_placer_headroom_scales_weight_and_full_arena_excluded():
    view = StubView()
    view.rates[("w-a", "llm.prefill")] = 100.0
    view.rates[("w-b", "llm.prefill")] = 100.0
    view.kv["w-a"] = {"pages_total": 100, "pages_free": 90}
    view.kv["w-b"] = {"pages_total": 100, "pages_free": 10}
    placer = ServingPlacer(view)
    cands = [hb("w-a"), hb("w-b")]
    picks = {"w-a": 0, "w-b": 0}
    for _ in range(100):
        picks[placer.pick(cands)] += 1
    assert picks["w-a"] >= 5 * picks["w-b"] > 0  # 9:1 headroom skew
    # a full arena gets nothing
    view.kv["w-b"]["pages_free"] = 0
    placer2 = ServingPlacer(view)
    assert all(placer2.pick(cands) == "w-a" for _ in range(10))


def test_placer_degrades_without_measurement_or_candidates():
    view = StubView()
    placer = ServingPlacer(view)
    assert placer.pick([hb("w-a")]) == ""  # nothing measured anywhere
    assert placer.fallbacks == 1
    view.drain["w-a"] = True
    view.rates[("w-a", "llm.prefill")] = 100.0
    assert placer.pick([hb("w-a")]) == ""  # only candidate is draining


# ---------------------------------------------------------------------------
# strategy integration + affinity retargeting
# ---------------------------------------------------------------------------


def _mk_strategy(view):
    reg = WorkerRegistry()
    pc = parse_pool_config({"topics": {"job.tpu.generate": "tpu"},
                            "pools": {"tpu": {}}})
    strat = ThroughputAwareStrategy(reg, pc, capacity=view,
                                    placer=ServingPlacer(view), native=False)
    return strat, reg


def test_strategy_serving_jobs_route_via_placer_then_stick():
    view = StubView()
    view.rates[("w-pre", "llm.prefill")] = 500.0
    view.rates[("w-dec", "llm.prefill")] = 500.0
    view.roles.update({"w-pre": "prefill", "w-dec": "decode"})
    view.kv["w-pre"] = {"pages_total": 100, "pages_free": 100}
    view.kv["w-dec"] = {"pages_total": 100, "pages_free": 100}
    strat, reg = _mk_strategy(view)
    reg.update(hb("w-pre"))
    reg.update(hb("w-dec"))
    req = JobRequest(job_id="j1", topic="job.tpu.generate",
                     labels={LABEL_OP: "llm.generate",
                             LABEL_SESSION_KEY: "conv-1"})
    assert strat.pick_subject(req) == "worker.w-pre.jobs"
    assert strat.routed_placed == 1
    # the follow-up turn rides session affinity, not a fresh placement
    req2 = JobRequest(job_id="j2", topic="job.tpu.generate",
                      labels={LABEL_OP: "llm.generate",
                              LABEL_SESSION_KEY: "conv-1"})
    assert strat.pick_subject(req2) == "worker.w-pre.jobs"
    assert strat.session_affinity_hits == 1 and strat.routed_placed == 1


def test_strategy_placer_fallback_is_generic_routing():
    """An empty prefill matrix must not break serving jobs: the placer
    returns "" and the generic measured-items/s (→ LeastLoaded) path
    routes as before."""
    view = StubView()
    strat, reg = _mk_strategy(view)
    reg.update(hb("w-a"))
    req = JobRequest(job_id="j1", topic="job.tpu.generate",
                     labels={LABEL_OP: "llm.generate"})
    assert strat.pick_subject(req) == "worker.w-a.jobs"
    assert strat.routed_placed == 0


def test_retarget_session_follows_ownership():
    """A SessionMoved announcement repoints the session's affinity: the
    next turn routes to the adopting worker, not the original placement."""
    view = StubView()
    view.rates[("w-pre", "llm.prefill")] = 500.0
    view.roles["w-pre"] = "prefill"
    view.roles["w-dec"] = "decode"  # excluded from new-session placement
    view.kv["w-pre"] = {"pages_total": 100, "pages_free": 100}
    strat, reg = _mk_strategy(view)
    reg.update(hb("w-pre"))
    reg.update(hb("w-dec"))
    first = strat.pick_subject(JobRequest(
        job_id="j1", topic="job.tpu.generate",
        labels={LABEL_OP: "llm.generate", LABEL_SESSION_KEY: "conv-9"}))
    assert first == "worker.w-pre.jobs"
    strat.retarget_session("conv-9", "w-dec")
    assert strat.session_affinity_retargeted == 1
    nxt = strat.pick_subject(JobRequest(
        job_id="j2", topic="job.tpu.generate",
        labels={LABEL_OP: "llm.generate", LABEL_SESSION_KEY: "conv-9"}))
    assert nxt == "worker.w-dec.jobs"


def test_batch_sticky_win_still_elects_session_affinity():
    """A session-carrying job routed by its batch key (a workflow turn
    riding wf-tpl template co-location, docs/SERVING.md §Prefix cache and
    tiering) must still record its session entry: the batch-sticky early
    return used to skip the election, so every later turn of the run
    counted "new" and could never hit."""
    view = StubView()
    strat, reg = _mk_strategy(view)
    reg.update(hb("w-a"))
    reg.update(hb("w-b"))
    # establish the template's batch entry (turn 1 of some sibling run)
    first = strat.pick_subject(JobRequest(
        job_id="r1:plan@1", topic="job.tpu.generate",
        labels={LABEL_OP: "llm.generate", LABEL_BATCH_KEY: "wf-tpl:agent"}))
    # a session whose affinity entry is absent rides the batch key ...
    second = strat.pick_subject(JobRequest(
        job_id="r2:plan@1", topic="job.tpu.generate",
        labels={LABEL_OP: "llm.generate", LABEL_BATCH_KEY: "wf-tpl:agent",
                LABEL_SESSION_KEY: "run-7"}))
    assert second == first
    # ... and that ride must have elected the session entry: the follow-up
    # turn (no batch key — e.g. a direct cancel/turn on the session) hits
    third = strat.pick_subject(JobRequest(
        job_id="r2:act@1", topic="job.tpu.generate",
        labels={LABEL_OP: "llm.generate", LABEL_SESSION_KEY: "run-7"}))
    assert third == first
    assert strat.session_affinity_hits == 1, (
        strat.session_affinity_hits, strat.session_affinity_new)


# ---------------------------------------------------------------------------
# engine: hand-off hook + rebalance picking
# ---------------------------------------------------------------------------


async def test_handoff_hook_fires_once_on_prefill_completion():
    be = FakeBackend(num_pages=32, step_delay=0.002)
    eng = ServingEngine(be, run_blocking=run_blocking, max_new_tokens_cap=64)
    fired = []
    eng.on_prefill_done = fired.append
    out = await eng.submit(GenRequest(prompt=[1, 2, 3], max_new_tokens=10,
                                      stream=False), job_id="h1")
    assert out["tokens"] == fake_ref([1, 2, 3], 10)
    assert fired == ["h1"]  # once, not per step
    await eng.stop()


async def test_handoff_hook_threshold_fires_mid_prefill():
    """serving_handoff_tokens > 0: the hook fires while the prompt is
    still prefilling, so long prompts start moving before ingestion
    finishes."""
    be = FakeBackend(num_pages=64, max_context=512, step_delay=0.002,
                     max_batch_tokens=8)
    eng = ServingEngine(be, run_blocking=run_blocking, max_new_tokens_cap=64,
                        handoff_threshold_tokens=8)
    state_at_fire = {}

    def hook(job_id):
        state_at_fire[job_id] = dict(eng.export_state(job_id))

    eng.on_prefill_done = hook
    prompt = list(range(1, 31))  # 30 tokens, chunked at <=8/step
    out = await eng.submit(GenRequest(prompt=prompt, max_new_tokens=5,
                                      stream=False), job_id="t1")
    assert out["tokens"] == fake_ref(prompt, 5)
    assert "t1" in state_at_fire
    assert 8 <= state_at_fire["t1"]["prefill_pos"] < len(prompt)
    await eng.stop()


async def test_policy_handoff_token_exact_property():
    """Acceptance: policy-triggered migrations are token-exact — the
    engine hook (completion AND mid-prefill threshold variants, random
    prompts) drives migrate_session to a peer and the relocated stream
    equals the sequential oracle."""
    rng = random.Random(23)
    for trial in range(4):
        threshold = rng.choice([0, 4, 9])
        a = ServingEngine(
            FakeBackend(num_pages=64, max_context=512, step_delay=0.002,
                           max_batch_tokens=8),
            run_blocking=run_blocking, max_new_tokens_cap=600,
            handoff_threshold_tokens=threshold)
        b = ServingEngine(FakeBackend(num_pages=64, max_context=512,
                                         step_delay=0.002),
                          run_blocking=run_blocking, max_new_tokens_cap=600)
        results: dict = {}
        srv = MigrationServer(install_into(b, results))
        await srv.start()
        moves: list = []

        def hook(job_id):
            moves.append(asyncio.ensure_future(
                migrate_session(a, job_id, srv.host, srv.port)))

        a.on_prefill_done = hook
        plen = rng.randint(1, 24)
        prompt = [rng.randrange(1, 200) for _ in range(plen)]
        n_new = rng.randint(2, 40)
        jid = f"ph{trial}"
        src = asyncio.ensure_future(a.submit(
            GenRequest(prompt=prompt, max_new_tokens=n_new, stream=False),
            job_id=jid))
        await wait_until(lambda: moves, msg="hand-off fired")
        moved = await moves[0]
        if moved:
            with pytest.raises(Exception):
                await asyncio.wait_for(src, timeout=10)
            await wait_until(lambda: jid in results, msg="target finished")
            got = results[jid]
            assert b.stats.migrated_in == 1
        else:  # racy finish before freeze: local completion is also exact
            got = (await asyncio.wait_for(src, timeout=10))["tokens"]
        assert got == fake_ref(prompt, n_new), (trial, threshold, prompt)
        await a.stop(), await b.stop(), await srv.stop()


async def test_mid_prefill_handoff_matches_oracle_real_backend():
    """The fp32 oracle check for a threshold hand-off that fires while the
    prompt is mid-prefill on the REAL paged backend: partially filled
    pages + prefill progress move worker→worker and the finished stream is
    token-identical to the uninterrupted run."""
    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend

    from .test_serving import ref_greedy

    cfg = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=128,
                            dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    bea = LlamaServingBackend(cfg, num_pages=64, page_size=8,
                              max_seqs=4, max_batch_tokens=12,
                              params_provider=lambda: params)
    beb = LlamaServingBackend(cfg, num_pages=64, page_size=8,
                              params_provider=lambda: params)
    a = ServingEngine(bea, run_blocking=run_blocking, max_new_tokens_cap=64,
                      handoff_threshold_tokens=9)
    b = ServingEngine(beb, run_blocking=run_blocking, max_new_tokens_cap=64)
    results: dict = {}
    srv = MigrationServer(install_into(b, results))
    await srv.start()
    fired = asyncio.Event()
    prefill_pos_at_fire = []

    def hook(job_id):
        prefill_pos_at_fire.append(a.export_state(job_id)["prefill_pos"])
        fired.set()
        asyncio.ensure_future(migrate_session(a, job_id, srv.host, srv.port))

    a.on_prefill_done = hook
    prompt = [7, 3, 11, 19, 2, 5, 23, 1, 13, 40, 9, 4, 17, 31, 2, 8, 5, 90,
              33, 12]  # 20 tokens: several chunks at <=12/step
    src = asyncio.ensure_future(a.submit(
        GenRequest(prompt=prompt, max_new_tokens=12, stream=False),
        job_id="mp1"))
    await asyncio.wait_for(fired.wait(), timeout=120)
    assert prefill_pos_at_fire[0] < len(prompt)  # genuinely mid-prefill
    try:
        out = (await asyncio.wait_for(src, timeout=120))["tokens"]
    except Exception:  # SessionMigrated: the target owns the result
        await wait_until(lambda: "mp1" in results, timeout_s=120,
                         msg="target finished")
        out = results["mp1"]
        assert b.stats.migrated_in == 1
    assert out == ref_greedy(cfg, params, prompt, 12)
    await a.stop(), await b.stop(), await srv.stop()


async def test_pick_rebalance_sessions_cheapest_and_immunity():
    """Cheapest = fewest live pages then oldest decode position; a
    migrated-in session is immune until its cooldown passes; drain's
    session_ids ignores immunity."""
    be = FakeBackend(num_pages=64, max_context=512, step_delay=0.01)
    eng = ServingEngine(be, run_blocking=run_blocking, max_new_tokens_cap=600,
                        migrate_in_cooldown_s=0.3)
    waiters = []
    for i, plen in enumerate((14, 2, 8)):  # page footprints 9,6,7 (ps=4)
        waiters.append(asyncio.ensure_future(eng.submit(
            GenRequest(prompt=list(range(1, plen + 1)), max_new_tokens=20,
                       stream=False), job_id=f"s{i}")))
    await wait_until(
        lambda: all((eng.export_state(f"s{i}") or {}).get("pos", 0)
                    > 0 for i in range(3)),
        msg="all sessions decoding")
    order = eng.pick_rebalance_sessions(3)
    assert order[0] == "s1" and set(order) == {"s0", "s1", "s2"}
    # adopt a migrated-in session: immune, so not pickable yet
    fut = await eng.install_session(
        GenRequest(prompt=[5], max_new_tokens=60, stream=False),
        job_id="adopted",
        state={"pos": 1, "prefill_pos": 1, "out_tokens": [9],
               "last_token": 9},
        records=[])
    assert "adopted" not in eng.pick_rebalance_sessions(4)
    assert "adopted" in eng.session_ids()  # drain still moves it
    await asyncio.sleep(0.35)  # cooldown passes → movable again
    assert "adopted" in eng.pick_rebalance_sessions(4)
    for w in waiters:
        w.cancel()
    fut.cancel()
    await eng.stop()


# ---------------------------------------------------------------------------
# DecodeRebalancer planning
# ---------------------------------------------------------------------------


def _mk_rebalancer(view, reg, **kw):
    kw.setdefault("hysteresis_ticks", 2)
    kw.setdefault("cooldown_s", 30.0)
    clock = [0.0]
    rb = DecodeRebalancer(None, view, reg, clock=lambda: clock[0], **kw)
    return rb, clock


def _serving_fleet_view(hot_sessions=8, hot_in_use=90):
    view = StubView()
    view.kv["w-hot"] = {"pages_total": 100,
                        "pages_free": 100 - hot_in_use,
                        "pages_in_use": hot_in_use}
    view.occ["w-hot"] = {"active_sessions": hot_sessions}
    view.kv["w-cold"] = {"pages_total": 100, "pages_free": 90,
                         "pages_in_use": 10}
    view.occ["w-cold"] = {"active_sessions": 2}
    view.rates[("w-cold", "llm.generate")] = 100.0
    return view


def test_rebalancer_skew_needs_hysteresis_then_cooldown_limits():
    view = _serving_fleet_view()
    reg = WorkerRegistry()
    reg.update(hb("w-hot", labels={LABEL_MIGRATE_ADDR: "127.0.0.1:1"}))
    reg.update(hb("w-cold", labels={LABEL_MIGRATE_ADDR: "127.0.0.1:2"}))
    rb, clock = _mk_rebalancer(view, reg, max_moves=2)
    assert rb.plan() == []  # tick 1: hot, but hysteresis holds fire
    cmds = rb.plan()  # tick 2: consecutive → command
    assert len(cmds) == 1
    cmd = cmds[0]
    assert cmd.worker_id == "w-hot" and cmd.target_worker == "w-cold"
    assert cmd.target_addr == "127.0.0.1:2"
    assert 1 <= cmd.max_sessions <= 2
    # still hot: the per-worker cooldown rate-limits further commands
    assert rb.plan() == [] and rb.plan() == []
    clock[0] += 31.0
    # continuously hot through the cooldown: fires again on expiry
    assert len(rb.plan()) == 1


def test_rebalancer_ignores_balanced_draining_and_single_worker():
    # 3 vs 2 sessions and 12 vs 10 pages in use: within skew ratio
    view = _serving_fleet_view(hot_sessions=3, hot_in_use=12)
    reg = WorkerRegistry()
    reg.update(hb("w-hot", labels={LABEL_MIGRATE_ADDR: "127.0.0.1:1"}))
    reg.update(hb("w-cold", labels={LABEL_MIGRATE_ADDR: "127.0.0.1:2"}))
    rb, _ = _mk_rebalancer(view, reg, skew_ratio=2.0)
    assert rb.plan() == [] and rb.plan() == []
    # a draining target never receives moves; with it gone there is only
    # one worker left → no plan either
    view.occ["w-hot"]["active_sessions"] = 8
    view.drain["w-cold"] = True
    assert rb.plan() == [] and rb.plan() == []


def test_rebalancer_page_pressure_alone_can_mark_hot():
    view = StubView()
    view.kv["w-hot"] = {"pages_total": 100, "pages_free": 5,
                        "pages_in_use": 95}
    view.occ["w-hot"] = {"active_sessions": 3}
    view.kv["w-cold"] = {"pages_total": 100, "pages_free": 80,
                         "pages_in_use": 20}
    view.occ["w-cold"] = {"active_sessions": 3}  # occupancy balanced
    reg = WorkerRegistry()
    reg.update(hb("w-hot", labels={LABEL_MIGRATE_ADDR: "127.0.0.1:1"}))
    reg.update(hb("w-cold", labels={LABEL_MIGRATE_ADDR: "127.0.0.1:2"}))
    rb, _ = _mk_rebalancer(view, reg)
    rb.plan()
    cmds = rb.plan()
    assert len(cmds) == 1 and "pressure" in cmds[0].reason


# ---------------------------------------------------------------------------
# worker e2e: hand-off, rebalance command, ping-pong immunity, cancel
# ---------------------------------------------------------------------------


def make_role_worker(bus, ms, wid, role, *, step_delay=0.01, metrics=None,
                     **eng_kw):
    w = make_serving_worker(bus, ms, wid, step_delay=step_delay,
                            metrics=metrics, **eng_kw)
    w.serving_role = role
    if role == "prefill":
        w.serving.on_prefill_done = w._on_prefill_done
    return w


async def submit_gen(bus, ms, wid, jid, prompt, n_new, *, session=None):
    ptr = await ms.put_context(jid, {
        "op": "llm.generate", "tokens": prompt, "max_new_tokens": n_new,
        "session_id": session or f"conv-{jid}",
    })
    await bus.publish(subj.direct_subject(wid), BusPacket.wrap(JobRequest(
        job_id=jid, topic="job.tpu.generate", context_ptr=ptr)))


class ResultTap:
    def __init__(self):
        self.results: dict[str, object] = {}

    async def __call__(self, subject, pkt):
        res = pkt.job_result
        if res is not None and res.status in ("SUCCEEDED", "CANCELLED",
                                              "FAILED"):
            self.results[res.job_id] = res


async def test_prefill_worker_hands_off_to_decode_peer_e2e():
    """The tentpole path end to end: a session submitted to a
    prefill-roled worker prefills there, live-migrates to the decode peer
    once the prompt completes, finishes token-exact from the NEW owner,
    and the adopting worker announces ownership (SessionMoved)."""
    bus = LoopbackBus()
    ms = MemoryStore(MemoryKV())
    metrics = Metrics()
    w1 = make_role_worker(bus, ms, "w-pre", "prefill", metrics=metrics)
    w2 = make_role_worker(bus, ms, "w-dec", "decode", metrics=metrics)
    await w1.start()
    await w2.start()
    moved = []

    async def tap_moved(subject, pkt):
        if pkt.session_moved is not None:
            moved.append(pkt.session_moved)

    await bus.subscribe(subj.SERVING_MOVED, tap_moved)
    tap = ResultTap()
    await bus.subscribe(subj.RESULT, tap)
    await w1.send_heartbeat()
    await w2.send_heartbeat()
    await bus.drain()
    assert "w-dec" in w1._peers and w1._peers["w-dec"]["role"] == "decode"
    prompt = [4, 9, 2]
    await submit_gen(bus, ms, "w-pre", "ho1", prompt, 40, session="conv-ho")
    await wait_until(lambda: "ho1" in tap.results, msg="job finished")
    res = tap.results["ho1"]
    assert res.status == "SUCCEEDED" and res.worker_id == "w-dec"
    assert (await ms.get_result("ho1"))["tokens"] == fake_ref(prompt, 40)
    assert w1.serving.stats.migrated_out == 1
    assert w2.serving.stats.migrated_in == 1
    assert metrics.serving_handoffs.total() >= 1
    assert moved and moved[0].to_worker == "w-dec"
    assert moved[0].session_key == "conv-ho"
    assert moved[0].reason == "handoff"
    # both arenas end clean: the target holds what its prefix cache kept
    await wait_until(
        lambda: w2.serving.allocator.used_pages == w2.serving.prefix.warm_pages,
        msg="target freed")
    assert w1.serving.allocator.used_pages == 0
    await w1.stop(), await w2.stop(), await bus.close()


async def test_cancel_after_handoff_reaches_new_owner():
    """Acceptance: session affinity follows ownership — a cancel issued
    after the hand-off lands on the adopting worker, which retires the
    session (pages freed) and publishes the CANCELLED result."""
    bus = LoopbackBus()
    ms = MemoryStore(MemoryKV())
    w1 = make_role_worker(bus, ms, "w-pre", "prefill", step_delay=0.02)
    w2 = make_role_worker(bus, ms, "w-dec", "decode", step_delay=0.02)
    await w1.start()
    await w2.start()
    tap = ResultTap()
    await bus.subscribe(subj.RESULT, tap)
    await w1.send_heartbeat()
    await w2.send_heartbeat()
    await bus.drain()
    await submit_gen(bus, ms, "w-pre", "ca1", [3, 1, 4], 100,
                     session="conv-ca")
    await wait_until(lambda: w2.serving.stats.migrated_in == 1,
                     msg="hand-off committed")
    await bus.publish(subj.CANCEL, BusPacket.wrap(JobCancel(job_id="ca1")))
    await wait_until(lambda: "ca1" in tap.results, msg="cancel published")
    res = tap.results["ca1"]
    assert res.status == "CANCELLED" and res.worker_id == "w-dec"
    assert w2.serving.stats.cancelled == 1
    await wait_until(lambda: w2.serving.allocator.used_pages == 0,
                     msg="pages freed on new owner")
    await w1.stop(), await w2.stop(), await bus.close()


async def test_rebalance_command_moves_cheapest_then_immunity_blocks_pingpong():
    """Acceptance: the governor's move lands the cheapest session on the
    target, where it is cooldown-immune — an immediate reverse command
    (oscillating skew) moves NOTHING back."""
    bus = LoopbackBus()
    ms = MemoryStore(MemoryKV())
    metrics = Metrics()
    w1 = make_role_worker(bus, ms, "w-a", "decode", step_delay=0.02,
                          metrics=metrics)
    w2 = make_role_worker(bus, ms, "w-b", "decode", step_delay=0.02,
                          metrics=metrics)
    await w1.start()
    await w2.start()
    await w1.send_heartbeat()
    await w2.send_heartbeat()
    await bus.drain()
    for i, plen in enumerate((9, 2)):  # rb1 is the cheaper session
        await submit_gen(bus, ms, "w-a", f"rb{i}",
                         list(range(1, plen + 1)), 80)
    await wait_until(lambda: w1.serving.active_sessions() == 2,
                     msg="sessions on w-a")
    await wait_until(
        lambda: all((w1.serving.export_state(f"rb{i}") or {}).get("pos", 0)
                    > 0 for i in range(2)),
        msg="decoding")
    await bus.publish(subj.SERVING_REBALANCE, BusPacket.wrap(
        SessionRebalance(worker_id="w-a", target_worker="w-b",
                         target_addr=w2._migration.addr, max_sessions=1)))
    await wait_until(lambda: w2.serving.stats.migrated_in == 1,
                     msg="rebalance move landed")
    assert w2.serving.describe_session("rb1") is not None  # the cheap one
    moved_before = w1.serving.stats.migrated_in
    # oscillation: the governor immediately asks w-b to shed — the
    # migrated-in session is immune, so nothing moves back
    await bus.publish(subj.SERVING_REBALANCE, BusPacket.wrap(
        SessionRebalance(worker_id="w-b", target_worker="w-a",
                         target_addr=w1._migration.addr, max_sessions=1)))
    await bus.drain()
    await asyncio.sleep(0.1)
    assert w1.serving.stats.migrated_in == moved_before  # no ping-pong
    assert metrics.serving_rebalances.value(stage="no_sessions") >= 1
    assert metrics.serving_rebalances.value(stage="moved") >= 1
    await w1.stop(), await w2.stop(), await bus.close()


async def test_handoff_retries_next_best_target_and_labels_failure():
    """Satellite: a failed handshake retries once (jittered) against the
    next-best peer instead of silently abandoning the hand-off, and the
    failure counter carries a {reason} label."""
    bus = LoopbackBus()
    ms = MemoryStore(MemoryKV())
    metrics = Metrics()
    w1 = make_role_worker(bus, ms, "w-pre", "prefill", step_delay=0.02,
                          metrics=metrics)
    w2 = make_role_worker(bus, ms, "w-dec", "decode", step_delay=0.02,
                          metrics=metrics)
    await w1.start()
    await w2.start()
    tap = ResultTap()
    await bus.subscribe(subj.RESULT, tap)
    await w2.send_heartbeat()
    await bus.drain()
    import time as _t

    # a dead peer that outranks the live one (more free pages)
    w1._peers["w-ghost"] = {
        "addr": "127.0.0.1:1", "pages_free": 10_000, "decode_tps": 999.0,
        "role": "decode", "draining": False, "seen": _t.monotonic(),
    }
    ranked = w1._ranked_handoff_peers()
    assert ranked[0][0] == "w-ghost" and ranked[1][0] == "w-dec"
    prompt = [8, 8, 1]
    await submit_gen(bus, ms, "w-pre", "rt1", prompt, 40)
    await wait_until(lambda: "rt1" in tap.results, msg="job finished")
    assert tap.results["rt1"].status == "SUCCEEDED"
    assert tap.results["rt1"].worker_id == "w-dec"  # landed on the retry
    assert (await ms.get_result("rt1"))["tokens"] == fake_ref(prompt, 40)
    assert metrics.serving_handoffs.value(outcome="retried_ok") == 1
    # the dead target's handshake failure is reason-labeled
    assert metrics.serving_migration_failures.value(reason="io") >= 1
    await w1.stop(), await w2.stop(), await bus.close()
