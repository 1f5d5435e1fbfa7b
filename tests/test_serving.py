"""Serving subsystem tests (ISSUE 7, docs/SERVING.md): page allocator
invariants (exhaustion → admission, reuse never leaks, fragmentation-free),
paged decode == full-forward greedy, continuous-batching join/leave
equivalence, cancel-of-stateful-jobs, scheduler session affinity, gateway
session-key stamping, and the SDK streaming helper."""
import asyncio
import random
import time

import pytest

from cordum_tpu.serving.engine import GenRequest, ServingEngine, SessionCancelled
from cordum_tpu.serving.pager import CacheExhausted, PageAllocator

from .fakes import FakeBackend, fake_ref, run_blocking


# ---------------------------------------------------------------- allocator


def test_pager_alloc_free_roundtrip():
    a = PageAllocator(num_pages=8, page_size=4)
    assert a.capacity == 7  # page 0 is the null page, never allocatable
    assert a.pages_for(1) == 1 and a.pages_for(4) == 1 and a.pages_for(5) == 2
    p1 = a.alloc("s1", 3)
    assert len(p1) == 3 and a.NULL_PAGE not in p1
    assert a.free_pages == 4 and a.used_pages == 3
    assert a.owner_pages("s1") == p1
    # cumulative per-owner alloc (a session growing its footprint)
    p2 = a.alloc("s1", 2)
    assert a.owner_pages("s1") == p1 + p2
    assert a.free("s1") == 5
    assert a.free_pages == 7
    assert a.free("s1") == 0  # double-free is a benign no-op
    assert a.free("never-seen") == 0


def test_pager_exhaustion_is_all_or_nothing():
    a = PageAllocator(num_pages=6, page_size=4)
    a.alloc("s1", 3)
    with pytest.raises(CacheExhausted):
        a.alloc("s2", 3)  # only 2 free
    # the failed alloc must not strand partial pages
    assert a.free_pages == 2 and a.owner_pages("s2") == []
    assert a.stats.exhaustions == 1
    a.free("s1")
    assert len(a.alloc("s2", 3)) == 3  # retirement unblocks the waiter


def test_pager_pages_never_shared_and_reuse_after_random_frees():
    """Page-granular free lists cannot fragment: after freeing owners in a
    random order, the full capacity is allocatable again, and no page is
    ever owned by two sessions at once."""
    rng = random.Random(7)
    a = PageAllocator(num_pages=33, page_size=8)
    owners = [f"s{i}" for i in range(8)]
    for i, o in enumerate(owners):
        a.alloc(o, (i % 4) + 1)
    seen: set[int] = set()
    for o in owners:
        pages = a.owner_pages(o)
        assert not (seen & set(pages)), "page owned by two sessions"
        seen.update(pages)
    rng.shuffle(owners)
    for o in owners:
        a.free(o)
    # no fragmentation: one owner can take every usable page
    assert len(a.alloc("big", a.capacity)) == 32
    assert a.free_pages == 0


def test_pager_rejects_degenerate_shapes():
    with pytest.raises(ValueError):
        PageAllocator(num_pages=1, page_size=4)  # null page only
    with pytest.raises(ValueError):
        PageAllocator(num_pages=4, page_size=0)
    a = PageAllocator(num_pages=4, page_size=4)
    with pytest.raises(ValueError):
        a.alloc("s", 0)


# ----------------------------------------------------- paged decode (jax)


@pytest.fixture(scope="module")
def llama_env():
    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend

    # fp32: the equality oracle compares argmax between the paged path and
    # the full forward, whose accumulation orders differ — bf16 rounding can
    # flip near-ties and turn an exact-math test flaky
    cfg = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=128,
                            dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    backend = LlamaServingBackend(
        cfg, num_pages=64, page_size=8, params_provider=lambda: params
    )
    return cfg, params, backend


def ref_greedy(cfg, params, prompt, n_new):
    """Sequential per-session decode oracle: full forward over the growing
    sequence, greedy argmax."""
    import jax.numpy as jnp

    from cordum_tpu.models import llama

    toks, out = list(prompt), []
    for _ in range(n_new):
        logits = llama.forward(params, jnp.asarray([toks], jnp.int32), cfg)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def paged_greedy(backend, alloc, owner, prompt, n_new):
    pages = alloc.alloc(owner, alloc.pages_for(len(prompt) + n_new))
    first = backend.prefill(prompt, pages)
    out, pos, last = [first], len(prompt), first
    for _ in range(n_new - 1):
        (nxt,) = backend.decode([(last, pos, pages)])
        pos, last = pos + 1, int(nxt)
        out.append(last)
    return out


def test_paged_decode_matches_full_forward(llama_env):
    """Prefill + paged decode steps reproduce full-forward greedy exactly —
    the paged KV cache is a cache, not an approximation."""
    cfg, params, be = llama_env
    alloc = PageAllocator(be.num_pages, be.page_size)
    # the 9-token prompt spans two pages (page_size=8): the multi-page
    # prefill scatter path is covered, not just single-page sessions
    for i, prompt in enumerate([[5, 9, 17, 3], [100, 42],
                                [7, 3, 11, 19, 2, 5, 23, 1, 13]]):
        assert paged_greedy(be, alloc, f"s{i}", prompt, 6) == ref_greedy(
            cfg, params, prompt, 6
        )


def test_ragged_batch_decode_matches_per_session(llama_env):
    """One ragged decode call over sessions of different lengths returns the
    same next token each would get decoding alone."""
    cfg, params, be = llama_env
    alloc = PageAllocator(be.num_pages, be.page_size)
    sessions = []
    for i, prompt in enumerate([[3, 1, 4, 1, 5], [9, 2], [6, 5, 3, 5, 8, 9, 7]]):
        pages = alloc.alloc(f"r{i}", alloc.pages_for(len(prompt) + 4))
        first = be.prefill(prompt, pages)
        sessions.append([first, len(prompt), pages, prompt, [first]])
    for _ in range(3):
        batch = be.decode([(s[0], s[1], s[2]) for s in sessions])
        for s, tok in zip(sessions, batch):
            s[0], s[1] = int(tok), s[1] + 1
            s[4].append(int(tok))
    for s in sessions:
        assert s[4] == ref_greedy(cfg, params, s[3], 4)


def test_page_reuse_never_leaks_across_sessions(llama_env):
    """Freed pages return to the pool dirty; a later owner's decode must be
    bit-identical to a fresh-cache run (stale K/V is unreachable through the
    causal mask + its own page table)."""
    cfg, params, be = llama_env
    alloc = PageAllocator(be.num_pages, be.page_size)
    # session A dirties a large footprint, then retires
    a_out = paged_greedy(be, alloc, "A", [11, 22, 33, 44, 55, 66], 8)
    assert alloc.free("A") > 0
    # session B reuses A's pages (FIFO free list hands them straight back)
    b_out = paged_greedy(be, alloc, "B", [200, 100, 50], 8)
    assert b_out == ref_greedy(cfg, params, [200, 100, 50], 8)
    assert b_out != a_out  # sanity: different conversations
    # and A again, over B's leavings, still exact
    alloc.free("B")
    assert paged_greedy(be, alloc, "A2", [11, 22, 33, 44, 55, 66], 8) == a_out


def test_ragged_mixed_prefill_decode_matches_sequential_property(llama_env):
    """Property (ISSUE 11): a randomized schedule of mixed prefill chunks +
    decode steps through the single ragged entry point is logit-identical
    (fp32 argmax) to per-session sequential prefill + padded decode —
    across varying prompt lengths (incl. multi-page), random chunk splits,
    and sessions joining and leaving mid-stream."""
    from cordum_tpu.serving.backend import StepEntry

    cfg, params, be = llama_env
    rng = random.Random(11)
    alloc = PageAllocator(be.num_pages, be.page_size)
    specs = []
    for i in range(6):
        plen = rng.randint(1, 2 * be.page_size + 3)  # spans 1-3 pages
        specs.append({
            "key": f"p{i}",
            "prompt": [rng.randrange(cfg.vocab_size) for _ in range(plen)],
            "n_new": rng.randint(1, 5),
        })
    waiting = list(specs)
    live: list[dict] = []
    out: dict[str, list[int]] = {s["key"]: [] for s in specs}
    guard = 0
    while waiting or live:
        guard += 1
        assert guard < 500, "schedule failed to converge"
        for _ in range(rng.randint(0, 2)):  # joins mid-stream
            if not waiting:
                break
            s = dict(waiting.pop(0), fed=0, pos=0, last=None)
            total = len(s["prompt"]) + s["n_new"]
            s["pages"] = alloc.alloc(s["key"], alloc.pages_for(total))
            live.append(s)
        if not live:
            continue
        entries, rows = [], []
        budget = be.max_batch_tokens
        for s in live:
            if budget <= 0:
                break
            if s["fed"] < len(s["prompt"]):  # prefill chunk, random split
                chunk = min(budget, rng.randint(1, len(s["prompt"]) - s["fed"]))
                completes = s["fed"] + chunk == len(s["prompt"])
                entries.append(StepEntry(
                    tokens=s["prompt"][s["fed"]:s["fed"] + chunk],
                    start=s["fed"], pages=s["pages"], sample=completes,
                    phase="prefill", key=s["key"]))
                s["fed"] += chunk
                budget -= chunk
            else:  # decode row
                entries.append(StepEntry(
                    tokens=[s["last"]], start=s["pos"], pages=s["pages"],
                    sample=True, phase="decode", key=s["key"]))
                budget -= 1
            rows.append(s)
        for s, tok in zip(rows, be.step(entries)):
            if tok is None:
                continue  # mid-prompt chunk
            if s["last"] is None:  # prefill completion: the first token
                s["pos"] = len(s["prompt"])
            else:
                s["pos"] += 1
            s["last"] = int(tok)
            out[s["key"]].append(int(tok))
        for s in [s for s in live if len(out[s["key"]]) >= s["n_new"]]:
            live.remove(s)  # leaves mid-stream free pages for reuse
            alloc.free(s["key"])
    for s in specs:
        assert out[s["key"]] == ref_greedy(cfg, params, s["prompt"],
                                           s["n_new"]), s["key"]


def test_ragged_single_program_no_recompile_cliff(llama_env):
    """Any mix of prompt lengths, batch widths and join/leave patterns
    compiles exactly ONE XLA program — the bucket-recompile cliff is gone,
    and ``cordum_serving_compile_total`` is the gated proof."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.serving.backend import LlamaServingBackend

    cfg, params, _ = llama_env
    metrics = Metrics()
    be = LlamaServingBackend(cfg, num_pages=64, page_size=8,
                             params_provider=lambda: params, metrics=metrics)
    alloc = PageAllocator(be.num_pages, be.page_size)
    # the old backend compiled one program per prompt-length bucket plus
    # one per pow2 decode-batch bucket; this mix would have cost >= 6
    sessions = []
    for i, plen in enumerate((1, 3, 9, 17)):
        prompt = [(7 * i + j) % cfg.vocab_size for j in range(plen)]
        pages = alloc.alloc(f"c{i}", alloc.pages_for(plen + 4))
        first = be.prefill(prompt, pages)
        sessions.append((first, plen, pages))
    for width in (1, 2, 4, 3):  # ragged join/leave widths, incl. non-pow2
        be.decode([(t, p, pg) for t, p, pg in sessions[:width]])
    assert be.compiled_programs() == 1
    assert metrics.serving_compiles.value(entry="ragged", walk_kernel="none", expert_kernel="none") == 1
    assert be.last_step_compiled is False  # steady state by now


# -------------------------------------------- engine (fake backend, fast)


async def test_engine_join_leave_matches_sequential():
    """Sessions joining and retiring mid-flight get exactly the tokens a
    sequential per-session decode would produce — continuous batching is a
    scheduling change, not a math change."""
    # the small decode delay keeps sessions in flight long enough that the
    # staggered joiners actually share steps with the early ones
    be = FakeBackend(num_pages=32, step_delay=0.005)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=8,
                        max_new_tokens_cap=64, max_concurrent_prefills=2)

    async def one(job_id, prompt, n_new, delay):
        await asyncio.sleep(delay)
        return await eng.submit(
            GenRequest(prompt=prompt, max_new_tokens=n_new, stream=False),
            job_id=job_id,
        )

    specs = [("a", [1, 2, 3], 12, 0.0), ("b", [4, 5], 4, 0.01),
             ("c", [9, 9, 9, 9], 8, 0.02), ("d", [7], 3, 0.05)]
    outs = await asyncio.wait_for(
        asyncio.gather(*(one(j, p, n, d) for j, p, n, d in specs)), timeout=20
    )
    for (job_id, prompt, n_new, _), out in zip(specs, outs):
        assert out["tokens"] == fake_ref(prompt, n_new), job_id
        assert out["finish_reason"] == "length"
    assert max(be.decode_batches) >= 2, "sessions never actually shared a step"
    # all freed: what is still held is the prefix cache's alone
    assert eng.allocator.used_pages == eng.prefix.warm_pages
    assert eng.stats.retired == 4 and eng.stats.failed == 0
    await eng.stop()


async def test_engine_chunked_prefill_rides_decode_steps():
    """A prompt longer than the flat-buffer budget prefills in chunks
    across several mixed steps while another session keeps decoding — both
    finish with exactly their sequential tokens (chunked prefill is a
    scheduling change, not a math change)."""
    be = FakeBackend(num_pages=64, page_size=4, max_context=128,
                     max_batch_tokens=8, step_delay=0.002)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=4,
                        max_new_tokens_cap=64)
    long_prompt = list(range(1, 31))  # 30 tokens >> the 8-token budget

    async def one(job_id, prompt, n_new, delay):
        await asyncio.sleep(delay)
        return await eng.submit(
            GenRequest(prompt=prompt, max_new_tokens=n_new, stream=False),
            job_id=job_id,
        )

    outs = await asyncio.wait_for(asyncio.gather(
        one("fast", [2, 3], 20, 0.0),
        one("slow", long_prompt, 4, 0.01),
    ), timeout=20)
    assert outs[0]["tokens"] == fake_ref([2, 3], 20)
    assert outs[1]["tokens"] == fake_ref(long_prompt, 4)
    # the long prompt really was chunked: sharing the 8-slot buffer with a
    # decode row leaves <= 7 tokens per chunk, so 30 tokens need >= 5
    assert be.prefill_chunks >= 5
    assert eng.stats.prefill_tokens == 30 + 2
    assert max(be.decode_batches) >= 2, "prefill never rode a decode step"
    await eng.stop()


async def test_engine_admission_queue_on_exhaustion():
    """A cache sized for one session at a time admits FIFO as pages free —
    exhaustion delays admission, it never fails an accepted session."""
    be = FakeBackend(num_pages=5, page_size=4)  # 4 usable pages
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=8,
                        max_new_tokens_cap=64)
    # each session needs 3 pages (prompt 4 + 6 new = 10 tokens) → one at a time
    outs = await asyncio.wait_for(
        asyncio.gather(*(
            eng.submit(GenRequest(prompt=[i, i, i, i], max_new_tokens=6,
                                  stream=False), job_id=f"x{i}")
            for i in range(3)
        )),
        timeout=20,
    )
    for i, out in enumerate(outs):
        assert out["tokens"] == fake_ref([i, i, i, i], 6)
    assert eng.stats.admission_waits > 0  # the queue actually formed
    assert max(be.decode_batches) == 1  # pages, not slots, were the limit
    # an accepted-but-impossible footprint is rejected upfront, not queued
    # (20 tokens fit the page-table width but need 5 of the 4 usable pages)
    with pytest.raises(ValueError, match="KV pages"):
        await eng.submit(GenRequest(prompt=[1] * 12, max_new_tokens=8),
                         job_id="huge")
    await eng.stop()


async def test_engine_eos_stops_early():
    be = FakeBackend()
    eng = ServingEngine(be, run_blocking=run_blocking)
    seq = fake_ref([2, 3], 16)
    eos = seq[2]  # third generated token
    out = await asyncio.wait_for(
        eng.submit(GenRequest(prompt=[2, 3], max_new_tokens=16, eos_token=eos,
                              stream=False), job_id="e"),
        timeout=10,
    )
    assert out["tokens"] == seq[:3] and out["finish_reason"] == "eos"
    await eng.stop()


async def test_engine_cancel_pending_and_active_frees_pages():
    be = FakeBackend(num_pages=64, max_context=512, step_delay=0.02)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=4,
                        max_new_tokens_cap=600)
    live = asyncio.ensure_future(eng.submit(
        GenRequest(prompt=[1, 2], max_new_tokens=200, stream=False), job_id="live"))
    for _ in range(200):
        await asyncio.sleep(0.01)
        if eng.active_sessions() == 1:
            break
    assert eng.active_sessions() == 1
    pages_held = eng.allocator.used_pages
    assert pages_held > 0
    # cancel a job that is only queued… (park it by filling max_sessions)
    assert eng.cancel("live") is True
    with pytest.raises(SessionCancelled):
        await asyncio.wait_for(live, timeout=10)
    for _ in range(100):  # the loop frees pages on its next tick
        await asyncio.sleep(0.01)
        if eng.allocator.used_pages == 0:
            break
    assert eng.allocator.used_pages == 0
    assert eng.cancel("live") is False  # already gone
    assert eng.cancel("never-existed") is False
    await eng.stop()


async def test_engine_rejects_over_context_request_without_killing_batch():
    """A request longer than the backend's static page-table width fails
    alone at submit — it must never become a session, where its first decode
    step would raise and retire every in-flight conversation on the worker."""
    be = FakeBackend(num_pages=256, page_size=4, max_context=32,
                     step_delay=0.005)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=8,
                        max_new_tokens_cap=600)
    live = asyncio.ensure_future(eng.submit(
        GenRequest(prompt=[1, 2, 3], max_new_tokens=12, stream=False),
        job_id="live"))
    for _ in range(200):
        await asyncio.sleep(0.005)
        if eng.active_sessions() == 1:
            break
    assert eng.active_sessions() == 1
    # the arena has room (10 of 255 pages) — only the table width bars it
    assert eng.allocator.pages_for(40) <= eng.allocator.free_pages
    with pytest.raises(ValueError, match="max_context"):
        await eng.submit(GenRequest(prompt=[9] * 20, max_new_tokens=20,
                                    stream=False), job_id="huge")
    # the in-flight session is untouched by the rejection
    out = await asyncio.wait_for(live, timeout=10)
    assert out["tokens"] == fake_ref([1, 2, 3], 12)
    assert eng.stats.failed == 0
    await eng.stop()


async def test_engine_cancel_pending_counts_in_retirement_metric():
    """Cancelling a still-queued session moves the retirement metric the
    same way the prefilling/decoding cancel paths do (both ride _retire)."""
    from cordum_tpu.infra.metrics import Metrics

    metrics = Metrics()
    be = FakeBackend(num_pages=64, max_context=512, step_delay=0.02)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=1,
                        max_new_tokens_cap=600, metrics=metrics)
    live = asyncio.ensure_future(eng.submit(
        GenRequest(prompt=[1], max_new_tokens=100, stream=False),
        job_id="live"))
    for _ in range(200):
        await asyncio.sleep(0.01)
        if eng.active_sessions() == 1:
            break
    assert eng.active_sessions() == 1
    queued = asyncio.ensure_future(eng.submit(
        GenRequest(prompt=[2], max_new_tokens=4, stream=False),
        job_id="queued"))
    for _ in range(100):
        await asyncio.sleep(0.005)
        if eng.queue_depth() == 1:
            break
    assert eng.queue_depth() == 1  # parked behind max_sessions=1
    assert eng.cancel("queued") is True
    with pytest.raises(SessionCancelled):
        await asyncio.wait_for(queued, timeout=5)
    assert eng.stats.cancelled == 1
    assert metrics.serving_retired.value(reason="cancelled") == 1
    assert eng.cancel("live") is True
    with pytest.raises(SessionCancelled):
        await asyncio.wait_for(live, timeout=10)
    assert metrics.serving_retired.value(reason="cancelled") == 2
    await eng.stop()


async def test_parts_tolerates_malformed_max_new_tokens():
    """A non-numeric max_new_tokens is not a session: parts() returns None
    so the job falls through to the handler path's descriptive failure."""
    eng = ServingEngine(FakeBackend(), run_blocking=run_blocking)
    good = {"op": "llm.generate", "tokens": [1, 2]}
    assert eng.parts(good) is not None
    for bad in ("abc", [16], {"n": 16}, "12.5"):
        assert eng.parts({**good, "max_new_tokens": bad}) is None, bad
    await eng.stop()


async def test_engine_stop_evicts_everything():
    be = FakeBackend(num_pages=64, max_context=512, step_delay=0.02)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=2,
                        max_new_tokens_cap=600)
    futs = [asyncio.ensure_future(eng.submit(
        GenRequest(prompt=[i], max_new_tokens=100, stream=False), job_id=f"s{i}"))
        for i in range(4)]  # 2 admitted, 2 pending
    await asyncio.sleep(0.1)
    await eng.stop()
    for f in futs:
        with pytest.raises((SessionCancelled, asyncio.CancelledError)):
            await asyncio.wait_for(f, timeout=5)
    assert eng.allocator.used_pages == 0
    with pytest.raises(RuntimeError):
        await eng.submit(GenRequest(prompt=[1]), job_id="late")


# ------------------------------------------- the order of a cycle
# (docs/SERVING.md §The continuous-batching step loop: bookkeeping, assemble,
# hand-over, publish, resolve, await)


class GatedBackend(FakeBackend):
    """A fake whose every step, once fed, waits in ``backend.step`` for the
    test's leave (``go``); ``free()`` lets all later steps through."""

    def __init__(self, **kw):
        import threading

        super().__init__(**kw)
        self.go = threading.Semaphore(0)
        self.gated = True

    def free(self):
        self.gated = False
        self.go.release()

    def device(self, n_step):
        if self.gated:
            assert self.go.acquire(timeout=20), "the test never let the step go"
            if not self.gated:
                self.go.release()  # pass the leave on to the next step
        super().device(n_step)


class CycleLog:
    """The order of hand-overs, returns, stream packets (as their sink
    returns, after an optional sleep) and resolved submits of one engine."""

    def __init__(self, sink_sleep=0.0):
        self.events = []
        self.packets = {}  # job -> [(tokens, n_generated, done)]
        self.sink_sleep = sink_sleep
        self.hold = {}  # job -> (packet ordinal, asyncio.Event) the sink waits for

    async def run_blocking(self, fn, *args):
        self.events.append("handed")
        try:
            return await asyncio.get_running_loop().run_in_executor(None, fn, *args)
        finally:
            self.events.append("returned")

    def sink(self, job):
        async def on_tokens(tokens, n_generated, done):
            mine = self.packets.setdefault(job, [])
            held = self.hold.get(job)
            if held is not None and held[0] == len(mine):
                await held[1].wait()
            elif self.sink_sleep:
                await asyncio.sleep(self.sink_sleep)
            mine.append((list(tokens), n_generated, done))
            self.events.append(("packet", job, n_generated))
        return on_tokens

    async def submit(self, eng, job, prompt, n_new):
        try:
            out = await eng.submit(GenRequest(prompt=prompt, max_new_tokens=n_new),
                                   job_id=job, on_tokens=self.sink(job))
        except Exception as e:  # noqa: BLE001 - the order of the error is the subject
            self.events.append(("error", job, type(e).__name__))
            raise
        self.events.append(("resolved", job, len(out["tokens"])))
        return out

    def streamed(self, job):
        """The job's stream, assembled: offsets contiguous and in order."""
        out = []
        for tokens, n_generated, _ in self.packets.get(job, []):
            assert n_generated - len(tokens) == len(out), (job, self.packets[job])
            out.extend(tokens)
        return out


async def until(cond, timeout=10.0):
    t0 = asyncio.get_running_loop().time()
    while not cond():
        assert asyncio.get_running_loop().time() - t0 < timeout, "never happened"
        await asyncio.sleep(0.002)


async def test_a_steps_packets_go_out_while_the_next_step_is_in_the_backend():
    """Step N's packet is published after step N+1's hand-over and before
    its return, and counted as behind a step; the last step's, with no
    successor, before the loop parks, and not counted."""
    from cordum_tpu.infra.metrics import Metrics

    log, be, metrics = CycleLog(), GatedBackend(num_pages=64), Metrics()
    eng = ServingEngine(be, run_blocking=log.run_blocking, max_sessions=4, metrics=metrics)
    job = asyncio.ensure_future(log.submit(eng, "a", [1, 2, 3], 3))
    await until(lambda: log.events == ["handed"])
    be.go.release()  # step 0 samples the first token; step 1 then waits in the backend
    await until(lambda: ("packet", "a", 1) in log.events)
    assert log.events == ["handed", "returned", "handed", ("packet", "a", 1)]
    assert (eng.stats.stream_packets, eng.stats.stream_packets_behind_step) == (1, 1)
    be.free()
    out = await asyncio.wait_for(job, timeout=10)
    assert out["tokens"] == log.streamed("a") == fake_ref([1, 2, 3], 3)
    assert log.events[4:] == ["returned", "handed", ("packet", "a", 2), "returned",
                              ("packet", "a", 3), ("resolved", "a", 3)]
    assert (eng.stats.stream_packets, eng.stats.stream_packets_behind_step) == (3, 2)
    # a session of one step has no step to hide behind
    log.events.clear()
    await asyncio.wait_for(log.submit(eng, "b", [4, 5], 1), timeout=10)
    assert log.events == ["handed", "returned", ("packet", "b", 1), ("resolved", "b", 1)]
    assert (eng.stats.stream_packets, eng.stats.stream_packets_behind_step) == (4, 2)
    assert metrics.serving_stream_packets.value(behind_step="true") == 2
    assert metrics.serving_stream_packets.value(behind_step="false") == 2
    await eng.stop()


@pytest.mark.parametrize("kind", ["says", "mute"])
async def test_the_publish_waits_until_the_step_is_fed_or_has_returned(kind):
    """The executor thread feeds the device in Python, so the loop holds the
    last step's packets back until the backend says the step is fed
    (``StepBackend.on_dispatched``); behind a backend that never says so,
    until the step has returned."""
    import time

    log = CycleLog()

    class Feeding(FakeBackend):
        def step(self, entries):
            hook, self.on_dispatched = self.on_dispatched, None
            try:
                log.events.append("feeding")
                time.sleep(0.01)
                if kind == "says":
                    log.events.append("fed")
                    hook()
                out = super().step(entries)
                log.events.append("result")
                return out
            finally:
                self.on_dispatched = hook

    eng = ServingEngine(Feeding(num_pages=64, step_delay=0.02),
                        run_blocking=log.run_blocking, max_sessions=4)
    out = await asyncio.wait_for(log.submit(eng, "a", [1, 2, 3], 3), timeout=10)
    assert out["tokens"] == log.streamed("a") == fake_ref([1, 2, 3], 3)
    cycle = ["handed", "feeding", "fed", ("packet", "a", 1), "result", "returned"]
    if kind == "mute":
        cycle = ["handed", "feeding", "result", ("packet", "a", 1), "returned"]
    assert log.events[log.events.index("returned") + 1:][:len(cycle)] == cycle
    await eng.stop()


@pytest.mark.parametrize("kind", ["one-step", "many-steps", "bursts"])
async def test_a_future_never_resolves_before_its_last_packets_sink_returned(kind):
    """With a sink that sleeps: when ``submit`` returns, the packet that
    carried the last token has left its sink; offsets are contiguous, in
    order and exactly-once per job."""
    from .test_speculative import cut2_drafter

    log = CycleLog(sink_sleep=0.004)
    n_new = 1 if kind == "one-step" else 14
    eng = ServingEngine(
        FakeBackend(num_pages=64, step_delay=0.001), run_blocking=log.run_blocking,
        max_sessions=4, speculative=kind == "bursts", drafter=cut2_drafter)
    prompts = {f"j{i}": [i + 1, 7, i + 2] for i in range(3)}
    outs = await asyncio.wait_for(asyncio.gather(*[
        log.submit(eng, job, prompt, n_new) for job, prompt in prompts.items()]), timeout=20)
    for (job, prompt), out in zip(prompts.items(), outs):
        assert out["tokens"] == log.streamed(job) == fake_ref(prompt, n_new)
        assert log.packets[job][-1][1:] == (n_new, True)
        assert [done for _, _, done in log.packets[job][:-1]] == [False] * (len(log.packets[job]) - 1)
        last = log.events.index(("packet", job, n_new))
        assert last < log.events.index(("resolved", job, n_new))
    if kind == "bursts":
        assert eng.stats.accepted_tokens > 0
        assert any(len(t) > 1 for j in prompts for t, _, _ in log.packets[j])
    assert eng.stats.stream_packets == sum(len(p) for p in log.packets.values())
    await eng.stop()


async def test_wait_quiesced_waits_for_the_frozen_sessions_pending_packet():
    """A session frozen while its step is in the backend gets that step's
    token; ``wait_quiesced`` returns only once the packet is out (behind a
    step the session does not ride), and ``export_state`` then equals what
    was streamed."""
    log, be = CycleLog(), GatedBackend(num_pages=64)
    eng = ServingEngine(be, run_blocking=log.run_blocking, max_sessions=4)
    jobs = [asyncio.ensure_future(log.submit(eng, j, p, 40))
            for j, p in (("a", [1, 2, 3]), ("b", [9, 8]))]
    for k in range(3):
        await until(lambda: log.events.count("handed") == k + 1)
        be.go.release()
        await until(lambda: log.events.count("returned") == k + 1)
    # the fourth step is handed over, and the third's packets are out: they go behind the
    # hand-over only once the executor thread has said the step was fed, which a busy machine
    # delays past the "handed" mark (the packet counts below would then be one short, and
    # the hold would catch the third step's packet with the fourth never awaited)
    await until(lambda: log.events.count("handed") == 4
                and not eng._untold("a") and not eng._untold("b"))
    # a step with both rows is in the backend: freeze a, hold its next packet
    n_before, b_before = len(log.packets["a"]), len(log.packets["b"])
    gate = asyncio.Event()
    log.hold["a"] = (n_before, gate)
    assert eng.freeze_session("a")
    quiesced = asyncio.ensure_future(eng.wait_quiesced("a"))
    be.free()
    # the next step is handed over with b alone, and b's packet goes out behind it
    await until(lambda: len(log.packets["b"]) == b_before + 1 and len(be.seen) == 5)
    assert log.events.count("handed") == 5 and [k for _, _, k, _ in be.seen[4]] == ["decode"]
    await asyncio.sleep(0.02)
    assert not quiesced.done() and len(log.packets["a"]) == n_before
    assert len(eng.export_state("a")["out_tokens"]) == n_before + 1  # booked, untold
    gate.set()
    await asyncio.wait_for(quiesced, timeout=10)
    assert eng.export_state("a")["out_tokens"] == log.streamed("a")
    assert len(log.packets["a"]) == n_before + 1
    eng.unfreeze_session("a")
    for job, prompt, out in zip(("a", "b"), ([1, 2, 3], [9, 8]),
                                await asyncio.wait_for(asyncio.gather(*jobs), timeout=20)):
        assert out["tokens"] == log.streamed(job) == fake_ref(prompt, 40)
    await eng.stop()


async def test_a_failing_step_delivers_the_step_before_it_first():
    """Step 2 raises: the tokens of steps 0 and 1 reach the sink before the
    riders get the error."""
    log = CycleLog(sink_sleep=0.003)
    eng = ServingEngine(FakeBackend(num_pages=64, fail_at={2}),
                        run_blocking=log.run_blocking, max_sessions=4)
    with pytest.raises(RuntimeError):
        await asyncio.wait_for(log.submit(eng, "a", [1, 2, 3], 8), timeout=10)
    assert log.streamed("a") == fake_ref([1, 2, 3], 2)
    assert [e for e in log.events if isinstance(e, tuple)] == [
        ("packet", "a", 1), ("packet", "a", 2), ("error", "a", "RuntimeError")]
    await eng.stop()


async def test_stop_delivers_pending_packets_before_the_cancellations():
    """``stop()`` while a step's packet is on its way through a slow sink:
    the packet leaves the sink, then the session is cancelled."""
    log, be = CycleLog(), FakeBackend(num_pages=64, step_delay=0.002)
    eng = ServingEngine(be, run_blocking=log.run_blocking, max_sessions=4)
    gate = asyncio.Event()
    log.hold["a"] = (2, gate)
    job = asyncio.ensure_future(log.submit(eng, "a", [1, 2, 3], 30))
    await until(lambda: len(log.packets.get("a", [])) == 2 and eng._active["a"].unsent)
    stopping = asyncio.ensure_future(eng.stop())
    await asyncio.sleep(0.02)
    assert not stopping.done() and not job.done()
    gate.set()
    await asyncio.wait_for(stopping, timeout=10)
    with pytest.raises(SessionCancelled):
        await asyncio.wait_for(job, timeout=5)
    told = [e for e in log.events if isinstance(e, tuple)]
    assert told[-2:] == [("packet", "a", 3), ("error", "a", "SessionCancelled")]
    assert log.streamed("a") == fake_ref([1, 2, 3], 30)[:3]
    assert eng.allocator.used_pages == 0


async def test_a_finishers_pages_are_admitted_into_by_the_very_next_assemble():
    """The pool holds one session: the second is admitted by the assemble
    right after the first's last step, whose packet is told only behind the
    second's first step."""
    log, be = CycleLog(sink_sleep=0.01), FakeBackend(num_pages=3, page_size=4)
    eng = ServingEngine(be, run_blocking=log.run_blocking, max_sessions=4,
                        prefix_cache=False)
    outs = await asyncio.wait_for(asyncio.gather(
        log.submit(eng, "a", [1, 2, 3], 4), log.submit(eng, "b", [5, 6, 7], 4)), timeout=20)
    assert [o["tokens"] for o in outs] == [fake_ref([1, 2, 3], 4), fake_ref([5, 6, 7], 4)]
    assert eng.stats.admission_waits >= 1
    # a: prefill + 3 decode rows = steps 0-3; b's prefill is step 4
    assert [[(phase, start) for _, start, phase, _ in step] for step in be.seen[3:5]] == [
        [("decode", 5)], [("prefill", 0)]]
    handed = [i for i, e in enumerate(log.events) if e == "handed"]
    assert handed[4] < log.events.index(("packet", "a", 4)) < log.events.index(("resolved", "a", 4))
    await eng.stop()


# ------------------------------------------------------- session affinity


def _affinity_fixture(native=False):
    from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
    from cordum_tpu.infra.config import parse_pool_config
    from cordum_tpu.infra.registry import WorkerRegistry

    reg = WorkerRegistry()
    pc = parse_pool_config({"topics": {"job.tpu.generate": "tpu"},
                            "pools": {"tpu": {"requires": []}}})
    return reg, LeastLoadedStrategy(reg, pc, native=native)


def test_strategy_session_affinity_sticks_and_migrates():
    from cordum_tpu.protocol.types import Heartbeat, JobRequest, LABEL_SESSION_KEY

    reg, strat = _affinity_fixture()
    for wid, active in (("w-a", 0), ("w-b", 1)):
        reg.update(Heartbeat(worker_id=wid, pool="tpu", active_jobs=active,
                             max_parallel_jobs=16))
    req = JobRequest(job_id="t1", topic="job.tpu.generate",
                     labels={LABEL_SESSION_KEY: "conv-1"})
    assert strat.pick_subject(req) == "worker.w-a.jobs"
    assert strat.session_affinity_new == 1
    # sticky across turns even when the holder grows busier (its KV pages
    # are there; re-routing would orphan them)
    reg.update(Heartbeat(worker_id="w-a", pool="tpu", active_jobs=9,
                         max_parallel_jobs=16))
    for _ in range(5):
        assert strat.pick_subject(req) == "worker.w-a.jobs"
    assert strat.session_affinity_hits == 5
    # sessionless jobs still load-balance
    assert strat.pick_subject(
        JobRequest(job_id="t2", topic="job.tpu.generate")) == "worker.w-b.jobs"
    # overload evicts: the session migrates (counted as a miss, not new)
    reg.update(Heartbeat(worker_id="w-a", pool="tpu", active_jobs=16,
                         max_parallel_jobs=16))
    assert strat.pick_subject(req) == "worker.w-b.jobs"
    assert strat.session_affinity_misses == 1


def test_strategy_session_ttl_outlives_batch_ttl():
    """The session TTL is sized to conversation think-time: an entry too old
    for batch affinity still sticks, and only SESSION_AFFINITY_TTL_S drops
    it (a drop then counts as a migration)."""
    from cordum_tpu.controlplane.scheduler.strategy import (
        _SESSION_PREFIX, BATCH_AFFINITY_TTL_S, SESSION_AFFINITY_TTL_S,
    )
    from cordum_tpu.protocol.types import Heartbeat, JobRequest, LABEL_SESSION_KEY

    assert SESSION_AFFINITY_TTL_S > BATCH_AFFINITY_TTL_S
    reg, strat = _affinity_fixture()
    reg.update(Heartbeat(worker_id="w-a", pool="tpu", max_parallel_jobs=16))
    req = JobRequest(job_id="t", topic="job.tpu.generate",
                     labels={LABEL_SESSION_KEY: "conv-9"})
    strat.pick_subject(req)
    akey = _SESSION_PREFIX + "conv-9"
    wid, stamped = strat._affinity[akey]
    # older than the batch TTL → still a hit
    strat._affinity[akey] = (wid, stamped - BATCH_AFFINITY_TTL_S - 1)
    strat.pick_subject(req)
    assert strat.session_affinity_hits == 1
    # older than the session TTL → dropped, rerouted as a miss
    strat._affinity[akey] = (wid, stamped - SESSION_AFFINITY_TTL_S - 1)
    strat.pick_subject(req)
    assert strat.session_affinity_misses == 1


def test_session_keys_never_collide_with_batch_keys():
    """A session id equal to a batch key routes through its own namespaced
    affinity entry (an adversarial session_id cannot hijack batch routing)."""
    from cordum_tpu.controlplane.scheduler.strategy import _SESSION_PREFIX
    from cordum_tpu.protocol.types import (
        Heartbeat, JobRequest, LABEL_BATCH_KEY, LABEL_SESSION_KEY,
    )

    reg, strat = _affinity_fixture()
    reg.update(Heartbeat(worker_id="w-a", pool="tpu", max_parallel_jobs=16))
    strat.pick_subject(JobRequest(job_id="b", topic="job.tpu.generate",
                                  labels={LABEL_BATCH_KEY: "embed"}))
    strat.pick_subject(JobRequest(job_id="s", topic="job.tpu.generate",
                                  labels={LABEL_SESSION_KEY: "embed"}))
    assert "embed" in strat._affinity
    assert _SESSION_PREFIX + "embed" in strat._affinity


# ------------------------------------------------- worker e2e (real stack)


async def settle(bus, rounds=6):
    for _ in range(rounds):
        await bus.drain()
        await asyncio.sleep(0.02)


def make_stack():
    from tests.test_batching import make_stack as _ms

    return _ms()


def make_serving_worker(bus, ms, *, backend=None, metrics=None, **eng_kw):
    from cordum_tpu.worker.handlers import TPUCompute, make_tpu_handlers
    from cordum_tpu.worker.runtime import Worker

    w = Worker(bus=bus, store=ms, worker_id="w-srv", pool="tpu",
               topics=["job.tpu.>"], capabilities=["tpu"],
               heartbeat_interval_s=999)
    compute = TPUCompute(tp=1)
    w.register_default(make_tpu_handlers(compute))
    eng = ServingEngine(backend or FakeBackend(num_pages=64),
                        run_blocking=w.run_in_executor, metrics=metrics,
                        tracer=w.tracer, **eng_kw)
    w.attach_serving(eng)
    return w


async def test_worker_generate_e2e_stream_and_terminal_result():
    """llm.generate through the full pipeline: tokens stream as progress
    packets, the terminal result carries the whole list, the scheduler does
    NOT persist per-token events, serving metrics move, and KV pages are
    freed on retirement."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import (
        BusPacket, JobRequest, STATUS_HINT_STREAM,
    )

    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    metrics = Metrics()
    w = make_serving_worker(bus, ms, metrics=metrics, max_sessions=4)
    await w.start()
    await settle(bus)
    streams: dict[str, list[int]] = {}

    async def ptap(subject, pkt):
        pr = pkt.job_progress
        if pr is not None and pr.status_hint == STATUS_HINT_STREAM:
            streams.setdefault(pr.job_id, []).extend(pr.tokens)

    await bus.subscribe(subj.PROGRESS, ptap)
    n = 3
    for i in range(n):
        jid = f"g{i}"
        ptr = await ms.put_context(jid, {
            "op": "llm.generate", "tokens": [i + 1, 5, 9],
            "max_new_tokens": 6, "session_id": f"conv-{i}",
        })
        await bus.publish(subj.SUBMIT, BusPacket.wrap(
            JobRequest(job_id=jid, topic="job.tpu.generate", context_ptr=ptr)))
    for _ in range(300):
        await settle(bus, rounds=2)
        states = [await js.get_state(f"g{i}") for i in range(n)]
        if all(s == "SUCCEEDED" for s in states):
            break
    assert all(s == "SUCCEEDED" for s in states), states
    for i in range(n):
        res = await ms.get_result(f"g{i}")
        assert res["tokens"] == fake_ref([i + 1, 5, 9], 6)
        assert res["session_key"] == f"conv-{i}"
        # the stream and the terminal result agree token-for-token
        assert streams[f"g{i}"] == res["tokens"]
        # per-token stream packets are transport, never job-store events
        evts = await js.events(f"g{i}")
        assert not any(e.get("event") == "progress" for e in evts), evts
    assert w.serving.allocator.used_pages == w.serving.prefix.warm_pages
    assert metrics.serving_admitted.value() >= n
    assert metrics.serving_retired.value(reason="finished") >= n
    await w.stop()
    await eng.stop()


async def test_worker_cancel_inflight_generate_frees_pages():
    """sys.job.cancel of a decoding llm.generate session evicts it from the
    loop, frees its KV pages and publishes CANCELLED (the stateful mirror of
    the batcher's cancel-while-queued)."""
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, JobCancel, JobRequest

    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = make_serving_worker(bus, ms,
                            backend=FakeBackend(num_pages=64, max_context=512,
                                                step_delay=0.02),
                            max_sessions=4, max_new_tokens_cap=600)
    await w.start()
    await settle(bus)
    ptr = await ms.put_context("gc", {
        "op": "llm.generate", "tokens": [1, 2, 3], "max_new_tokens": 200,
        "session_id": "conv-c",
    })
    await bus.publish(subj.SUBMIT, BusPacket.wrap(
        JobRequest(job_id="gc", topic="job.tpu.generate", context_ptr=ptr)))
    for _ in range(300):
        await asyncio.sleep(0.02)
        if w.serving.active_sessions() == 1:
            break
    assert w.serving.active_sessions() == 1, "session never started decoding"
    assert w.serving.allocator.used_pages > 0
    await bus.publish(subj.CANCEL, BusPacket.wrap(JobCancel(job_id="gc", reason="test")))
    for _ in range(300):
        await asyncio.sleep(0.02)
        if await js.get_state("gc") == "CANCELLED":
            break
    assert await js.get_state("gc") == "CANCELLED"
    for _ in range(100):
        await asyncio.sleep(0.01)
        if w.serving.allocator.used_pages == 0:
            break
    assert w.serving.allocator.used_pages == 0
    assert w.serving.active_sessions() == 0
    await w.stop()
    await eng.stop()


async def test_worker_invalid_generate_payload_fails_pointedly():
    """A malformed llm.generate payload is not a session: it takes the
    per-job handler path and fails with the op's own error."""
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.protocol.types import BusPacket, JobRequest

    kv, bus, js, ms, eng = make_stack()
    await eng.start()
    w = make_serving_worker(bus, ms)
    await w.start()
    await settle(bus)
    bad = {
        "gbad": {"op": "llm.generate", "tokens": "oops"},
        "gbad2": {"op": "llm.generate", "tokens": [1, 2],
                  "max_new_tokens": "lots"},
    }
    for jid, payload in bad.items():
        ptr = await ms.put_context(jid, payload)
        await bus.publish(subj.SUBMIT, BusPacket.wrap(
            JobRequest(job_id=jid, topic="job.tpu.generate", context_ptr=ptr)))
    for _ in range(100):
        await settle(bus)
        states = [await js.get_state(j) for j in bad]
        if all(s == "FAILED" for s in states):
            break
    for jid in bad:
        meta = await js.get_meta(jid)
        assert meta["state"] == "FAILED" and "tokens" in meta["error_message"]
    assert w.serving.stats.admitted == 0
    await w.stop()
    await eng.stop()


# --------------------------------------------------- gateway + sdk


async def test_gateway_stamps_session_key():
    from cordum_tpu.protocol.types import LABEL_SESSION_KEY
    from tests.test_gateway import GwStack

    async with GwStack() as s:
        r = await s.client.post("/api/v1/jobs", json={
            "topic": "job.work",
            "payload": {"op": "llm.generate", "tokens": [1, 2],
                        "session_id": "conv-42"},
        }, headers=s.h())
        assert r.status == 202
        doc = await r.json()
        await s.settle()
        # labels live on the persisted JobRequest, not the meta hash
        req = await s.job_store.get_request(doc["job_id"])
        assert req is not None
        assert req.labels[LABEL_SESSION_KEY] == "conv-42"
        # non-serving payloads must not grow the label
        r = await s.client.post("/api/v1/jobs", json={
            "topic": "job.work", "payload": {"op": "echo", "session_id": "x"},
        }, headers=s.h())
        doc2 = await r.json()
        await s.settle()
        req2 = await s.job_store.get_request(doc2["job_id"])
        assert req2 is not None and LABEL_SESSION_KEY not in (req2.labels or {})


class ServingGwStack:
    """Gateway + scheduler + a serving worker on job.tpu.generate, behind a
    live HTTP server (the SDK streaming helper's home turf)."""

    def __init__(self):
        from aiohttp.test_utils import TestClient, TestServer  # noqa: F401

        from tests.test_gateway import GwStack

        self.inner = GwStack()

    async def __aenter__(self):
        from cordum_tpu.controlplane.scheduler.strategy import LeastLoadedStrategy
        from cordum_tpu.infra.config import parse_pool_config

        s = self.inner
        # widen the scheduler's routing to the serving topic
        pc = parse_pool_config({
            "topics": {"job.work": "p", "job.tpu.generate": "tpu"},
            "pools": {"p": {}, "tpu": {}},
        })
        s.scheduler.strategy = LeastLoadedStrategy(s.scheduler.registry, pc)
        await s.__aenter__()
        self.worker = make_serving_worker(s.bus, s.mem, max_sessions=4)
        await self.worker.start()
        await s.settle()
        return self

    async def __aexit__(self, *exc):
        await self.worker.stop()
        await self.inner.__aexit__(*exc)


async def test_sdk_generate_streams_tokens():
    from cordum_tpu.sdk.client import Client

    async with ServingGwStack() as st:
        s = st.inner
        c = Client(str(s.client.make_url("")), api_key="user-key")
        try:
            got = [t async for t in c.generate(
                [1, 2, 3], session_id="conv-sdk", max_new_tokens=6,
                timeout_s=30)]
            assert got == fake_ref([1, 2, 3], 6)
            # non-streaming fallback: same contract, one burst
            got2 = [t async for t in c.generate(
                [1, 2, 3], session_id="conv-sdk", max_new_tokens=6,
                stream=False, timeout_s=30)]
            assert got2 == got
        finally:
            await c.close()


# ------------------------------------------- the flight recorder's serving side
# (docs/OBSERVABILITY.md §Serving spans and metrics)

class SpanSink:
    """A worker ``Tracer`` on a loopback bus with one listener on
    ``sys.trace.span`` that keeps every span it hears."""

    def __init__(self, bus=None):
        from cordum_tpu.infra.bus import LoopbackBus
        from cordum_tpu.obs.tracer import Tracer

        self.bus = bus or LoopbackBus()
        self.tracer = Tracer("worker", self.bus)
        self.spans = []

    async def listen(self):
        from cordum_tpu.protocol import subjects as subj

        async def on_span(subject, pkt):
            self.spans.append(pkt.span)

        await self.bus.subscribe(subj.TRACE_SPAN, on_span)
        return self

    def named(self, *names):
        return [s for s in self.spans if s.name in names]

    def step_traces(self):
        traces = {}
        for s in self.spans:
            if s.name == "step" or s.name.startswith("step."):
                traces.setdefault(s.trace_id, []).append(s)
        return traces


def traced_engine(sink, backend, **kw):
    eng = ServingEngine(backend, run_blocking=run_blocking, tracer=sink.tracer,
                        **kw)
    eng.worker_id = "w-t"
    return eng


async def generate(eng, n, *, prompt_len=5, new=6, trace=True):
    return await asyncio.gather(*[
        eng.submit(
            GenRequest(prompt=[i + 1] * prompt_len, max_new_tokens=new,
                       stream=False),
            job_id=f"j{i}", trace_id=f"tr-{i}" if trace else "",
            parent_span_id=f"exec-{i}" if trace else "",
        ) for i in range(n)
    ])


async def test_serving_queue_and_prefill_spans_split_each_ttft():
    """Every locally born request gets exactly one ``serving.queue`` and one
    ``serving.prefill`` on its own trace under its ``execute`` span; the two
    are contiguous and sum to its ``ttft_seconds`` entry."""
    from cordum_tpu.infra.metrics import Metrics

    sink = await SpanSink().listen()
    metrics = Metrics()
    # two slots for six requests, prompts longer than the 8-token budget:
    # real queueing, several chunks
    be = FakeBackend(num_pages=64, step_delay=0.002, max_batch_tokens=8)
    eng = traced_engine(sink, be, max_sessions=2, metrics=metrics)
    n = 6
    await generate(eng, n, prompt_len=13)
    await eng.stop()
    await sink.bus.drain()
    ttfts = []
    for i in range(n):
        mine = [s for s in sink.spans if s.trace_id == f"tr-{i}"]
        assert sorted(s.name for s in mine) == ["serving.prefill", "serving.queue"]
        q, p = sorted(mine, key=lambda s: s.name, reverse=True)
        assert q.parent_span_id == p.parent_span_id == f"exec-{i}"
        assert q.service == p.service == "worker"
        assert q.end_us == p.start_us  # contiguous
        assert set(q.attrs) == {"pending_ahead", "admission_waits", "prefix_hit_tokens"}
        assert set(p.attrs) == {"prompt_tokens", "chunks", "steps"}
        assert p.attrs["prompt_tokens"] == "13"
        assert int(p.attrs["steps"]) >= int(p.attrs["chunks"]) >= 2
        ttfts.append((p.end_us - q.start_us) / 1e6)
    # later arrivals queued behind the first two
    assert max(int(s.attrs["pending_ahead"]) for s in sink.named("serving.queue")) >= 3
    assert len(eng.stats.ttft_seconds) == n
    for span_sum, ttft in zip(sorted(ttfts), sorted(eng.stats.ttft_seconds)):
        assert abs(span_sum - ttft) < 3e-6  # two microsecond roundings
    # the always-on series close at the same boundaries
    for hist in (metrics.serving_queue, metrics.serving_prefill):
        ((_, _, _, count),) = hist._snapshot()
        assert count == n


async def test_migrated_in_and_resumed_sessions_get_no_ttft_spans():
    """A session adopted from a peer, and one resumed after a failover, had
    their first token on another worker's clock: neither span, and no
    ``ttft_seconds`` entry."""
    sink = await SpanSink().listen()
    be = FakeBackend(num_pages=64, step_delay=0.002)
    eng = traced_engine(sink, be, max_sessions=4)
    prompt = [3, 1, 4]
    carried = fake_ref(prompt, 2)
    fut = await eng.install_session(
        GenRequest(prompt=prompt, max_new_tokens=5, stream=False),
        job_id="mig", trace_id="tr-mig", parent_span_id="exec-mig",
        state={"pos": len(prompt) + 1, "prefill_pos": len(prompt),
               "out_tokens": carried, "last_token": carried[-1]},
        records=[{"i": 0, "used": 4, "k": prompt + carried[:1], "v": [], "shape": [4]}],
    )
    resumed = eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=5, stream=False,
                   resume_tokens=carried),
        job_id="res", trace_id="tr-res", parent_span_id="exec-res",
    )
    local = eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=5, stream=False),
        job_id="loc", trace_id="tr-loc", parent_span_id="exec-loc",
    )
    out_res, out_loc = await asyncio.gather(resumed, local)
    assert await fut == out_res["tokens"] == out_loc["tokens"] == fake_ref(prompt, 5)
    await eng.stop()
    await sink.bus.drain()
    ttft_spans = sink.named("serving.queue", "serving.prefill")
    assert sorted((s.trace_id, s.name) for s in ttft_spans) == [
        ("tr-loc", "serving.prefill"), ("tr-loc", "serving.queue")]
    assert len(eng.stats.ttft_seconds) == 1


@pytest.mark.parametrize("kind", ["stamping", "plain", "streaming"])
async def test_sampled_cycle_children_are_contiguous_and_sum_to_step(kind, llama_env):
    """A kept cycle is a trace of its own: root ``step`` with six contiguous
    children that sum to it exactly.  The real backend stamps its four
    phases; a backend that stamps nothing reads as one ``wait``.  Sessions
    that stream (their packets told behind the next step, through a sink
    that sleeps) add no span and no phase."""
    from cordum_tpu.serving.backend import STEP_PHASES

    sink = await SpanSink().listen()
    if kind == "stamping":
        be = llama_env[2]
    else:
        be = FakeBackend(num_pages=64, step_delay=0.004)
    eng = traced_engine(sink, be, max_sessions=4)
    if kind == "streaming":
        log = CycleLog(sink_sleep=0.001)
        await asyncio.gather(*[log.submit(eng, f"j{i}", [i + 1] * 5, 8) for i in range(3)])
        assert eng.stats.stream_packets == 24
        assert eng.stats.stream_packets_behind_step >= 21  # all but the last step's
    else:
        await generate(eng, 3, new=8)
        assert eng.stats.stream_packets == 0
    await eng.stop()
    await sink.bus.drain()
    traces = sink.step_traces()
    assert traces and len(traces) < eng.stats.steps  # a sample, not every cycle
    for trace_id, spans in traces.items():
        assert trace_id.startswith("step-w-t-")
        assert [s.name for s in spans] == [f"step.{p}" for p in STEP_PHASES] + ["step"]
        *children, root = spans  # the root is published last
        assert all(s.service == "worker" for s in spans)
        assert not root.parent_span_id
        assert all(c.parent_span_id == root.span_id for c in children)
        assert children[0].start_us == root.start_us and children[-1].end_us == root.end_us
        for a, b in zip(children, children[1:]):
            assert a.end_us == b.start_us
        assert sum(c.duration_us for c in children) == root.duration_us
        assert {"occupancy", "live_tokens", "prefill_tokens", "retired",
                "compiled", "published_behind"} <= set(root.attrs)
        behind, told = map(int, root.attrs["published_behind"].split("/"))
        # what a cycle tells is the step before it, behind its own
        assert behind == told == (0 if kind != "streaming" or trace_id.endswith("-0") else 3)
        by = {c.name: c.duration_us for c in children}
        if kind != "stamping":
            assert by["step.pack"] == by["step.dispatch"] == by["step.unpack"] == 0
            # the fake's 4 ms sleep lies between the hand-over (stamped by
            # the loop, late under load) and the return
            assert by["step.assemble"] + by["step.wait"] >= 4000
    if kind == "stamping":
        # the first cycle is always kept, and it is the one that compiled
        first = traces["step-w-t-0"][-1]
        assert first.attrs["compiled"] in ("true", "false")
        assert be.last_phases == tuple(sorted(be.last_phases)) and len(be.last_phases) == 5


async def test_stalled_cycle_is_kept_whatever_the_rate_cap_says():
    """A cycle over three times the running median of the last 64 is kept
    though the last kept cycle began under 250 ms before it."""
    from cordum_tpu.serving import engine as engine_mod

    sink = await SpanSink().listen()
    be = FakeBackend(slow_at={70: 0.06, 73: 0.06}, num_pages=64, max_context=256)
    eng = traced_engine(sink, be, max_sessions=2, max_new_tokens_cap=128)
    await generate(eng, 1, new=90)
    await eng.stop()
    await sink.bus.drain()
    roots = {t: spans[-1] for t, spans in sink.step_traces().items()}
    assert "step-w-t-0" in roots
    a, b = roots["step-w-t-70"], roots["step-w-t-73"]
    assert b.start_us - a.start_us < engine_mod.STEP_SAMPLE_PERIOD_NS / 1e3
    for root in (a, b):
        assert root.duration_us >= 60_000
        wait = next(s for s in sink.spans
                    if s.trace_id == root.trace_id and s.name == "step.wait")
        assert wait.duration_us >= 60_000  # the phase it stalled in
    assert len(roots) < eng.stats.steps / 2


async def test_no_span_is_published_between_a_step_returning_and_the_next_hand_over():
    """Finished spans wait until the next step is on the device: the order of
    hand-overs, returns and span publishes on a recording bus."""
    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.protocol import subjects as subj

    events = []

    class RecordingBus(LoopbackBus):
        async def publish(self, subject, pkt):
            if subject == subj.TRACE_SPAN:
                events.append("span")
            await super().publish(subject, pkt)

    async def recording_run_blocking(fn, *args):
        events.append("handed")
        try:
            return await asyncio.get_running_loop().run_in_executor(None, fn, *args)
        finally:
            events.append("returned")

    sink = await SpanSink(RecordingBus()).listen()
    be = FakeBackend(num_pages=64, step_delay=0.01, max_context=256)
    eng = ServingEngine(be, run_blocking=recording_run_blocking,
                        tracer=sink.tracer, max_sessions=4,
                        max_new_tokens_cap=64)
    # all submitted at once and never idle in between: 40 steps of 10 ms
    # cross the 250 ms sampling period, so several cycles are kept
    await generate(eng, 6, new=40)
    last_return = max(i for i, e in enumerate(events) if e == "returned")
    busy = events[:last_return]
    assert busy.count("span") >= 6 * 2 + 7  # the requests' spans and a cycle's
    on_device = False
    for e in busy:
        if e == "handed":
            on_device = True
        elif e == "returned":
            on_device = False
        else:
            assert on_device, "a span was published with no step on the device"
    await eng.stop()


async def test_no_span_is_built_without_a_listener(monkeypatch):
    """With nobody on ``sys.trace.span`` the loop stamps and counts, and
    builds no ``Span`` object at all."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.obs import tracer as tracer_mod

    built = []
    real = tracer_mod.Span

    def counting(*a, **kw):
        built.append(kw.get("name"))
        return real(*a, **kw)

    monkeypatch.setattr(tracer_mod, "Span", counting)
    sink = SpanSink()  # a tracer and a bus, nobody listening
    metrics = Metrics()
    eng = traced_engine(sink, FakeBackend(num_pages=64), max_sessions=4,
                        metrics=metrics)
    await generate(eng, 3)
    await eng.stop()
    assert eng.stats.steps > 0 and built == []
    assert not eng._spans
    # the same run with a listener builds them (the counter does count)
    await sink.listen()
    eng = traced_engine(sink, FakeBackend(num_pages=64), max_sessions=4)
    await generate(eng, 3)
    await eng.stop()
    assert "step" in built and "serving.queue" in built


async def test_step_phase_histogram_sees_every_cycle():
    """``cordum_serving_step_phase_seconds`` is observed for all six phases
    on every cycle, kept or not, and needs no tracer."""
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.serving.backend import STEP_PHASES

    metrics = Metrics()
    eng = ServingEngine(FakeBackend(num_pages=64, step_delay=0.001),
                        run_blocking=run_blocking, max_sessions=4,
                        metrics=metrics)
    await generate(eng, 3, new=10, trace=False)
    await eng.stop()
    counts = {dict(key)["phase"]: (total, s)
              for key, _, s, total in metrics.serving_step_phase._snapshot()}
    assert set(counts) == set(STEP_PHASES)
    assert {total for total, _ in counts.values()} == {eng.stats.steps}
    # the fake's sleep is the wait; the phases sum to about the cycles
    assert counts["wait"][1] >= 0.001 * eng.stats.steps
    assert "cordum_serving_step_phase_seconds" in metrics.render()


async def test_failed_step_leaves_an_error_step_span():
    """A step that raises fails its riders and is kept as a lone ``step``
    root with status ERROR (the phases of a call that never returned are
    unknown)."""
    sink = await SpanSink().listen()
    eng = traced_engine(sink, FakeBackend(num_pages=64, fail_at={2}), max_sessions=4)
    with pytest.raises(RuntimeError):
        await generate(eng, 1)
    out = await eng.submit(  # the loop goes on
        GenRequest(prompt=[2, 7], max_new_tokens=4, stream=False), job_id="next")
    assert out["tokens"] == fake_ref([2, 7], 4)
    await eng.stop()
    await sink.bus.drain()
    (err,) = [s for s in sink.spans if s.status == "ERROR"]
    assert err.name == "step" and err.attrs["error"] == "RuntimeError"
    assert [s.name for s in sink.spans if s.trace_id == err.trace_id] == ["step"]


# ---- the loop outside its cycles, and what lies inside a phase (ISSUE 51) ----

class ReadyFake(FakeBackend):
    """A fake that says when its result was ready (``last_ready_ns``), as a
    backend that feeds a device does, and collects in the steps of
    ``collect_at`` (a forced generation-2 collection inside the step)."""

    def __init__(self, *a, collect_at=(), **kw):
        super().__init__(*a, **kw)
        self.collect_at = set(collect_at)

    def device(self, n_step):
        super().device(n_step)
        if n_step in self.collect_at:
            import gc

            gc.collect()
        self._ready = time.time_ns()
        time.sleep(0.0005)  # the result's way back

    def step(self, entries):
        out = super().step(entries)
        self.last_ready_ns = self._ready
        return out


def record_cycles(eng):
    """Every cycle's own ``(start, end)`` stamps, kept or not."""
    cycles = []
    closed = eng._cycle_closed

    def recording(n_step, marks, attrs):
        cycles.append((marks[0], marks[-1]))
        closed(n_step, marks, attrs)

    eng._cycle_closed = recording
    return cycles


def hold_admission(eng, cycles):
    """Pages that free late: the next ``cycles`` cycles admit nothing."""
    admit, left = eng._admit, [cycles]

    async def late_admit():
        if left[0]:
            left[0] -= 1
        else:
            await admit()

    eng._admit = late_admit


async def freeze_for(eng, log, job, seconds):
    """Freeze ``job`` once it streams, stand still, thaw it: a live session
    and no row to feed."""
    while len(log.packets.get(job, [])) < 2:
        await asyncio.sleep(0.001)
    assert eng.freeze_session(job)
    await eng.wait_quiesced(job)
    await asyncio.sleep(seconds)
    eng.unfreeze_session(job)


async def test_cycles_parks_and_polls_cover_the_loops_life():
    """Every cycle starts where the interval before it ended, and the
    cycles' stamps, ``parked_seconds`` and ``polled_seconds`` add up to the
    loop's life from its first cycle to its last stamp, with nothing
    between."""
    from cordum_tpu.infra.metrics import Metrics

    metrics = Metrics()
    eng = ServingEngine(FakeBackend(num_pages=64, step_delay=0.002, max_context=256),
                        run_blocking=run_blocking, max_sessions=4, metrics=metrics)
    cycles = record_cycles(eng)
    await generate(eng, 2, trace=False)  # busy, then parked
    await asyncio.sleep(0.03)
    hold_admission(eng, 3)  # woken, and three cycles of pending work with no pages
    log = CycleLog()
    task = asyncio.ensure_future(log.submit(eng, "s", [7] * 5, 12))
    await freeze_for(eng, log, "s", 0.02)  # live and nothing to feed
    await task
    await asyncio.sleep(0.01)  # parked again, and woken once more
    await eng.submit(GenRequest(prompt=[3, 1], max_new_tokens=3, stream=False), job_id="z")
    st = eng.stats
    assert len(cycles) == st.steps
    life = (cycles[-1][1] - cycles[0][0]) / 1e9  # the last park is still open
    in_cycles = sum(b - a for a, b in cycles) / 1e9
    assert st.parked_seconds >= 0.03 + 0.01 and st.polled_seconds >= 0.02 + 0.003
    assert abs(life - (in_cycles + st.parked_seconds + st.polled_seconds)) < 1e-6
    # no cycle overlaps the one before, and most follow it at once
    assert all(a[1] <= b[0] for a, b in zip(cycles, cycles[1:]))
    assert sum(a[1] == b[0] for a, b in zip(cycles, cycles[1:])) >= len(cycles) - 8
    idle = metrics.serving_loop_idle
    assert idle.value(state="parked") == pytest.approx(st.parked_seconds)
    assert idle.value(state="poll") == pytest.approx(st.polled_seconds)
    assert "cordum_serving_loop_idle_seconds_total" in metrics.render()
    await eng.stop()


@pytest.mark.parametrize("state", ["parked", "pages", "budget"])
async def test_a_park_covers_its_wait_and_a_poll_names_its_reason(state):
    """``serving.parked`` covers the time the loop stood with nothing live;
    ``serving.poll`` is ONE span over the cycles that fed nothing, with the
    reason; both on a trace ``loop-<worker>-<steps done>``, and neither
    overlaps a kept cycle."""
    sink = await SpanSink().listen()
    eng = traced_engine(sink, FakeBackend(num_pages=64, step_delay=0.002, max_context=256),
                        max_sessions=4)
    await generate(eng, 1)  # the start-up record is told, then the loop parks
    steps_before = eng.stats.steps
    t0 = time.time_ns() // 1000
    await asyncio.sleep(0.03)
    t1 = time.time_ns() // 1000
    log = CycleLog()
    if state == "pages":
        hold_admission(eng, 5)
    task = asyncio.ensure_future(log.submit(eng, "s", [7] * 5, 8))
    if state == "budget":
        await freeze_for(eng, log, "s", 0.02)
    await task
    await eng.stop()
    await sink.bus.drain()
    parks, polls = sink.named("serving.parked"), sink.named("serving.poll")
    (park,) = [p for p in parks if p.start_us <= t0]
    assert park.end_us >= t1 and park.trace_id == f"loop-w-t-{steps_before}"
    assert park.attrs == {} and not park.parent_span_id
    if state == "parked":
        assert polls == []
    else:
        (poll,) = polls  # one span however many cycles polled
        assert poll.attrs == {"reason": state} and poll.trace_id.startswith("loop-w-t-")
        assert poll.duration_us >= (5_000 if state == "pages" else 20_000)
        assert eng.stats.polled_seconds == pytest.approx(poll.duration_us / 1e6, abs=1e-5)
    roots = [spans[-1] for spans in sink.step_traces().values()]
    for idle in parks + polls:
        assert all(r.end_us <= idle.start_us or idle.end_us <= r.start_us for r in roots)


@pytest.mark.parametrize("says", [True, False])
async def test_a_kept_cycle_holds_seven_step_spans_and_what_lies_inside_them(says):
    """``wait.fetch``, ``emit.wake`` and ``runtime.gc`` join a kept cycle's
    trace under names that are no ``step*``: the trace still holds exactly
    seven of those and the benchmark's ``cycles`` still returns it;
    ``wait.fetch`` lies inside ``step.wait`` and is absent for a backend
    whose ``last_ready_ns`` is None, ``emit.wake`` inside ``step.emit``."""
    from benchmarks.layer_metrics import step_cycle_ms

    sink = await SpanSink().listen()
    cls = ReadyFake if says else FakeBackend
    be = cls(num_pages=64, step_delay=0.002, max_context=256, slow_at={70: 0.05})
    if says:
        be.collect_at = {70}
    eng = traced_engine(sink, be, max_sessions=2, max_new_tokens_cap=128)
    await generate(eng, 1, new=90)
    await eng.stop()
    await sink.bus.drain()
    traces = {}
    for s in sink.spans:
        if s.trace_id.startswith("step-"):
            traces.setdefault(s.trace_id, []).append(s)
    assert len(traces) >= 2 and "step-w-t-70" in traces
    run = {"spans": [{"name": s.name, "trace": s.trace_id, "start_us": s.start_us,
                      "end_us": s.end_us} for s in sink.spans]}
    assert len(step_cycle_ms.cycles(run)) == len(traces)
    for trace_id, spans in traces.items():
        by = {s.name: s for s in spans}
        assert sum(n == "step" or n.startswith("step.") for n in by) == 7
        assert spans[-1].name == "step"  # the root still lands last
        if trace_id == "step-w-t-0":
            # closed before the start-up record was told: the parent's seven
            assert len(spans) == 7
            continue
        wait, emit, wake = by["step.wait"], by["step.emit"], by["emit.wake"]
        assert wake.parent_span_id == emit.span_id
        assert emit.start_us == wake.start_us <= wake.end_us <= emit.end_us
        assert ("wait.fetch" in by) == says
        if says:
            fetch = by["wait.fetch"]
            assert fetch.parent_span_id == wait.span_id
            assert wait.start_us <= fetch.start_us <= fetch.end_us == wait.end_us
            assert 400 <= fetch.duration_us < wait.duration_us  # the fake's way back
    if says:
        # the forced collection kept its cycle, with the cause on it
        root = traces["step-w-t-70"][-1]
        gcs = [s for s in traces["step-w-t-70"] if s.name == "runtime.gc"]
        assert gcs and all(s.parent_span_id == root.span_id for s in gcs)
        assert all(root.start_us <= s.start_us <= s.end_us <= root.end_us for s in gcs)
        assert "2" in {s.attrs["generation"] for s in gcs} and root.attrs["gc_gen"] == "2"
        assert float(root.attrs["gc_ms"]) == pytest.approx(
            sum(s.duration_us for s in gcs) / 1e3, abs=0.01)


async def test_a_forced_collection_inside_a_step_keeps_its_cycle():
    """A generation-2 collection inside a fake step: the cycle is kept though
    the rate cap would drop it and it is no stall, the pause lies inside the
    cycle's ``step.wait``, the root carries ``gc_ms``, and the stats count
    every pause; with nobody listening the stats alone."""
    sink = await SpanSink().listen()
    be = ReadyFake(num_pages=64, step_delay=0.001, max_context=256, collect_at={20, 22})
    eng = traced_engine(sink, be, max_sessions=2, max_new_tokens_cap=128)
    await generate(eng, 1, new=40)
    assert eng.stats.gc_pauses >= 2 and eng.stats.gc_pause_seconds > 0
    await eng.stop()
    await sink.bus.drain()
    traces = sink.step_traces()
    for n in (20, 22):  # 22 began well inside 250 ms of 20
        root = traces[f"step-w-t-{n}"][-1]
        wait = next(s for s in traces[f"step-w-t-{n}"] if s.name == "step.wait")
        pause = max((s for s in sink.named("runtime.gc") if s.trace_id == root.trace_id),
                    key=lambda s: s.duration_us)
        assert wait.start_us <= pause.start_us and pause.end_us <= wait.end_us
        assert float(root.attrs["gc_ms"]) > 0
    assert "gc_ms" not in traces["step-w-t-0"][-1].attrs
    quiet = ServingEngine(ReadyFake(num_pages=64, collect_at={3}), run_blocking=run_blocking)
    await generate(quiet, 1, new=8, trace=False)
    assert quiet.stats.gc_pauses >= 1 and not quiet._spans
    await quiet.stop()


async def test_the_collector_is_watched_from_the_startup_record_to_stop(monkeypatch):
    """``gc.callbacks`` is as it was until ``_tell_startup`` and again after
    ``stop()``; between them it holds ONE more entry, shared with a
    ``RuntimeProfiler`` started beside the engine."""
    import gc

    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.obs import profiler
    from cordum_tpu.obs.profiler import RuntimeProfiler
    from cordum_tpu.serving import engine as engine_mod

    # the process's watch may still be held by an engine some test never
    # stopped: this test gets one of its own
    GC_PAUSES = profiler.GcPauses()
    monkeypatch.setattr(profiler, "GC_PAUSES", GC_PAUSES)
    monkeypatch.setattr(engine_mod, "GC_PAUSES", GC_PAUSES)
    before = list(gc.callbacks)
    be = FakeBackend(num_pages=64, max_batch_tokens=4)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=2)
    seen = []
    tell = eng._tell_startup

    def telling(end_ns):
        seen.append(list(gc.callbacks))
        tell(end_ns)
        seen.append(list(gc.callbacks))

    eng._tell_startup = telling
    # a prompt of three chunks: two cycles run before a token is sampled
    out = await eng.submit(GenRequest(prompt=[5] * 10, max_new_tokens=4, stream=False),
                           job_id="a")
    assert len(out["tokens"]) == 4 and be.steps >= 6
    assert seen[0] == before and seen[1] == before + [GC_PAUSES._on_gc]
    metrics = Metrics()
    prof = RuntimeProfiler(metrics, service="test", tick_s=5.0)
    await prof.start()
    assert gc.callbacks == before + [GC_PAUSES._on_gc]  # one entry, two holders
    gc.collect()
    assert metrics.gc_pauses.value(generation="2") == 1
    await prof.stop()
    assert gc.callbacks == before + [GC_PAUSES._on_gc]  # the engine still holds it
    await eng.stop()
    assert gc.callbacks == before


async def test_first_packet_span_ends_after_the_sink_returned():
    """``serving.first_packet`` lies on the request's own trace beside
    ``serving.queue`` / ``serving.prefill``, starts where ``serving.prefill``
    ends and ends after the session's first ``on_tokens`` call returned; a
    session without a sink, and the one whose first token came before the
    start-up record was told, have none."""
    sink = await SpanSink().listen()
    eng = traced_engine(sink, FakeBackend(num_pages=64, step_delay=0.002), max_sessions=4)
    returned = {}

    def streaming(job):
        async def on_tokens(tokens, n_generated, done):
            await asyncio.sleep(0.003)
            returned.setdefault(job, time.time_ns() // 1000)
        return on_tokens

    async def submit(job, sinked=True):
        return await eng.submit(
            GenRequest(prompt=[4, 2, 9], max_new_tokens=5), job_id=job, trace_id=f"tr-{job}",
            parent_span_id=f"exec-{job}", on_tokens=streaming(job) if sinked else None)

    await submit("first")  # its first token closes the start-up record
    await asyncio.gather(submit("a"), submit("b"), submit("quiet", sinked=False))
    await eng.stop()
    await sink.bus.drain()
    by_trace = {}
    for s in sink.named("serving.first_packet"):
        assert s.trace_id not in by_trace
        by_trace[s.trace_id] = s
    assert set(by_trace) == {"tr-a", "tr-b"}
    for job in ("a", "b"):
        sp = by_trace[f"tr-{job}"]
        (prefill,) = [s for s in sink.named("serving.prefill") if s.trace_id == sp.trace_id]
        assert sp.parent_span_id == prefill.parent_span_id == f"exec-{job}"
        assert sp.start_us == prefill.end_us
        assert sp.duration_us >= 3_000 and returned[job] - 50 <= sp.end_us <= returned[job] + 20_000
        assert set(sp.attrs) == {"behind_step", "tokens"} and sp.attrs["tokens"] == "1"


def test_ragged_step_scopes_are_metadata_only():
    """The named scopes in ``ragged_step`` change no equation of its jaxpr,
    and its lowered text names all six."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import attention, llama

    cfg = llama.LlamaConfig.tiny()
    params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    k_pages, v_pages = jax.eval_shape(lambda: attention.init_kv_pages(cfg, 8, 4))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = (params, k_pages, v_pages, i32(8), i32(8), i32(5, 4), i32(8), i32(4))

    def step(*a):
        return llama.ragged_step(*a, cfg)

    scoped = jax.make_jaxpr(step)(*args)
    lowered = jax.jit(step).lower(*args).as_text(debug_info=True)
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        bare = jax.make_jaxpr(step)(*args)
    finally:
        jax.named_scope = real
    assert len(scoped.eqns) == len(bare.eqns) and str(scoped) == str(bare)
    for scope in ("embed", "kv_write", "attn_gather", "attn_scores", "mlp", "lm_head"):
        assert f"/{scope}/" in lowered or f"/{scope}\"" in lowered, scope
