"""Prefix cache + session tiering (ISSUE 18, docs/SERVING.md §Prefix cache
and tiering): refcounted copy-on-write shared-prefix KV pages, radix-cache
admission hits, LRU eviction under exhaustion, and hibernate/restore through
the host-RAM cold arena — with the allocator's accounting property-tested
under random admit/share/CoW/free/hibernate interleavings and the real paged
backend pinned to the fp32 sequential oracle."""
import asyncio
import random
import time

import pytest

from cordum_tpu.serving.engine import (
    GenRequest,
    ServingEngine,
    SessionHibernated,
)
from cordum_tpu.serving.pager import (
    CacheExhausted,
    PageAccountingError,
    PageAllocator,
)
from cordum_tpu.serving.prefixcache import PrefixCache

from .fakes import FakeBackend, fake_ref, run_blocking
from .test_serving_failover import wait_until

# ------------------------------------------------------- allocator refcounts


def test_refcount_share_lifecycle():
    a = PageAllocator(8, 4)
    p = a.alloc("s1", 3)
    a.retain([p[0]])
    assert a.refcount(p[0]) == 2 and a.stats.shares == 1
    assert a.free("s1") == 2  # the shared page survives under the extra ref
    assert a.refcount(p[0]) == 1 and a.free_pages == 6
    assert a.release([p[0]]) == 1
    assert a.free_pages == 7
    a.check_consistency()


def test_double_free_and_share_of_free_raise():
    a = PageAllocator(8, 4)
    p = a.alloc("s1", 2)
    a.free("s1")
    with pytest.raises(PageAccountingError):
        a.release([p[0]])  # double free fails loudly
    with pytest.raises(PageAccountingError):
        a.retain([p[1]])  # sharing a freed page would alias the free list
    assert a.free("s1") == 0  # unknown-owner free stays a benign no-op
    a.check_consistency()


def test_alloc_shared_all_or_nothing():
    a = PageAllocator(8, 4)  # capacity 7
    shared = a.alloc("cache", 2)
    with pytest.raises(CacheExhausted):
        a.alloc("s2", 6, shared=shared)
    assert a.refcount(shared[0]) == 1  # the failed admission touched nothing
    got = a.alloc("s2", 3, shared=shared)
    assert got[:2] == shared and len(got) == 5
    assert a.refcount(shared[0]) == 2
    assert a.free("s2") == 3  # fresh tail freed, shared prefix survives
    assert a.free("cache") == 2
    a.check_consistency()
    assert a.free_pages == a.capacity


def test_swap_owned_cow_bookkeeping():
    a = PageAllocator(8, 4)
    pages = a.alloc("s1", 2)
    (fresh,) = a.alloc_raw(1)
    a.swap_owned("s1", pages[1], fresh)
    a.release([pages[1]])  # the CoW path's release of the old page
    assert a.free("s1") == 2  # pages[0] + the swapped-in fresh page
    a.check_consistency()
    assert a.free_pages == a.capacity
    with pytest.raises(PageAccountingError):
        a.swap_owned("nobody", 1, 2)


def test_allocator_random_ops_property():
    """No interleaving of alloc/share/release/free ever leaves a page both
    free and referenced, a negative refcount, or a lost page."""
    rng = random.Random(7)
    a = PageAllocator(17, 4)
    owners: dict[str, list[int]] = {}
    cache: list[int] = []  # bare references (retain'd / alloc_raw'd)
    for step in range(2000):
        op = rng.random()
        if op < 0.35:
            name = f"o{step}"
            shared = (
                [rng.choice(cache) for _ in range(rng.randint(0, 2))]
                if cache else []
            )
            try:
                n = rng.randint(0 if shared else 1, 4)
                owners[name] = a.alloc(name, n, shared=shared)
                # the allocator added one ref per shared entry on top of the
                # cache's own — the owner's table now co-holds those pages
            except (CacheExhausted, ValueError):
                pass
        elif op < 0.55 and owners:
            name = rng.choice(list(owners))
            a.free(name)
            del owners[name]
        elif op < 0.7:
            live = [p for pages in owners.values() for p in pages]
            if live:
                p = rng.choice(live)
                a.retain([p])
                cache.append(p)
        elif op < 0.85 and cache:
            a.release([cache.pop(rng.randrange(len(cache)))])
        else:
            try:
                cache.extend(a.alloc_raw(rng.randint(1, 2)))
            except CacheExhausted:
                pass
        a.check_consistency(live_tables=owners)
    for name in list(owners):
        a.free(name)
    while cache:
        a.release([cache.pop()])
    a.check_consistency()
    assert a.free_pages == a.capacity


# ------------------------------------------------------------- radix cache


def test_radix_match_register_evict():
    a = PageAllocator(32, 4)
    c = PrefixCache(a)
    toks = list(range(1, 13))  # 12 tokens = 3 full pages
    pages = a.alloc("s1", 3)
    assert c.match(toks) == []
    assert c.register(toks, pages) == 3
    a.free("s1")
    assert a.used_pages == 3  # the cache's refs keep them off the free list
    assert [n.page for n in c.match(toks + [99])] == pages
    # a divergent suffix shares only the common full-page prefix
    assert [n.page for n in c.match(toks[:8] + [7, 7, 7, 7])] == pages[:2]
    # partial trailing page is never cached
    assert c.register(toks[:6], a.alloc("s2", 2)) == 0
    a.free("s2")
    assert c.evict(2) == 2 and a.used_pages == 1  # LRU leaves first
    c.evict(5)
    assert a.used_pages == 0 and c.warm_pages == 0
    a.check_consistency()


def test_evict_skips_pages_shared_with_live_sessions():
    a = PageAllocator(32, 4)
    c = PrefixCache(a)
    toks = list(range(1, 13))
    pages = a.alloc("s1", 3)
    c.register(toks, pages)
    a.free("s1")
    a.retain([pages[0]])  # a live session still maps the first page
    assert c.evict(3) == 2  # the shared root is not evictable
    assert a.refcount(pages[0]) == 2 and a.used_pages == 1
    a.release([pages[0]])
    a.release([pages[0]])
    a.check_consistency()


def test_demote_promote_roundtrip():
    a = PageAllocator(16, 4)
    c = PrefixCache(a)
    toks = [5, 6, 7, 8]
    pages = a.alloc("s1", 1)
    c.register(toks, pages)
    a.free("s1")
    (node,) = c.match(toks)
    # demote refuses while a live sharer holds the page
    a.retain([node.page])
    assert c.demote(node, {"i": 0, "k": [5, 6, 7, 8]}) is False
    a.release([node.page])
    assert c.demote(node, {"i": 0, "k": [5, 6, 7, 8]}) is True
    assert node.cold and a.used_pages == 0 and c.cold_pages == 1
    # the cold node still matches; promote re-warms it onto a fresh page
    (again,) = c.match(toks)
    assert again is node
    (fresh,) = a.alloc_raw(1)
    c.promote(node, fresh)
    assert node.warm and c.warm_pages == 1
    c.evict(1)
    a.check_consistency()


# --------------------------------------------- engine (arena-modeling fake)


class Tap:
    """Token-stream sink asserting exactly-once delivery: the engine emits
    (tokens, end_offset, done); replays must agree with what streamed."""

    def __init__(self):
        self.buf: list[int] = []

    async def __call__(self, tokens, end_offset, done):
        start = end_offset - len(tokens)
        for i, t in enumerate(tokens):
            idx = start + i
            if idx == len(self.buf):
                self.buf.append(int(t))
            elif idx < len(self.buf):
                assert self.buf[idx] == int(t), (
                    f"replayed token diverges at {idx}: {self.buf[idx]} vs {t}")
            else:
                raise AssertionError(f"gap in stream at {idx}")


async def test_prefix_cache_requires_whole_rows():
    """A backend whose pages do not cover the whole row under one table
    (the contract's one capability) cannot share them: the cache and the
    tiering stay off entirely, as they do when the caller says so."""
    windowed = FakeBackend()
    windowed.kv_whole_row = False
    for be, kw in ((windowed, {}), (FakeBackend(), {"prefix_cache": False})):
        eng = ServingEngine(be, run_blocking=run_blocking, **kw)
        assert eng.prefix is None and eng.tiering is None
        await eng.stop()


async def test_prefix_hit_skips_prefill_token_identical():
    be = FakeBackend(num_pages=32, page_size=4, max_context=128)
    eng = ServingEngine(be, run_blocking=run_blocking, max_new_tokens_cap=64)
    assert eng.prefix is not None
    prompt = [9, 2, 7, 1, 8, 3, 5, 4, 6]  # two full pages + one token
    out1 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=6, stream=False),
        job_id="a"), timeout=20)
    assert out1["tokens"] == fake_ref(prompt, 6)
    assert eng.stats.prefix_misses == 1 and be.fed_prefill["a"] == len(prompt)
    out2 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=6, stream=False),
        job_id="b"), timeout=20)
    # token-identical to the no-sharing run, with the shared pages' prefill
    # skipped: only the post-divergence token crosses the device
    assert out2["tokens"] == out1["tokens"]
    assert eng.stats.prefix_hits == 1
    assert eng.stats.prefix_hit_tokens == 8
    assert be.fed_prefill["b"] == len(prompt) - 8
    eng.allocator.check_consistency()
    await eng.stop()


async def test_page_aligned_hit_cow_protects_shared_page():
    """A prompt that is an exact page multiple backs its hit up one token;
    re-feeding the final token writes into shared territory, which the CoW
    guard must copy — the cached page stays byte-identical for later hits."""
    be = FakeBackend(num_pages=32, page_size=4, max_context=128)
    eng = ServingEngine(be, run_blocking=run_blocking, max_new_tokens_cap=64)
    prompt = [11, 3, 7, 2, 9, 5, 8, 1]  # exactly two pages
    out1 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=5, stream=False),
        job_id="a"), timeout=20)
    cached = [n.page for n in eng.prefix.match(prompt, touch=False)]
    snapshot = [list(be.arena[p]) for p in cached]
    out2 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=5, stream=False),
        job_id="b"), timeout=20)
    assert out2["tokens"] == out1["tokens"] == fake_ref(prompt, 5)
    assert eng.stats.prefix_hits == 1 and eng.stats.prefix_hit_tokens == 7
    assert be.copies >= 1 and eng.stats.cow_copies >= 1
    # the shared pages the cache holds were never scribbled on
    assert [list(be.arena[p]) for p in cached] == snapshot
    out3 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=5, stream=False),
        job_id="c"), timeout=20)
    assert out3["tokens"] == out1["tokens"] and eng.stats.prefix_hits == 2
    eng.allocator.check_consistency()
    await eng.stop()


async def test_exhaustion_lru_evicts_cached_prefixes():
    be = FakeBackend(num_pages=8, page_size=4, max_context=128,
                          max_batch_tokens=64)
    eng = ServingEngine(be, run_blocking=run_blocking, max_new_tokens_cap=64)
    p_old = list(range(1, 17))       # 16 tokens: 4 full pages when cached
    p_new = list(range(101, 117))    # distinct: a miss that needs room
    out = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=p_old, max_new_tokens=4, stream=False),
        job_id="old"), timeout=20)
    assert out["tokens"] == fake_ref(p_old, 4)
    cached = eng.prefix.warm_pages
    assert cached >= 4
    # footprint 5 > free pages: admission LRU-evicts the cache's pages
    # instead of parking in the admission queue forever
    out = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=p_new, max_new_tokens=4, stream=False),
        job_id="new"), timeout=20)
    assert out["tokens"] == fake_ref(p_new, 4)
    assert eng.prefix.stats.evicted_pages >= 1
    eng.allocator.check_consistency()
    await eng.stop()


async def test_turn_hibernate_restore_roundtrip():
    """A finished conversation's cached pages demote to host-RAM records on
    the idle sweep (device pages freed), and the next turn re-warms them —
    token-identical to never having hibernated, with the tier accounting
    and worker hooks following along."""
    be = FakeBackend(num_pages=32, page_size=4, max_context=128)
    eng = ServingEngine(be, run_blocking=run_blocking, max_new_tokens_cap=64,
                        hibernate_after_s=30.0)
    events: list[tuple[str, str]] = []
    eng.tiering.on_hibernated = lambda k: events.append(("hibernated", k))
    eng.tiering.on_restored = lambda k: events.append(("restored", k))
    prompt = [4, 8, 2, 6, 1, 9]
    out1 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=7, stream=False,
                   session_key="conv"),
        job_id="t1"), timeout=20)
    assert out1["tokens"] == fake_ref(prompt, 7)
    warm = eng.prefix.warm_pages
    assert warm >= 2 and eng.tiering.resident_sessions == 1
    assert eng.tiering.tier_counts() == (1, 0)
    demoted = await eng.tiering.sweep(now=time.monotonic() + 60)
    assert demoted == warm
    assert eng.prefix.warm_pages == 0 and eng.prefix.cold_pages == warm
    assert eng.allocator.used_pages == 0  # device arena fully released
    assert eng.tiering.tier_counts() == (0, 1)
    assert events == [("hibernated", "conv")]
    # next turn: history + new suffix — the cold path restores, then hits
    p2 = prompt + out1["tokens"] + [42]
    out2 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=p2, max_new_tokens=4, stream=False,
                   session_key="conv"),
        job_id="t2"), timeout=20)
    assert out2["tokens"] == fake_ref(p2, 4)
    assert eng.stats.prefix_hits == 1
    assert eng.prefix.stats.restored_pages >= warm
    assert ("restored", "conv") in events
    eng.allocator.check_consistency()
    await eng.stop()


async def test_live_hibernate_restore_exactly_once():
    """hibernate_session freezes a mid-decode session whole into the cold
    arena (waiter sees SessionHibernated, device pages freed);
    restore_hibernated resumes it token-identically and the stream dedupes
    to an exactly-once sequence across the gap."""
    be = FakeBackend(num_pages=32, page_size=4, max_context=128,
                          step_delay=0.01)
    eng = ServingEngine(be, run_blocking=run_blocking, max_new_tokens_cap=64)
    tap = Tap()
    prompt = [3, 1, 4, 1, 5]
    src = asyncio.ensure_future(eng.submit(
        GenRequest(prompt=prompt, max_new_tokens=24, stream=True,
                   session_key="hib"),
        job_id="h1", on_tokens=tap))
    await wait_until(
        lambda: (eng.export_state("h1") or {}).get("pos", 0) >= 10,
        msg="session mid-decode")
    assert await eng.hibernate_session("h1") is True
    with pytest.raises(SessionHibernated):
        await asyncio.wait_for(src, timeout=5)
    assert eng.allocator.used_pages == 0
    assert "h1" in eng.tiering.arena and eng.tiering.arena.bytes > 0
    assert eng.stats.hibernated_out == 1
    fut = await eng.restore_hibernated("h1", on_tokens=tap)
    toks = await asyncio.wait_for(fut, timeout=20)
    assert toks == fake_ref(prompt, 24)
    assert eng.stats.restored_in == 1
    await wait_until(lambda: len(tap.buf) == 24, msg="stream complete")
    assert tap.buf == toks  # exactly-once across the hibernate gap
    assert len(eng.tiering.arena) == 0 and eng.tiering.arena.bytes == 0
    eng.allocator.check_consistency()
    await eng.stop()


async def test_random_interleaving_accounting_property():
    """Random admissions over shared prompt pools interleaved with
    hibernate sweeps: every session's tokens match the sequential oracle
    and the allocator's invariants hold at every checkpoint."""
    rng = random.Random(99)
    be = FakeBackend(num_pages=24, page_size=4, max_context=96,
                          step_delay=0.001)
    eng = ServingEngine(be, run_blocking=run_blocking, max_sessions=6,
                        max_new_tokens_cap=64, hibernate_after_s=30.0)
    base = [[rng.randrange(1, 200) for _ in range(rng.randint(4, 10))]
            for _ in range(3)]
    expected: dict[str, list[int]] = {}
    tasks = []
    for i in range(18):
        if rng.random() < 0.6:
            prompt = list(rng.choice(base)) + [
                rng.randrange(1, 200) for _ in range(rng.randint(0, 4))]
        else:
            prompt = [rng.randrange(1, 200) for _ in range(rng.randint(1, 10))]
        n_new = rng.randint(2, 10)
        jid = f"r{i}"
        expected[jid] = fake_ref(prompt, n_new)
        tasks.append(asyncio.ensure_future(eng.submit(
            GenRequest(prompt=prompt, max_new_tokens=n_new, stream=False,
                       session_key=f"conv{i % 5}"),
            job_id=jid)))
        if rng.random() < 0.4:
            await asyncio.sleep(0.005)
            # alternate aggressive and no-op sweeps mid-flight
            shift = 60 if rng.random() < 0.5 else -60
            await eng.tiering.sweep(now=time.monotonic() + shift)
            eng.allocator.check_consistency(live_tables={
                s.job_id: s.pages for s in eng._active.values()})
    outs = await asyncio.wait_for(asyncio.gather(*tasks), timeout=60)
    for jid, out in zip(expected, outs):
        assert out["tokens"] == expected[jid], jid
    eng.allocator.check_consistency(live_tables={
        s.job_id: s.pages for s in eng._active.values()})
    assert eng.stats.prefix_hits > 0  # the pools actually shared
    # drain the cache completely: every page accounted back to the free list
    eng.prefix.evict(eng.allocator.capacity)
    assert eng.allocator.used_pages == 0
    eng.allocator.check_consistency()
    await eng.stop()


# --------------------------------------------------- CI perf-floor wiring


def test_floor_checker_gates_chat_keys():
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
    base = {"chat_prefix_ttft_speedup": 2.4, "chat_token_identical": 1,
            "chat_prefix_hit_rate": 0.857, "chat_resident_over_capacity": 1.6,
            "chat_restored_pages": 8, "chat_restore_pause_p50_ms": 1.0}
    # healthy values: no chat-key violations (other keys flag missing)
    assert not any("chat" in v for v in mod.check(dict(base), floors))
    for key, bad in [("chat_prefix_ttft_speedup", 1.0),
                     ("chat_token_identical", 0),
                     ("chat_prefix_hit_rate", 0.1),
                     ("chat_resident_over_capacity", 0.9),
                     ("chat_restored_pages", 0),
                     ("chat_restore_pause_p50_ms", 900.0)]:
        doc = dict(base)
        doc[key] = bad
        assert any(key in v for v in mod.check(doc, floors)), key
    # a missing chat key is itself a violation (the gate cannot be skipped)
    doc = dict(base)
    doc.pop("chat_token_identical")
    assert any("chat_token_identical" in v for v in mod.check(doc, floors))


# ---------------------------------------------------- real backend (fp32)


async def test_prefix_and_hibernate_real_backend_oracle():
    """On the real paged-Llama backend: a session sharing a cached system
    prefix produces EXACTLY the fp32 sequential-oracle tokens (sharing is a
    placement change, not a math change), and a hibernate → restore cycle
    through host-RAM records is bit-identical to never hibernating."""
    import jax
    import jax.numpy as jnp

    from cordum_tpu.models import llama
    from cordum_tpu.serving.backend import LlamaServingBackend

    from .test_serving import ref_greedy

    cfg = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=128,
                            dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    be = LlamaServingBackend(cfg, num_pages=64, page_size=8,
                             params_provider=lambda: params)
    eng = ServingEngine(be, run_blocking=run_blocking, max_new_tokens_cap=64,
                        hibernate_after_s=30.0)
    assert eng.prefix is not None  # the real backend carries copy_page
    system = [7, 3, 11, 19, 2, 5, 23, 1]  # exactly one 8-slot page
    p1 = system + [13, 4]
    out1 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=p1, max_new_tokens=8, stream=False,
                   session_key="s1"),
        job_id="rb1"), timeout=180)
    assert out1["tokens"] == ref_greedy(cfg, params, p1, 8)
    p2 = system + [42, 9, 77]
    out2 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=p2, max_new_tokens=8, stream=False,
                   session_key="s2"),
        job_id="rb2"), timeout=180)
    assert eng.stats.prefix_hits >= 1 and eng.stats.prefix_hit_tokens >= 8
    assert out2["tokens"] == ref_greedy(cfg, params, p2, 8)
    # hibernate every idle cached page, then a third turn restores them
    demoted = await eng.tiering.sweep(now=time.monotonic() + 60)
    assert demoted >= 1 and eng.prefix.warm_pages == 0
    p3 = p1 + out1["tokens"][:2]
    out3 = await asyncio.wait_for(eng.submit(
        GenRequest(prompt=p3, max_new_tokens=6, stream=False,
                   session_key="s1"),
        job_id="rb3"), timeout=180)
    assert out3["tokens"] == ref_greedy(cfg, params, p3, 6)
    assert eng.prefix.stats.restored_pages >= 1
    eng.allocator.check_consistency()
    await eng.stop()
