"""The contract between ``ServingEngine`` and what runs its step
(``serving.backend.StepBackend``, docs/SERVING.md §Backend contract): every
implementer has every member with the declared type and reports a step the
same way, and the engine makes the same decisions over the tests' fake as
over the backend the chip runs."""
import time
import typing

import pytest

from cordum_tpu.serving.backend import ServingBackend, StepBackend, StepEntry
from cordum_tpu.serving.engine import GenRequest, ServingEngine

from .fakes import FakeBackend, run_blocking

SHAPES = dict(num_pages=32, page_size=4, max_seqs=4, max_batch_tokens=8)


def build(kind):
    if kind == "fake":
        return FakeBackend(max_context=64, **SHAPES)
    if kind == "afmoe":
        import jax

        from cordum_tpu.models import afmoe

        from .test_afmoe_serving import tiny

        cfg = tiny()
        return ServingBackend(
            cfg, params=afmoe.init_params(jax.random.PRNGKey(3), cfg), **SHAPES)
    from .test_sharded_serving import tiny_cfg, tiny_params

    cfg = tiny_cfg()
    kw = dict(params=tiny_params(cfg), max_context=64, **SHAPES)
    if kind == "llama":
        return ServingBackend(cfg, **kw)
    from cordum_tpu.serving.shard import ServingGangGroup, ShardedServingBackend

    return (ShardedServingBackend(cfg, tp=2, **kw) if kind == "sharded-tp2"
            else ServingGangGroup(cfg, tp=2, **kw))


@pytest.mark.parametrize("kind", ["llama", "afmoe", "sharded-tp2", "gang-tp2", "fake"])
def test_every_backend_keeps_the_contract(kind):
    be = build(kind)
    assert isinstance(be, StepBackend)
    hints = typing.get_type_hints(StepBackend)
    assert set(StepBackend.SHAPES + StepBackend.REPORT) < set(hints)

    def check_members():
        for name, hint in hints.items():
            value = getattr(be, name)
            if name in ("on_step", "on_dispatched"):
                assert value is None or callable(value)
            else:
                assert isinstance(value, typing.get_origin(hint) or hint), name

    check_members()
    for shape in ("num_pages", "page_size", "max_context", "max_seqs", "max_batch_tokens"):
        assert getattr(be, shape) > 0
    # a ring and its pool come together, and only without whole rows
    assert bool(be.ring_pages) == bool(be.num_window_pages) == (not be.kv_whole_row)
    assert be.last_phases == () and be.last_attn_blocks == (0, 0)

    ring = be.ring_pages
    rows = [StepEntry(tokens=[5, 9, 2], start=0, pages=[1, 2], phase="prefill",
                      window_pages=list(range(1, 1 + ring))),
            StepEntry(tokens=[7], start=0, pages=[3, 4],
                      window_pages=list(range(1 + ring, 1 + 2 * ring)))]
    tapped, fed = [], []
    be.on_step = tapped.append
    be.on_dispatched = lambda: fed.append(time.time_ns())
    before = time.time_ns()
    out = be.step(rows)
    after = time.time_ns()
    assert len(out) == 2 and all(type(t) is int for t in out)
    assert tapped == [rows]
    # said once a step: after its dispatch, or on entry where there is none
    assert len(fed) == 1 and before <= fed[0] <= be.last_phases[3]
    assert be.last_phases[2] <= fed[0] or be.last_phases[0] == be.last_phases[2]
    check_members()
    assert len(be.last_phases) == 5
    assert [before, *be.last_phases, after] == sorted([before, *be.last_phases, after])
    assert type(be.last_step_compiled) is bool
    walked, of = be.last_attn_blocks
    assert type(walked) is int and type(of) is int and walked <= of
    # a backend that counts blocks says how long one is
    assert (be.attn_block_tokens > 0) == (of > 0)
    assert of == 0 or of * be.attn_block_tokens >= be.max_context
    assert (be.last_window_blocks > 0) == bool(ring)
    if be.kv_whole_row:
        # page 1 holds row 0's three positions; a copy of it is the same
        # page, and so is what an import makes of its export
        be.copy_page(1, 5)
        recs = be.export_kv([5], 0, 3)
        assert recs and all(r["i"] == 0 and r["used"] == 3 for r in recs)
        assert recs == be.export_kv([1], 0, 3)
        if kind != "sharded-tp2":  # one rank exports its heads: half a page
            be.import_kv([6], recs)
            assert be.export_kv([6], 0, 3) == recs


@pytest.mark.parametrize("kind", ["llama", "fake"])
async def test_the_fake_and_the_real_backend_see_the_same_engine(kind):
    """One scenario, length-bounded with no EOS, so no decision depends on a
    token's value: a prompt chunked over three steps, then a second session
    whose prompt is three full pages of the first's (a prefix hit that ends
    at the prompt's end, so its last page is copied before it is written)."""
    eng = ServingEngine(build(kind), run_blocking=run_blocking, max_new_tokens_cap=16)
    assert (eng.max_sessions, eng.step_tokens, eng.max_context) == (4, 8, 64)
    assert eng.prefix is not None and eng.window_allocator is None
    first = list(range(10, 30))
    for job_id, prompt, n_new in (("a", first, 6), ("b", first[:12], 4)):
        out = await eng.submit(
            GenRequest(prompt=prompt, max_new_tokens=n_new, stream=False), job_id=job_id)
        assert len(out["tokens"]) == n_new and out["finish_reason"] == "length"
    st = eng.stats
    assert {
        "admitted": st.admitted, "retired": st.retired, "failed": st.failed,
        "steps": st.steps, "prefill_chunks": st.prefill_chunks,
        "prefill_tokens": st.prefill_tokens, "decoded_tokens": st.decoded_tokens,
        "prefix_hits": st.prefix_hits, "prefix_misses": st.prefix_misses,
        "prefix_hit_tokens": st.prefix_hit_tokens, "cow_copies": st.cow_copies,
        "max_occupancy": st.max_occupancy,
        "pages_held": eng.allocator.used_pages, "pages_cached": eng.prefix.warm_pages,
    } == {
        "admitted": 2, "retired": 2, "failed": 0,
        "steps": 12, "prefill_chunks": 4,
        "prefill_tokens": 21, "decoded_tokens": 10,
        "prefix_hits": 1, "prefix_misses": 1,
        "prefix_hit_tokens": 11, "cow_copies": 1,
        "max_occupancy": 1,
        "pages_held": 6, "pages_cached": 6,
    }
    eng.allocator.check_consistency()
    await eng.stop()
