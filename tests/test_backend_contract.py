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
            elif name == "last_ready_ns":
                assert value is None or type(value) is int
            else:
                assert isinstance(value, typing.get_origin(hint) or hint), name

    check_members()
    for shape in ("num_pages", "page_size", "max_context", "max_seqs", "max_batch_tokens"):
        assert getattr(be, shape) > 0
    # a ring and its pool come together, and only without whole rows
    assert bool(be.ring_pages) == bool(be.num_window_pages) == (not be.kv_whole_row)
    assert be.last_phases == () and be.last_attn_blocks == (0, 0)
    assert be.last_ready_ns is None

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
    # a backend that feeds a device says when its result was ready, inside its
    # ``wait``; one whose call is a single ``wait`` (the fake, the gang: each
    # rank waits in its turn) does not say
    if kind in ("fake", "gang-tp2"):
        assert be.last_ready_ns is None
    else:
        assert be.last_phases[2] <= be.last_ready_ns <= be.last_phases[3]
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


# ----------------------------------------------------------------- the seam
#: what names a model family or a kernel's module: neither the engine nor the
#: backend says any of them (``serving/modelspec.py``: the model's
#: specification states its kernels and names its counters)
FAMILY_WORDS = ("moe_", "kda_", "ssd", "afmoe", "axk1", "bailing", "longcat", "falcon",
                "latent_walk", "head_walk", "expert_mlp")


def test_the_engine_and_the_backend_name_no_family_and_no_kernel():
    import ast
    import dataclasses
    import inspect
    import io
    import tokenize

    from cordum_tpu.serving import backend, engine
    from cordum_tpu.serving.engine import ServingStats

    for module in (engine, backend):
        source = inspect.getsource(module)
        code = " ".join(tok.string for tok in tokenize.generate_tokens(io.StringIO(source).readline)
                        if tok.type != tokenize.COMMENT)
        assert not [w for w in FAMILY_WORDS if w in code], module.__name__
        assert "getattr(self.cfg" not in code  # no probe of a model's shape
        # what the module imports of ``cordum_tpu.models``, wherever in it
        taken = set()
        for node in ast.walk(ast.parse(source)):
            if isinstance(node, ast.ImportFrom) and (node.module or "").split(".")[0] == "models":
                taken |= {f"{node.module}.{a.name}" for a in node.names}
            assert not (isinstance(node, ast.Import)
                        and any(".models" in a.name for a in node.names))
        # the walk's own module, and the default model of a backend built with none
        assert taken == ({"models.attention", "models.llama.LlamaConfig"} if module is backend
                         else set())
    hints = typing.get_type_hints(StepBackend)
    assert {"kernels", "last_attrs", "last_counters"} <= set(hints)
    assert not {"walk_kernel", "expert_kernel", "state_kernel"} & set(dir(StepBackend))
    assert "last_attrs" in StepBackend.REPORT
    fields = {f.name for f in dataclasses.fields(ServingStats)}
    assert "model" in fields and {"state_slots_peak", "state_decode_rows",
                                  "state_chunk_tokens"} <= fields
    assert not [f for f in fields if f.startswith(("moe_", "kda_", "state_rows_"))]


@pytest.mark.parametrize("kind", ["fake", "real"])
async def test_a_made_up_family_needs_no_edit_of_the_engine_or_the_backend(kind, monkeypatch):
    """A specification made up here, with a counter, a span attribute and a
    kernel label of its own, reaches ``ServingStats.model``, the ``step`` span
    and (through a real backend) the ``startup.kernels`` phase."""
    import dataclasses

    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.obs import startup
    from cordum_tpu.obs.tracer import Tracer
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.serving import engine as engine_mod

    monkeypatch.setattr(engine_mod, "STEP_SAMPLE_PERIOD_NS", 0)  # every cycle a ``step`` trace
    if kind == "fake":
        be = FakeBackend(max_context=64, **SHAPES)
        inner = be.step

        def step(entries):  # a backend's report of a step, under names nobody declared
            fed = sum(len(e.tokens) for e in entries)
            be.last_counters = {"made_up_fed": fed, "made_up_sevens": 7}
            be.last_attrs = {"made_up": f"{fed}/made_up_step"}
            return inner(entries)

        be.step = step
    else:
        import jax.numpy as jnp

        from cordum_tpu.models import llama

        base = llama.LlamaConfig.tiny().serving_spec()

        def program(sample_logits):
            dense = base.program(sample_logits)

            def ragged_program(p, kp, vp, toks, pos, pt, ts, oi):
                out, kp, vp = dense(p, kp, vp, toks, pos, pt, ts, oi)
                fed = jnp.sum(ts < pt.shape[0] - 1, dtype=jnp.int32)
                return jnp.concatenate([out, jnp.stack([fed, jnp.int32(7)])]), kp, vp

            return ragged_program

        def count_aux(aux, live_tokens, kernels):
            assert int(aux[0]) == live_tokens
            return ({"made_up_fed": int(aux[0]), "made_up_sevens": int(aux[1])},
                    {"made_up": f"{int(aux[0])}/{kernels['state']}"})

        spec = dataclasses.replace(
            base, family="made-up", program=program, aux_shape=(2,), count_aux=count_aux,
            kernels=lambda platform, mesh_devices: {
                "walk": "", "state": "made_up_step", "where": f"{platform}-{mesh_devices}"})
        be = ServingBackend(spec, num_pages=37, page_size=4, max_seqs=4, max_batch_tokens=8,
                            max_context=64)
    bus, spans = LoopbackBus(), []

    async def on_span(subject, pkt):
        spans.append(pkt.span)

    await bus.subscribe(subj.TRACE_SPAN, on_span)
    eng = ServingEngine(be, run_blocking=run_blocking, tracer=Tracer("worker", bus),
                        max_new_tokens_cap=16)
    eng.worker_id = "w-made-up"
    out = await eng.submit(GenRequest(prompt=list(range(10, 23)), max_new_tokens=5, stream=False),
                           job_id="a", trace_id="tr-a", parent_span_id="ex-a")
    assert len(out["tokens"]) == 5
    await eng.stop()
    await bus.drain()
    st = eng.stats
    # 13 prompt tokens and the four sampled ones that were fed back
    assert st.model == {"made_up_fed": 13 + 4, "made_up_sevens": 7 * st.steps}
    assert st.model["a name nobody counted"] == 0
    steps = [s for s in spans if s.name == "step"]
    assert len(steps) == st.steps
    assert sum(int(s.attrs["made_up"].split("/")[0]) for s in steps) == 13 + 4
    assert {s.attrs["made_up"].split("/")[1] for s in steps} == {"made_up_step"}
    if kind == "fake":
        # a backend that counts no blocks says nothing of a walk
        assert all("walk_kernel" not in s.attrs for s in steps)
        return
    assert {s.attrs["walk_kernel"] for s in steps} == {"none"}
    assert be.kernels == {"walk": "", "state": "made_up_step", "where": "cpu-1"}
    phase = [p for p in startup.phases() if p.name == "startup.kernels"]
    assert len(phase) == 1
    assert phase[0].attrs == {"walk": "none", "state": "made_up_step", "where": "cpu-1"}
    assert [p.name for p in startup.phases() if p.id == phase[0].parent] == ["startup.state"]


# a chunk of 20 at position 37, decode rows at 5, 63 and 90, a draft row of 1 + 3 at 48
WALK_ROWS = [(37, 20), (5, 1), (63, 1), (90, 1), (48, 4)]


@pytest.mark.parametrize("tile_slots, block_tokens, window, own_ends, want", [
    # what the backend's own method reported for this step before the count
    # left it for ``attention.count_walk`` (run on the tree of PR 44 with these shapes):
    # (longest walk over whole rows, over rings, (table rows gathered, query
    # slots computed), fed slots that needed their block); ``own_ends`` is one flag a
    # kind of page since ISSUE 47 (PR 44's one flag was the whole-row kind's)
    (8, (16,), None, (False,), (6, 0, (48, 384), 96)),
    (8, (16,), None, (True,), (6, 0, (26, 208), 96)),
    (4, (32,), None, (False,), (3, 0, (32, 128), 54)),
    (4, (32,), None, (True,), (3, 0, (18, 72), 54)),
    (8, (16, 8), 24, (False, False), (6, 5, (88, 704), 197)),
    (8, (16, 8), 24, (True, False), (6, 5, (66, 528), 197)),
    # the rings by each tile's own first and last block (ISSUE 47: their walk is the kernel),
    # counted by hand: the chunk's tiles 5 + 5 + 5 blocks of 8, the decode rows 1, 3 and 4,
    # the draft row 4: 27 beside the whole rows' 26; in tiles of four 35 beside 18
    (8, (16, 8), 24, (True, True), (6, 5, (53, 424), 197)),
    (4, (32, 8), 24, (True, True), (3, 5, (53, 212), 155)),
])
def test_the_hosts_count_of_a_mixed_step_is_what_it_was(tile_slots, block_tokens, window,
                                                        own_ends, want):
    import numpy as np

    from cordum_tpu.models import attention

    spans, positions, lo = [], np.zeros(32, np.int32), 0
    for start, n in WALK_ROWS:
        positions[lo:lo + n] = np.arange(start, start + n)
        spans.append((lo, lo + n))
        lo += n
    got = attention.count_walk(np.array(spans), positions, tile_slots, block_tokens, window,
                               own_ends)
    assert got == want
    assert all(type(n) is int for n in (got[0], got[1], *got[2], got[3]))


@pytest.mark.parametrize("kind", ["llama", "fake"])
async def test_a_kept_cycle_splits_its_wait_where_the_backend_says(kind):
    """Over the backend the chip runs, a kept cycle's ``wait.fetch`` starts at
    the backend's own ``last_ready_ns`` and ends with ``step.wait``; over the
    fake, which does not say, there is none.  ``emit.wake`` needs no word of
    the backend.  Either way the trace holds seven ``step*`` spans."""
    from .test_serving import SpanSink, traced_engine

    sink = await SpanSink().listen()
    be = build(kind)
    eng = traced_engine(sink, be, max_new_tokens_cap=16)
    ready = []
    closed = eng._cycle_closed

    def recording(n_step, marks, attrs):
        ready.append(be.last_ready_ns)
        closed(n_step, marks, attrs)

    eng._cycle_closed = recording
    for job in ("a", "b"):  # the first closes the start-up record; both cycles 0 are kept
        await eng.submit(GenRequest(prompt=[5, 9, 2], max_new_tokens=3, stream=False), job_id=job)
        eng._kept_cycle_ns = 0
    await eng.stop()
    await sink.bus.drain()
    cycle = {s.name: s for s in sink.spans if s.trace_id == "step-w-t-3"}
    assert sum(n == "step" or n.startswith("step.") for n in cycle) == 7
    wait, emit = cycle["step.wait"], cycle["step.emit"]
    assert emit.start_us == cycle["emit.wake"].start_us <= cycle["emit.wake"].end_us <= emit.end_us
    if kind == "fake":
        assert ready == [None] * 6 and "wait.fetch" not in cycle
    else:
        fetch = cycle["wait.fetch"]
        assert fetch.start_us == ready[3] // 1000 and fetch.parent_span_id == wait.span_id
        assert wait.start_us <= fetch.start_us <= fetch.end_us == wait.end_us
