"""The start-up trace (``obs/startup.py``; docs/OBSERVABILITY.md §Serving
spans and metrics): the tiny configuration through the real ``TPUCompute``,
``ServingBackend`` and engine of ``attach_default_tpu_worker``."""
import asyncio
import time

import jax
import pytest

from cordum_tpu.infra.bus import LoopbackBus
from cordum_tpu.infra.kv import MemoryKV
from cordum_tpu.infra.memstore import MemoryStore
from cordum_tpu.infra.metrics import Metrics
from cordum_tpu.obs import startup
from cordum_tpu.protocol import subjects as subj
from cordum_tpu.serving.engine import GenRequest
from cordum_tpu.worker.handlers import attach_default_tpu_worker
from cordum_tpu.worker.runtime import Worker

from .fakes import FakeBackend

TOP = ["startup.compute", "startup.backend", "startup.state", "startup.program",
       "startup.first_step"]
CHILDREN = {"startup.embedder": "startup.compute", "startup.weights": "startup.state",
            "startup.arenas": "startup.state", "startup.kernels": "startup.state",
            "startup.program.trace": "startup.program",
            "startup.program.lower": "startup.program", "startup.program.load": "startup.program"}


class Served:
    """One worker as ``cmd/worker.py`` wires it (tiny llama, page size 16),
    with a listener on ``sys.trace.span`` that keeps what it hears.  A pool
    of ``pages`` that no other test asks for: a program this process has
    compiled before is in jit's own cache, and JAX says nothing of it."""

    def __init__(self, pages):
        self.pages = pages
        self.bus = LoopbackBus()
        self.spans = []
        self.metrics = Metrics()
        self.worker = Worker(bus=self.bus, store=MemoryStore(MemoryKV()), worker_id="w-s",
                             pool="tpu", topics=["job.tpu.>"], capabilities=["tpu"])

    async def __aenter__(self):
        async def on_span(subject, pkt):
            self.spans.append(pkt.span)

        await self.bus.subscribe(subj.TRACE_SPAN, on_span)
        # a program of the test's own, outside any backend call: nobody's
        jax.jit(lambda x: x * 2 + 1)(3.0).block_until_ready()
        attach_default_tpu_worker(self.worker, metrics=self.metrics, batching=False, gang=False,
                                  serving_speculative=False, serving_max_new_tokens=16,
                                  serving_cache_pages=self.pages)
        await self.worker.start()
        self.engine = self.worker.serving
        return self

    async def __aexit__(self, *exc):
        await self.worker.stop()
        await self.bus.drain()

    async def generate(self, prompt, job_id, new=4):
        return await asyncio.wait_for(self.engine.submit(
            GenRequest(prompt=prompt, max_new_tokens=new, stream=False), job_id=job_id),
            timeout=240)

    async def heard(self):
        """Everything published so far (spans go out behind a step or when
        the loop parks)."""
        await asyncio.sleep(0.05)
        await self.bus.drain()
        return self.spans

    def compile_events(self):
        return sum(self.compiles(e)
                   for e in ("state", "ragged", "copy_page", "gather_page", "scatter_page"))

    def compiles(self, entry):
        """The compile counter's series of this backend (a llama program:
        neither kernel) for ``entry``."""
        return self.metrics.serving_compiles.value(
            entry=entry, walk_kernel="none", expert_kernel="none")


async def test_record_holds_every_phase_once_and_closes_with_the_first_token():
    async with Served(pages=101) as s:
        assert [p.name for p in startup.phases()] == [
            "startup.embedder", "startup.compute", "startup.backend"]
        stepped = []  # when each step's results were unpacked, and whether it sampled
        s.engine.backend.on_step = lambda entries: stepped.append(
            (time.time_ns(), any(e.sample for e in entries)))
        await s.generate(list(range(1, 40)), "j1")  # more than one chunk, then decode
        rows = startup.phases()
        names = [p.name for p in rows]
        for name in (*TOP, *CHILDREN):
            assert names.count(name) == 1, (name, names)
        assert names[-1] == "startup" and names.count("startup") == 1
        by_name = {p.name: p for p in rows}
        root = by_name["startup"]
        assert root.id == 0 and root.parent == -1
        for name in TOP:
            assert by_name[name].parent == 0
        for child, parent in CHILDREN.items():
            c, p = by_name[child], by_name[parent]
            assert c.parent == p.id
            assert p.start_ns <= c.start_ns <= c.end_ns <= p.end_ns, child
        for p in rows[:-1]:
            assert root.start_ns <= p.start_ns <= p.end_ns <= root.end_ns, p.name
        # the order the work happens in, nothing inside anything else
        for a, b in zip(TOP, TOP[1:]):
            assert by_name[a].end_ns <= by_name[b].start_ns, (a, b)
        # closed by the first cycle that returned a sampled token: not the
        # first step (a chunk that samples nothing), and not the last
        first = [sampled for _, sampled in stepped].index(True)
        assert 0 < first < len(stepped) - 1
        assert stepped[first][0] < root.end_ns < stepped[first + 1][0]
        assert by_name["startup.arenas"].attrs["bytes"] > 0
        prog = by_name["startup.program"].attrs
        assert prog["entry"] == "ragged" and "ragged_program" in prog["fun"]
        assert prog["programs"] == 1 and prog["cache_hit"] == "false"  # no cache on the CPU
        # the test's own jit is in no count: the weights', the arenas' and the step's are
        assert root.attrs["programs"] == by_name["startup.state"].attrs["programs"] + 1
        assert root.attrs["programs"] == s.compile_events()
        assert root.attrs["worker_id"] == "w-s" and root.attrs["waiting_ms"] >= 0
        covered = startup.covered_ns(rows)
        assert covered == sum(by_name[n].end_ns - by_name[n].start_ns for n in TOP)
        assert abs((root.end_ns - root.start_ns - covered) / 1e6 - root.attrs["waiting_ms"]) < 1e-3


async def test_published_once_as_a_trace_of_its_own_with_the_gauge():
    async with Served(pages=103) as s:
        await s.generate(list(range(1, 20)), "j1")
        await s.generate(list(range(3, 30)), "j2")
        mine = [sp for sp in await s.heard() if sp.trace_id == "startup-w-s"]
        rows = startup.phases()
        assert [sp.name for sp in mine] == [p.name for p in rows]  # children first, the root last
        root = mine[-1]
        assert root.name == "startup" and root.parent_span_id == ""
        ids = {sp.span_id: sp for sp in mine}
        assert len(ids) == len(mine)
        for sp, ph in zip(mine[:-1], rows):
            assert sp.parent_span_id in ids and sp.service == "worker"
            assert ids[sp.parent_span_id].name == (
                CHILDREN.get(sp.name, "startup")), sp.name
            assert (sp.start_us, sp.end_us) == (ph.start_ns // 1000, ph.end_ns // 1000)
        assert root.attrs["worker_id"] == "w-s" and int(root.attrs["programs"]) >= 1
        assert {"entry", "fun", "cache_hit"} <= set(
            next(sp for sp in mine if sp.name == "startup.program").attrs)
        g = s.metrics.startup_phase
        for ph in rows:
            assert g.value(phase=ph.name) == pytest.approx(ph.seconds)
        assert g.value(phase="startup") >= g.value(phase="startup.state") > 0


async def test_step_reads_compiled_from_the_compilers_own_events():
    async with Served(pages=107) as s:
        prompt = list(range(1, 33))  # exactly two pages
        out1 = await s.generate(prompt, "a")
        assert s.compiles("ragged") == 1
        assert s.compiles("copy_page") == 0
        # the same prompt again hits both pages and re-feeds its last token,
        # which writes into a shared page: the first copy-on-write, and so
        # the first use of the page copy program, inside that cycle
        n_before, closed = s.engine.stats.steps, startup.phases()
        out2 = await s.generate(prompt, "b")
        assert out2["tokens"] == out1["tokens"] and s.engine.stats.cow_copies >= 1
        assert startup.phases() == closed  # a later program is its step's, not the record's
        steps = {int(sp.trace_id.rsplit("-", 1)[1]): sp
                 for sp in await s.heard() if sp.name == "step"}
        first = steps[0].attrs
        assert first["compiled"] == "true" and float(first["compile_ms"]) > 0
        assert first["cache_hit"] == "false"
        assert steps[1].attrs["compiled"] == "false"
        assert "compile_ms" not in steps[1].attrs and "cache_hit" not in steps[1].attrs
        again = steps[n_before].attrs  # always kept, whatever the sampling period says
        assert again["compiled"] == "true" and float(again["compile_ms"]) > 0
        assert [n for n, sp in steps.items() if sp.attrs["compiled"] == "true"] == [0, n_before]
        assert s.compiles("copy_page") == 1
        assert s.compiles("ragged") == 1
        assert s.engine.backend.compiled_programs() == 1
        assert not s.engine.backend.last_step_compiled


async def test_an_engine_over_a_stand_in_closes_nothing():
    from cordum_tpu.serving.engine import ServingEngine

    with startup.phase("startup.compute"):
        pass

    async def run_blocking(fn, *a):
        return await asyncio.get_running_loop().run_in_executor(None, fn, *a)

    eng = ServingEngine(FakeBackend(), run_blocking=run_blocking, metrics=Metrics())
    await eng.submit(GenRequest(prompt=[1, 2, 3], max_new_tokens=3, stream=False), job_id="f")
    await eng.stop()
    assert [p.name for p in startup.phases()] == ["startup.compute"]  # still open
    assert startup.close(1) is None


def test_reset_bound_and_an_inert_closed_record():
    with startup.phase("startup.state", note="x") as attrs:
        attrs["bytes"] = 7
        with startup.phase("startup.weights"):
            pass
    ev = startup.ProgramEvents(spans=[
        ("trace", 100, 400, "inner"), ("trace", 50, 500, "outer"),  # nested: once
        ("lower", 500, 600, "jit_f"), ("load", 600, 900, "jit_f")], hits=1)
    assert ev.of("trace") == [(50, 500)] and ev.compiles == 1 and ev.compile_ns == 300
    startup.program("ragged", 40, ev, ran_until_ns=1000)
    rows = startup.phases()
    assert [(p.name, p.end_ns - p.start_ns) for p in rows[2:]] == [
        ("startup.program.trace", 450), ("startup.program.lower", 100),
        ("startup.program.load", 300), ("startup.program", 860), ("startup.first_step", 100)]
    assert rows[1].attrs == {"note": "x", "bytes": 7} and rows[0].parent == rows[1].id
    assert rows[5].attrs["cache_hit"] == "true"
    closed = startup.close(2000, worker_id="w")
    assert closed[-1].name == "startup" and closed[-1].attrs["cache_hits"] == 1
    assert startup.close(3000) is None
    with startup.phase("startup.compute"):  # closed: inert
        pass
    startup.program("copy_page", 0, ev)
    assert len(startup.phases()) == len(closed)
    startup.reset()
    assert startup.phases() == []
    for _ in range(startup.PHASE_LIMIT + 10):
        with startup.phase("startup.compute"):
            pass
    assert len(startup.phases()) == startup.PHASE_LIMIT
