"""``models/expert_mlp.py``: the grouped expert products of one expert layer
as one Pallas TPU kernel (ISSUE 41; docs/SERVING.md §The expert layer).

On the CPU the step programs hold the ``jax.lax.ragged_dot`` form (the kernel
is chosen where a program is lowered for the TPU), so these tests call the
kernel themselves, or steer ``jax.lax.platform_dependent`` to its ``tpu``
branch IN THE TEST, and run it in Pallas' TPU interpret mode.  Each case holds
the kernel to the ``ragged_dot`` form and to a plain float32 product over the
same ``counts``; the rows behind the last group are NaN for the kernel alone,
so a finite, equal result shows that nothing of them reaches a visited row."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from cordum_tpu.models import afmoe, axk1, bailing, expert_mlp, longcat

A, ITEM = expert_mlp.ALIGN, expert_mlp.ITEM_ROWS

#: counts a held expert; the operand has ``rows`` rows (the sum, or more)
CASES = {
    "every-group-empty": dict(counts=[0] * 8, rows=32),
    "one-row-an-expert": dict(counts=[1] * 8, rows=32),
    "one-expert-with-every-row": dict(counts=[0, 0, 0, 48, 0, 0], rows=48),
    "the-first-and-the-last-expert-alone": dict(counts=[3, 0, 0, 0, 0, 2], rows=32),
    # groups that begin and end inside a 16-row piece and straddle pieces and chunks
    "groups-that-straddle-pieces": dict(counts=[5, 0, 13, 1, 0, 17, 30, 2], rows=80),
    "a-group-that-ends-on-a-boundary": dict(counts=[16, 0, 16, 7], rows=48),
    # a span beyond ITEM_ROWS: several items of one expert, the next group behind them
    "a-fat-group-of-several-items": dict(counts=[3, 0, ITEM + 70, 9], rows=ITEM + 96),
    "rows-that-are-no-whole-piece": dict(counts=[2, 4, 0, 1], rows=9),
    "float32": dict(counts=[2, 0, 21, 3], rows=32, dtype=jnp.float32, tol=2e-5),
    # three blocks of the expert width: the accumulator runs over them
    "blocks-of-the-expert-width": dict(counts=[1, 19, 0, 4], rows=32, fe=384, budget=2 ** 19),
}


def operands(counts, rows, d=128, fe=256, dtype=jnp.bfloat16, seed=0):
    held = len(counts)
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    mat = lambda key, shape, fan: (  # noqa: E731
        jax.random.normal(key, shape, jnp.float32) / fan ** 0.5).astype(dtype)
    return (mat(k[0], (rows, d), 1.0), mat(k[1], (held, d, fe), d), mat(k[2], (held, d, fe), d),
            mat(k[3], (held, fe, d), fe), jnp.asarray(counts, jnp.int32))


def plain(xs, e_gate, e_up, e_down, counts):
    """float32, a group after the other."""
    xs, e_gate, e_up, e_down = (np.asarray(a, np.float32) for a in (xs, e_gate, e_up, e_down))
    out, at = np.zeros(xs.shape, np.float32), 0
    for e, n in enumerate(np.asarray(counts)):
        x = xs[at:at + n]
        g = x @ e_gate[e]
        out[at:at + n] = (g / (1 + np.exp(-g)) * (x @ e_up[e])) @ e_down[e]
        at += n
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_equals_the_ragged_form_and_a_plain_reference(case, monkeypatch):
    spec = dict(CASES[case])
    counts, tol = spec["counts"], spec.get("tol", 4e-2)
    if "budget" in spec:  # a budget these tiny experts do not fit whole
        monkeypatch.setattr(expert_mlp, "VMEM_BUDGET_BYTES", spec["budget"])
    xs, e_gate, e_up, e_down, c = operands(
        counts, spec["rows"], fe=spec.get("fe", 256), dtype=spec.get("dtype", jnp.bfloat16))
    if "budget" in spec:
        assert expert_mlp.block_width(128, spec["fe"], 2) == 128
    n = sum(counts)
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(expert_mlp.expert_mlp(xs.at[n:].set(jnp.nan), e_gate, e_up, e_down, c))
    assert got.shape == xs.shape and got.dtype == np.float32
    assert np.isfinite(got[:n]).all()
    want = np.asarray(afmoe.ragged_products(xs, e_gate, e_up, e_down, c))
    np.testing.assert_allclose(got[:n], want[:n], atol=tol, rtol=tol)
    np.testing.assert_allclose(got[:n], plain(xs, e_gate, e_up, e_down, c)[:n], atol=tol, rtol=tol)


def test_the_intermediate_is_no_coarser_than_the_ragged_form():
    """bfloat16 operands: the kernel rounds ``silu(gate) * up`` once, the
    ``ragged_dot`` form ``gate``, ``up`` and their product: the kernel lies
    closer to the float32 product of the same bfloat16 operands."""
    xs, e_gate, e_up, e_down, c = operands([9, 0, 14, 9], 32, seed=5)
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(expert_mlp.expert_mlp(xs, e_gate, e_up, e_down, c))
    ragged = np.asarray(afmoe.ragged_products(xs, e_gate, e_up, e_down, c))
    ref = plain(xs, e_gate, e_up, e_down, c)
    assert np.abs(got - ref).mean() < np.abs(ragged - ref).mean()


@pytest.mark.parametrize("counts", [
    [0] * 12, [1] * 12, [0, 0, 7, 0, 1, 0, 0, 0, 30, 0, 0, 2], [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 64],
    [15, 1, 0, 16, 17, 0, 0, 3, 0, 0, 0, 0], [0, 2 * ITEM + 1, 0, 5, 0, 0, 0, 0, 0, 0, 0, 0],
    [A - 1, ITEM, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0]])
def test_the_work_list_visits_the_touched_experts_and_nothing_else(counts):
    """An item a touched expert (more for a span beyond ``ITEM_ROWS``), in the
    experts' order; the items cover exactly the groups' rows; the host's count
    is the kernel's; the static grid holds them."""
    rows = -(-max(sum(counts), 1) // A) * A
    c = np.asarray(counts, np.int32)
    e, lo, hi, total = (np.asarray(a) for a in expert_mlp.work_list(jnp.asarray(c), rows))
    total = int(total[0])
    per = expert_mlp.item_counts(c)
    assert total == per.sum() == np.asarray(expert_mlp.item_counts(jnp.asarray(c))).sum()
    assert ((per > 0) == (c > 0)).all() and total <= expert_mlp.max_items(len(counts), rows) == len(e)
    assert sorted(set(e[:total])) == list(np.flatnonzero(c))  # no untouched expert
    assert (np.diff(e[:total]) >= 0).all()
    starts = np.cumsum(c) - c
    covered = np.zeros(rows, int)
    for i in range(total):
        assert starts[e[i]] <= lo[i] < hi[i] <= starts[e[i]] + c[e[i]]
        assert hi[i] - lo[i] // A * A <= ITEM  # an item's span from its boundary
        covered[lo[i]:hi[i]] += 1
    assert (covered[:c.sum()] == 1).all() and not covered[c.sum():].any()
    if total:  # the items behind the last repeat it: no block index moves
        assert (e[total:] == e[total - 1]).all() and (lo[total:] == lo[total - 1]).all()
    if (c <= ITEM - A + 1).all():
        assert total == (c > 0).sum()  # thin groups: one item each


def test_the_rule_that_chooses_the_kernel_is_the_platform_and_shapes_alone(monkeypatch):
    assert expert_mlp.holds_kernel("tpu", 2560, 768, 2)
    assert not expert_mlp.holds_kernel("cpu", 2560, 768, 2)
    assert not expert_mlp.holds_kernel("gpu", 7168, 2048, 2)
    # the block follows d, the dtype and the budget: the four sparse cells' experts
    widths = {(2560, 768): 384, (7168, 2048): 128, (6144, 2048): 128, (3072, 3072): 384}
    for (d, fe), f in widths.items():
        assert expert_mlp.block_width(d, fe, 2) == f
        assert 2 * 3 * d * f * 2 <= expert_mlp.VMEM_BUDGET_BYTES // 2
        assert expert_mlp.vmem_bytes(d, f, 2) <= expert_mlp.VMEM_BUDGET_BYTES
    assert expert_mlp.block_width(6144, 2048, 4) == 0  # float32 weights: half the columns, so none
    # experts no block of which fits are the ragged form's on every platform
    assert expert_mlp.block_width(2 ** 17, 2048, 2) == 0
    assert not expert_mlp.holds_kernel("tpu", 2 ** 17, 2048, 2)
    xs, e_gate, e_up, e_down, c = operands([1, 2], 16, d=64, fe=32)
    seen = []
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, default, **by: seen.append(sorted(by)) or default(*args))
    afmoe.grouped_products(xs, e_gate, e_up, e_down, c)
    assert seen == [["tpu"]]  # both forms are handed to the lowering; no other input decides
    monkeypatch.setattr(expert_mlp, "VMEM_BUDGET_BYTES", 1024)
    afmoe.grouped_products(xs, e_gate, e_up, e_down, c)
    assert seen == [["tpu"]]  # nothing fits: the ragged form alone, no choice left to make


@pytest.fixture
def kernel_products(monkeypatch):
    """``platform_dependent`` takes the branch ``take`` names (the kernel's,
    interpreted; ``"default"`` for the form the CPU runs)."""
    take = ["tpu"]
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, default, tpu: {"tpu": tpu, "default": default}[take[0]](*args))
    with pltpu.force_tpu_interpret_mode():
        yield take


FAMILIES = {
    "afmoe": (afmoe, lambda: afmoe.AfmoeConfig(first_expert=4, experts_held=8)),
    "axk1": (axk1, lambda: axk1.Axk1Config(first_expert=8, experts_held=8)),
    "longcat": (longcat, lambda: longcat.LongcatConfig(first_expert=0, experts_held=8)),
    "bailing": (bailing, lambda: bailing.BailingConfig(first_expert=0, experts_held=16)),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_each_familys_expert_layer_is_the_same_under_both_forms(family, kernel_products):
    """``afmoe.expert_layer`` over each family's tiny config (its own router
    settings, part of the experts held here, padding slots that route
    nowhere): the layer's output and counts under the kernel are those under
    the ``ragged_dot`` form."""
    module, make = FAMILIES[family]
    cfg = make()
    params = module.init_params(jax.random.PRNGKey(7), cfg)
    layer = next(lay for lay in params["layers"] if "e_gate" in lay)
    t = 24
    m = jax.random.normal(jax.random.PRNGKey(8), (t, cfg.d_model), jnp.float32)
    live = jnp.arange(t) < t - 5
    got, n_got = afmoe.expert_layer(m, layer, cfg, live)
    kernel_products[0] = "default"
    want, n_want = afmoe.expert_layer(m, layer, cfg, live)
    assert np.asarray(n_got).tolist() == np.asarray(n_want).tolist()
    assert 0 < int(n_want[:cfg.experts_held].sum()) < t * cfg.top_k  # some picks here, some not
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-2, rtol=3e-2)


def test_a_program_without_an_expert_layer_never_imports_the_module():
    import inspect

    from cordum_tpu.models import llama

    assert "expert_mlp" not in inspect.getsource(llama)


async def test_the_counter_says_how_the_products_engage(monkeypatch):
    """``StepBackend.kernels``' ``expert`` role, the ``step`` span's
    ``expert_kernel`` and ``moe_items``, ``ServingStats.model``'s
    ``moe_kernel_items``, the label on ``cordum_serving_compile_total`` and the
    ``startup.kernels`` phase:
    ``none`` / 0 on the CPU (the arenas' platform holds ``ragged_dot``), and
    under a backend that reports the kernel (as one on the TPU does) the items
    the kernel's own rule makes of each step's counts."""
    import asyncio

    from cordum_tpu.infra.bus import LoopbackBus
    from cordum_tpu.infra.metrics import Metrics
    from cordum_tpu.obs import startup
    from cordum_tpu.obs.tracer import Tracer
    from cordum_tpu.protocol import subjects as subj
    from cordum_tpu.serving import engine as engine_mod
    from cordum_tpu.serving.backend import ServingBackend, StepBackend
    from cordum_tpu.serving.engine import GenRequest, ServingEngine

    assert StepBackend.kernels == {} and not hasattr(StepBackend, "expert_kernel")
    monkeypatch.setattr(engine_mod, "STEP_SAMPLE_PERIOD_NS", 0)  # every cycle a ``step`` trace
    cfg = afmoe.AfmoeConfig(first_expert=4, experts_held=8)
    metrics, bus, spans = Metrics(), LoopbackBus(), []

    async def on_span(subject, pkt):
        spans.append(pkt.span)

    await bus.subscribe(subj.TRACE_SPAN, on_span)
    be = ServingBackend(cfg, num_pages=83, page_size=8, max_seqs=3, max_batch_tokens=3 + 9,
                        params=afmoe.init_params(jax.random.PRNGKey(7), cfg), metrics=metrics)

    async def run_blocking(fn, *args):
        return await asyncio.get_running_loop().run_in_executor(None, fn, *args)

    eng = ServingEngine(be, run_blocking=run_blocking, tracer=Tracer("worker", bus),
                        max_sessions=3, max_new_tokens_cap=16)
    eng.worker_id = "w-e"
    seen = []  # per step: the items the kernel's rule makes of the step's counts
    inner = be.step

    def tapped(entries):
        out = inner(entries)
        seen.append(int(expert_mlp.item_counts(be.last_aux[:, :cfg.experts_held]).sum()))
        return out

    be.step = tapped

    async def generate(job):
        return await asyncio.wait_for(eng.submit(
            GenRequest(prompt=list(range(1, 20)), max_new_tokens=4, stream=False), job_id=job,
            trace_id=f"tr-{job}", parent_span_id=f"ex-{job}"), timeout=240)

    await generate("a")
    assert be.kernels["expert"] == "" and eng.stats.model["moe_kernel_items"] == 0 and sum(seen) > 0
    assert "moe_kernel_items" not in be.last_counters  # the key is the kernel's: absent without it
    assert metrics.serving_compiles.value(
        entry="ragged", walk_kernel="none", expert_kernel="none") == 1
    phase = [p for p in startup.phases() if p.name == "startup.kernels"]
    assert len(phase) == 1 and phase[0].attrs == {"walk": "none", "ring": "none", "expert": "none"}
    assert [p.name for p in startup.phases() if p.id == phase[0].parent] == ["startup.state"]
    n_cpu = len(seen)
    # as a backend whose arenas live on the TPU reports, by the specification's own rule
    be.kernels = {**be.kernels, "expert": be.spec.kernels(expert_mlp.PLATFORM, 1)["expert"]}
    assert be.kernels["expert"] == expert_mlp.KERNEL_NAME
    await generate("b")
    await eng.stop()
    await bus.drain()
    assert eng.stats.model["moe_kernel_items"] == sum(seen[n_cpu:]) > 0
    steps = sorted((s for s in spans if s.name == "step"), key=lambda s: s.start_us)
    assert steps and all({"expert_kernel", "moe_items", "moe_touched"} <= set(s.attrs) for s in steps)
    cpu = [s for s in steps if s.attrs["expert_kernel"] == "none"]
    tpu = [s for s in steps if s.attrs["expert_kernel"] == expert_mlp.KERNEL_NAME]
    assert cpu and tpu and len(cpu) + len(tpu) == len(steps)
    assert all(s.attrs["moe_items"] == "0" for s in cpu)
    # at these sizes every group is thin: an item a touched expert
    assert all(s.attrs["moe_items"] == s.attrs["moe_touched"] != "0" for s in tpu)


def test_the_roofline_reader_reads_either_form_and_nothing_else():
    """``benchmarks/layer_metrics/moe_grouped_roofline_share.py``: over a trace
    with ``ragged-dot*`` operations it reads what ``moe_experts_roofline_share``
    reads (the parent's side of a comparison), over one with ``expert_mlp*``
    the same count over the kernel's seconds, and nothing without either."""
    from benchmarks.families import afmoe as fam
    from benchmarks.layer_metrics import moe_experts_roofline_share as old
    from benchmarks.layer_metrics import moe_grouped_roofline_share as new

    doc = {"hidden_size": 2560, "moe_intermediate_size": 768}
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    steps = [{"at": 10.0 + i, "counters": {"moe_assignments_here": 20 + i, "moe_experts_touched": 17}}
             for i in range(3)]
    saved = fam.STEPS[:]
    fam.STEPS[:] = steps
    try:
        def run(ops):
            return {"config": doc, "peaks": peaks, "slice": {"t0": 9.0, "t1": 20.0},
                    "trace": {"device_ops": ops, "module_runs_s": {"jit_ragged_program": [0.009] * 3}}}

        ragged = run([["ragged-dot-none bf16[1024,768]", 0.002], ["fusion f32[64]", 0.001],
                      ["ragged-dot-none f32[1024,2560]", 0.001]])
        assert new.read(ragged) == pytest.approx(old.read(ragged)) and 0 < new.read(ragged) < 100
        kernel = run([["expert_mlp f32[1024,2560]", 0.0012], ["fusion f32[64]", 0.001]])
        assert old.read(kernel) is None  # the accepted reader falls silent where the kernel took over
        assert new.read(kernel) == pytest.approx(new.read(ragged) * 0.003 / 0.0012)
        assert new.read(run([["fusion f32[64]", 0.001]])) is None
        assert new.read({**kernel, "peaks": None}) is None  # a rehearsal: no device, no share
    finally:
        fam.STEPS[:] = saved
    assert (new.LAYER, new.UNIT, new.BETTER, new.SOURCE, new.MOVES) == (
        "model programs", "%", "higher", "device_trace", "tpot_p95_ms")
