"""The ten readers of the serving loop's own spans (``serving.queue``,
``serving.prefill`` and the sampled ``step`` traces), each on a hand-made
``run["spans"]``, and the contract's entries for them."""
import os

import pytest

from benchmarks.harness import cells

WORKLOADS = ["mistral7b-chat-open", "internlm2-toolcalls-open"]
NEW = ["engine_queue_ms", "engine_queue_p95_ms", "engine_prefill_ms", "engine_prefill_p95_ms",
       "step_cycle_ms", "step_assemble_ms", "step_feed_ms", "step_wait_ms", "step_emit_ms",
       "step_host_share"]
PHASES = ("assemble", "pack", "dispatch", "wait", "unpack", "emit")


def span(name, trace, start_us, dur_us):
    return {"name": name, "trace": trace, "start_us": start_us, "end_us": start_us + dur_us,
            "service": "worker", "at": 0.0}


def cycle(n, start_us, durs_us):
    """One kept cycle: six contiguous children and the root, as the engine
    publishes them (children first)."""
    out, at = [], start_us
    for phase, d in zip(PHASES, durs_us):
        out.append(span(f"step.{phase}", f"step-w-{n}", at, d))
        at += d
    return out + [span("step", f"step-w-{n}", start_us, sum(durs_us))]


def a_run():
    spans = []
    # five requests: queue 1, 2, 3, 4, 100 ms; prefill 10, 20, 30, 40, 500 ms
    for i, (q, p) in enumerate(zip((1, 2, 3, 4, 100), (10, 20, 30, 40, 500))):
        spans += [span("submit", f"tr-{i}", 0, 250), span("execute", f"tr-{i}", 300, 900_000),
                  span("serving.queue", f"tr-{i}", 400, q * 1000),
                  span("serving.prefill", f"tr-{i}", 400 + q * 1000, p * 1000)]
    # three kept cycles (assemble, pack, dispatch, wait, unpack, emit), the last stalled
    spans += cycle(0, 10_000, (300, 100, 400, 22_000, 200, 1_000))
    spans += cycle(9, 260_000, (500, 100, 600, 22_400, 200, 1_200))
    spans += cycle(11, 300_000, (400, 200, 400, 2_000_000, 100, 900))
    # a cycle of which one child fell outside the window: its root still counts
    # as a cycle, its parts are left out of the per-cycle sums
    spans += cycle(20, 900_000, (300, 100, 400, 21_000, 200, 1_000))[1:]
    return {"spans": spans}


def read(name, run):
    return cells.load_reader(name).read(run)


def test_each_reader_on_a_hand_made_run():
    run = a_run()
    assert read("engine_queue_ms", run) == 3.0
    assert read("engine_queue_p95_ms", run) == pytest.approx(4 + 0.8 * 96)
    assert read("engine_prefill_ms", run) == 30.0
    assert read("engine_prefill_p95_ms", run) == pytest.approx(40 + 0.8 * 460)
    assert read("step_cycle_ms", run) == pytest.approx(24.5)  # of 23, 24, 25, 2002
    assert read("step_assemble_ms", run) == pytest.approx(0.4)  # of 0.3, 0.5, 0.4; the cut cycle lost this child
    assert read("step_feed_ms", run) == pytest.approx(0.6)  # whole cycles only: 0.5, 0.7, 0.6
    assert read("step_wait_ms", run) == pytest.approx((22.0 + 22.4) / 2)
    assert read("step_emit_ms", run) == pytest.approx(1.2)  # 1.2, 1.4, 1.0
    host = (2_000 + 2_600 + 2_000)
    assert read("step_host_share", run) == pytest.approx(100 * host / (24_000 + 25_000 + 2_002_000))


def test_the_stalled_cycle_counts_in_the_share_and_not_in_the_medians():
    run = a_run()
    calm = {"spans": [s for s in run["spans"] if s["trace"] != "step-w-11"]}
    assert read("step_wait_ms", calm) == pytest.approx(22.0)  # 21, 22, 22.4
    assert read("step_host_share", calm) > 10 * read("step_host_share", run)


@pytest.mark.parametrize("name", NEW)
def test_a_run_without_the_spans_reads_nothing(name):
    """The parent commit publishes none of these spans (its ``decode-step``
    is another name): every reader returns None and the metric is left out."""
    parent = {"spans": [span("submit", "tr-0", 0, 250), span("execute", "tr-0", 300, 9_000),
                        span("decode-step", "tr-0", 400, 26_000)]}
    assert read(name, parent) is None
    assert read(name, {"spans": []}) is None


def test_every_new_entry_has_its_reader_and_its_cells():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW  # appended, in order
    for name in NEW:
        entry = by_name[name]
        assert os.path.isfile(os.path.join(cells.BENCH_DIR, "layer_metrics", f"{name}.py"))
        assert entry["workloads"] == WORKLOADS and entry["source"] == "program_span"
        mod = cells.load_reader(name)
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"])
        # each cell reports the end-to-end metric the entry names
        for w in WORKLOADS:
            assert entry["moves"] in {m["name"] for m in cells.resolve(w).end_to_end}
