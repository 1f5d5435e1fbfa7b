"""The nine readers of the serving loop's idle time and of what lies inside a
step cycle's phases (``serving.parked``, ``serving.poll``, ``wait.fetch``,
``emit.wake``, ``runtime.gc``, ``serving.first_packet``; ISSUE 51), each on a
hand-made ``run["spans"]``, the 0.0 / None rule, and the contract's entries."""
import os

import pytest

from benchmarks.harness import cells

ALL = [w["name"] for w in cells.load_benchmark()["workloads"]][:9]
LLAMA = ["mistral7b-chat-open", "internlm2-toolcalls-open"]
NEW = {"engine_parked_share": ALL, "engine_poll_share": ALL, "step_ready_ms": ALL,
       "step_fetch_ms": ALL, "loop_wake_ms": ALL, "gc_pause_max_ms": ALL, "gc_pause_share": ALL,
       "first_token_return_ms": LLAMA, "first_token_return_p95_ms": LLAMA}
PHASES = ("assemble", "pack", "dispatch", "wait", "unpack", "emit")
#: the wall clock's microseconds at the window's opening, and the monotonic clock's seconds
WALL0, MONO0 = 1_700_000_000_000_000, 5_000.0


def span(name, trace, start_us, dur_us, late_us=400):
    """A span ``start_us`` into the window, arriving ``late_us`` after its end."""
    return {"name": name, "trace": trace, "start_us": WALL0 + start_us,
            "end_us": WALL0 + start_us + dur_us, "service": "worker",
            "at": MONO0 + (start_us + dur_us + late_us) / 1e6}


def cycle(n, start_us, durs_us, fetch_us=None, wake_us=None, gc=()):
    """One kept cycle as the engine publishes it: six contiguous children,
    what lies inside ``wait`` and ``emit``, the collector's pauses, the root."""
    out, at, trace = [], start_us, f"step-w-{n}"
    for phase, d in zip(PHASES, durs_us):
        out.append(span(f"step.{phase}", trace, at, d))
        if phase == "wait" and fetch_us is not None:
            out.append(span("wait.fetch", trace, at + d - fetch_us, fetch_us))
        if phase == "emit" and wake_us is not None:
            out.append(span("emit.wake", trace, at, wake_us))
        at += d
    out += [span("runtime.gc", trace, start_us + off, d) for off, d in gc]
    return out + [span("step", trace, start_us, sum(durs_us))]


def a_run():
    """A window of 10 s: three kept cycles, one of them held by a collection
    of 90 ms; a park that began 2 s before the window opened and ended 1 s
    into it, another of 3 s inside it; one poll of 0.5 s; a short collection
    on the second park's trace; five first packets."""
    spans = cycle(7, 1_000_000, (300, 100, 400, 8_000, 200, 1_000), fetch_us=500, wake_us=150)
    spans += cycle(9, 1_300_000, (500, 100, 600, 8_400, 200, 1_200), fetch_us=700, wake_us=250)
    spans += cycle(11, 1_600_000, (400, 200, 400, 98_000, 100, 900), fetch_us=600, wake_us=200,
                   gc=[(2_000, 90_000)])
    # a cycle that lost a child to the window's edge counts nowhere
    spans += cycle(20, 9_900_000, (300, 100, 400, 9_000, 200, 1_000), fetch_us=5_000, wake_us=900)[1:]
    spans += [span("serving.parked", "loop-w-0", -2_000_000, 3_000_000),
              span("serving.parked", "loop-w-40", 4_000_000, 3_000_000),
              span("runtime.gc", "loop-w-40", 5_000_000, 2_500),
              span("serving.poll", "loop-w-60", 8_000_000, 500_000)]
    for i, d in enumerate((1, 2, 3, 4, 50)):
        spans += [span("serving.prefill", f"tr-{i}", 1_000 * i, 20_000),
                  span("serving.first_packet", f"tr-{i}", 1_000 * i + 20_000, d * 1000)]
    return {"spans": spans, "t0": MONO0 + 400 / 1e6, "window_s": 10.0}


def read(name, run):
    return cells.load_reader(name).read(run)


def test_each_reader_on_a_hand_made_run():
    run = a_run()
    assert read("engine_parked_share", run) == pytest.approx(100 * (1.0 + 3.0) / 10.0, abs=1e-4)
    assert read("engine_poll_share", run) == pytest.approx(5.0, abs=1e-4)
    assert read("step_ready_ms", run) == pytest.approx(7.7)  # of 7.5, 7.7, 97.4
    assert read("step_fetch_ms", run) == pytest.approx(0.6)  # of 0.5, 0.7, 0.6
    assert read("loop_wake_ms", run) == pytest.approx(0.2)  # of 0.15, 0.25, 0.2
    assert read("gc_pause_max_ms", run) == pytest.approx(90.0)
    assert read("gc_pause_share", run) == pytest.approx(100 * 0.0925 / 10.0)
    assert read("first_token_return_ms", run) == pytest.approx(3.0)
    assert read("first_token_return_p95_ms", run) == pytest.approx(4 + 0.8 * 46)


def test_a_park_is_cut_to_the_window_on_the_spans_own_clock():
    """The clocks' distance is the smallest arrival less end: with every span
    arriving later than that the cut moves with it, never past the window."""
    run = a_run()
    for s in run["spans"]:
        s["at"] += 0.05  # a bus 50 ms slower: the opening is read 50 ms early
    assert read("engine_parked_share", run) == pytest.approx(100 * (1.05 + 3.0) / 10.0, abs=1e-4)
    run = a_run()
    run["spans"] = [s for s in run["spans"] if s["trace"] != "loop-w-0"]
    assert read("engine_parked_share", run) == pytest.approx(30.0, abs=1e-4)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_zero_and_none_rule(name):
    """A program that stamps its idle time and had none of it in the window
    reads 0.0 on the shares and the longest pause (a busy cell never parks, a
    quiet window has no long pause); a program without the stamps (the parent:
    ``step`` spans and no ``emit.wake``) reads None on all nine."""
    busy = {"spans": cycle(7, 1_000_000, (300, 100, 400, 8_000, 200, 1_000),
                           fetch_us=500, wake_us=150), "t0": MONO0, "window_s": 10.0}
    zero = {"engine_parked_share", "engine_poll_share", "gc_pause_max_ms", "gc_pause_share"}
    if name in zero:
        assert read(name, busy) == 0.0 and type(read(name, busy)) is float
    elif name.startswith("first_token"):
        assert read(name, busy) is None  # no request's first packet in it
    else:
        assert read(name, busy) > 0
    parent = {"spans": cycle(7, 1_000_000, (300, 100, 400, 8_000, 200, 1_000))
              + [span("serving.queue", "tr-0", 0, 250), span("serving.prefill", "tr-0", 250, 9_000)],
              "t0": MONO0, "window_s": 10.0}
    assert read(name, parent) is None
    assert read(name, {"spans": [], "t0": MONO0, "window_s": 10.0}) is None


def test_the_accepted_step_readers_still_keep_every_cycle():
    """No new span is a ``step*``: ``step_cycle_ms.cycles`` keeps the three
    whole cycles, and the phase medians read as they did without them."""
    from benchmarks.layer_metrics import step_cycle_ms

    run = a_run()
    bare = {"spans": [s for s in run["spans"] if s["name"] == "step" or s["name"].startswith("step.")]}
    assert len(step_cycle_ms.cycles(run)) == len(step_cycle_ms.cycles(bare)) == 3
    for name in ("step_cycle_ms", "step_wait_ms", "step_emit_ms", "step_assemble_ms",
                 "step_feed_ms", "step_host_share"):
        assert read(name, run) == read(name, bare)


def test_every_new_entry_has_its_reader_and_its_cells():
    bench = cells.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("engine_parked_share")
    assert names[at:at + len(NEW)] == list(NEW)  # appended together, in this order
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, workloads in NEW.items():
        entry = by_name[name]
        assert os.path.isfile(os.path.join(cells.BENCH_DIR, "layer_metrics", f"{name}.py"))
        assert entry["workloads"] == workloads and entry["source"] == "program_span"
        mod = cells.load_reader(name)
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"])
        for w in workloads:  # each cell reports the end-to-end metric the entry names
            assert entry["moves"] in {m["name"] for m in cells.resolve(w).end_to_end}
