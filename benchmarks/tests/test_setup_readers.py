"""The seven readers of the start-up record (``cordum_tpu/obs/startup.py``),
each on a hand-made record, and the contract's entries for them."""
import pytest

from benchmarks.harness import cells

S = 1_000_000_000  # a second, in the record's nanoseconds


def row(id_, name, start_s, end_s, parent=0, **attrs):
    return {"id": id_, "name": name, "start_ns": int(start_s * S), "end_ns": int(end_s * S),
            "parent": parent, "attrs": attrs}


def a_record():
    """A start-up of 20 s: compute 3 (embedder 2.9), backend 0.1, then 6 s
    of nothing (the wait for a first request), state 0.5, the ragged program
    7.4 (trace 2 + lower 1 + load 4, 0.4 between them), first step 1.5, a
    page copy program of 0.5 (all of it its load), and 1 s more before the
    first sampled token."""
    return [
        row(2, "startup.embedder", 0.1, 3.0, parent=1),
        row(1, "startup.compute", 0.0, 3.0),
        row(3, "startup.backend", 3.0, 3.1),
        row(5, "startup.weights", 9.1, 9.2, parent=4),
        row(6, "startup.arenas", 9.2, 9.6, parent=4, bytes=1 << 30),
        row(4, "startup.state", 9.1, 9.6, programs=2, cache_hits=2),
        row(8, "startup.program.trace", 9.7, 11.7, parent=7),
        row(9, "startup.program.lower", 11.8, 12.8, parent=7),
        row(10, "startup.program.load", 13.0, 17.0, parent=7),
        row(7, "startup.program", 9.6, 17.0, entry="ragged", programs=1, cache_hits=1),
        row(11, "startup.first_step", 17.0, 18.5),
        row(13, "startup.program.load", 18.5, 19.0, parent=12),
        row(12, "startup.program", 18.5, 19.0, entry="copy_page", programs=1, cache_hits=0),
        row(0, "startup", 0.0, 20.0, parent=-1, worker_id="bench-w1", programs=4, cache_hits=3,
            waiting_ms=7000.0),
    ]


WANT = {
    "setup_compute_s": 3.0,
    "setup_state_s": 0.5,
    "setup_trace_lower_s": 3.0,
    "setup_load_s": 4.5,
    "setup_first_step_s": 1.5,
    "setup_serving_s": 13.0,  # 3 + 0.1 + 0.5 + 7.4 + 1.5 + 0.5: the root's 20 less 7 of waiting
    "setup_cache_hit_share": 75.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_reader_on_a_hand_made_record(name, monkeypatch):
    reader = cells.load_reader(name)
    assert reader.read({"startup": a_record()}) == pytest.approx(WANT[name])
    # a record that never closed (no first token), and a program without one
    assert reader.read({"startup": a_record()[:-1]}) is None
    assert reader.read({"startup": []}) is None
    import sys

    import cordum_tpu.obs

    # the parent's tree: no such module
    monkeypatch.delattr(cordum_tpu.obs, "startup", raising=False)
    monkeypatch.setitem(sys.modules, "cordum_tpu.obs.startup", None)
    assert reader.read({}) is None


def test_the_phases_sum_to_the_programs_share():
    """No phase counted twice, none left out: the five metrics and the
    ``startup.backend`` phase miss ``setup_serving_s`` only by what lies
    between a program's trace, lowering and load."""
    run = {"startup": a_record()}
    parts = sum(cells.load_reader(n).read(run) for n in WANT if n.endswith("_s")
                and n != "setup_serving_s") + 0.1
    assert cells.load_reader("setup_serving_s").read(run) - parts == pytest.approx(0.4)


def test_the_readers_take_the_programs_own_record():
    from cordum_tpu.obs import startup

    startup.reset()
    try:
        with startup.phase("startup.compute"):
            pass
        assert cells.load_reader("setup_compute_s").read({}) is None  # still open
        ev = startup.ProgramEvents(spans=[("load", 10, 20, "jit_f")], hits=1)
        startup.program("ragged", 5, ev, ran_until_ns=30)
        startup.close(40, worker_id="w")
        assert cells.load_reader("setup_load_s").read({}) == pytest.approx(1e-8)
        assert cells.load_reader("setup_cache_hit_share").read({}) == 100.0
    finally:
        startup.reset()


def test_contract_entries():
    bench = cells.load_benchmark()
    names = [w["name"] for w in bench["workloads"]]
    tail = bench["per_layer"][-7:]
    assert sorted(m["name"] for m in tail) == sorted(WANT)
    for m in tail:
        reader = cells.load_reader(m["name"])
        assert m["moves"] == reader.MOVES == "setup_s" and m["source"] == reader.SOURCE
        assert (m["unit"], m["better"], m["layer"]) == (reader.UNIT, reader.BETTER, reader.LAYER)
        assert m["workloads"] == names[:5]
        assert (m["better"] == "higher") == (m["name"] == "setup_cache_hit_share")
