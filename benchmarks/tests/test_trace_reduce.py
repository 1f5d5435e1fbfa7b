"""The reduction from a profiler trace to numbers, on hand-made intervals and
on a small RECORDED trace: ``data/ragged_slice.xplane.pb`` is the device plane
of a real chip trace (PR 23, first chip call: mistral7b-chat-open on a TPU
v5e), cut to its first seven program executions."""
import os

import pytest

from benchmarks.harness import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data", "ragged_slice.xplane.pb")


def test_union_and_gaps_of_intervals():
    ivals = [(0, 10), (5, 12), (20, 30), (30, 31), (40, 41)]
    assert tr.union_ns(ivals) == 12 + 11 + 1
    assert tr.gaps_ns(ivals) == [(12, 8), (31, 9)]
    assert tr.union_ns([]) == 0.0 and tr.gaps_ns([(0, 1)]) == []


def test_operation_names_reduce_to_kind_and_result_shape():
    assert tr.op_kind(
        "%broadcast_in_dim.436 = bf16[64,2048,8,4,128]{4,3,2,1,0:T(4,128)(2,1)} "
        "broadcast(bf16[64,2048,8,128]{3,2,1,0} %bitcast.26), dimensions={0,1,2,4}"
    ) == "broadcast_in_dim bf16[64,2048,8,4,128]"
    assert tr.op_kind("%copy-start = (bf16[4096,14336]{1,0:T(8,128)(2,1)S(1)}, "
                      "bf16[4096,14336]{1,0}, u32[]{:") == "copy-start bf16[4096,14336]"
    assert tr.op_kind("%fusion.7") == "fusion"
    assert tr.module_base("jit_ragged_program(2430862326933979176)") == "jit_ragged_program"


def test_reduce_planes_on_a_hand_made_device():
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [("x", 0.0, 5e9)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_step(1)", 0.0, 100.0), ("jit_step(1)", 150.0, 100.0)]},
            {"name": "XLA Ops", "events": [("%a.1 = f32[2]{0} add()", 0.0, 60.0),
                                           ("%a.2 = f32[2]{0} add()", 70.0, 30.0),
                                           ("%b = f32[4]{0} mul()", 150.0, 100.0)]}]},
    ]
    out = tr.reduce_planes(planes)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(190e-9) and out["span_s"] == pytest.approx(250e-9)
    assert out["device_ops"] == [["b f32[4]", pytest.approx(100e-9)], ["a f32[2]", pytest.approx(90e-9)]]
    assert out["module_runs_s"] == {"jit_step": [pytest.approx(100e-9)] * 2}
    assert out["between_modules_s"] == [pytest.approx(50e-9)]
    assert out["inside_modules_idle_s"] == pytest.approx(10e-9)
    assert tr.reduce_planes(planes[:1]) == {"devices": 0}


def test_recorded_chip_trace():
    out = tr.reduce_file(RECORDED)
    assert out["devices"] == 1
    ragged = out["module_runs_s"]["jit_ragged_program"]
    assert len(ragged) >= 2 and all(0.14 < d < 0.17 for d in ragged)  # 157 ms a step that day
    assert 0 < out["busy_s"] <= out["span_s"]
    assert out["busy_s"] / out["span_s"] > 0.9          # the device was busy nearly throughout
    top, seconds = out["device_ops"][0]
    assert top == "broadcast_in_dim bf16[64,2048,8,4,128]"  # K and V repeated to 32 heads
    assert seconds > 0.4 * out["busy_s"]
    assert len(out["device_ops"]) <= 10


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert tr.find_xplane(str(tmp_path)).endswith("host.xplane.pb")


def test_idle_share_and_breakdown_stand_on_the_trace_own_span():
    """Busy time and the window it is divided by come from the same events:
    nothing is cut off, and a longer or shorter host-side slice changes
    neither."""
    from benchmarks import run as bench_run
    from benchmarks.layer_metrics import device_idle_share

    out = tr.reduce_file(RECORDED)
    share = device_idle_share.read({"trace": out, "slice": {"t0": 0.0, "t1": 0.001}})
    assert share == pytest.approx(100.0 * (1 - out["busy_s"] / out["span_s"]))
    assert 0.0 < share < 10.0
    assert device_idle_share.read({"trace": {"devices": 0}}) is None

    got = bench_run.Collected()
    got.step_calls = [(10.0 + 0.2 * i, 10.0 + 0.2 * i + 0.17) for i in range(5)]
    bd = bench_run.breakdown(out, got, {"t0": 10.0, "t1": 11.0})
    gaps = dict((name.split(":")[0].split(" (")[0], s) for name, s in bd["idle_gaps"])
    assert gaps["device idle between programs"] == pytest.approx(sum(out["between_modules_s"]))
    n = len(out["module_runs_s"]["jit_ragged_program"])
    assert gaps["host between backend.step calls"] == pytest.approx(n * 0.03)
    assert len(bd["idle_gaps"]) <= 10 and len(bd["device_ops"]) <= 10
    # no step call seen inside the slice: the trace's own lines remain
    assert len(bench_run.breakdown(out, bench_run.Collected(), {"t0": 0.0, "t1": 1.0})["idle_gaps"]) >= 2
