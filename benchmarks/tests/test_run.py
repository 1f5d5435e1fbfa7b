"""Drive a whole run past the look for a chip (``--rehearse``: tiny widths,
same control flow, same child process over HTTP) and see ``correct`` true on
the sound program and FALSE with the timed path broken underneath: a token
altered where it is produced, and a token delivered twice."""
import argparse
import asyncio

from benchmarks import run as bench_run
from benchmarks.harness import cells


def drive(workload, seed=2 ** 31 + 3, trace=0, seconds=3.0, bench=None):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=trace,
                              rehearse=True, rate=0.0, control=0)
    return asyncio.run(bench_run.run_cell(args, cells.resolve(workload, bench)))


def with_closed_cell():
    """``BENCHMARK.json`` plus the closed-loop cell that PERF.md keeps for a
    later PR: one ``workloads`` entry over ``traffic/chat-closed.json``, and
    nothing else, is what adding it takes."""
    bench = cells.load_benchmark()
    bench["workloads"] = bench["workloads"] + [{
        "name": "mistral7b-batch-closed", "config": "mistral-7b-v0.3",
        "traffic": "chat-closed", "chips": 1, "why": "closed loop after a ramp"}]
    return bench


def test_sound_run_is_correct_and_shaped_to_the_contract():
    out = drive("internlm2-toolcalls-open", trace=1)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["device"]["platform"] == "cpu"  # a rehearsal names its device
    for name in ("gateway_submit_ms", "sched_dispatch_ms", "worker_intake_ms",
                 "engine_ttft_ms", "batch_occupancy", "live_token_share", "kv_pool_held_share",
                 "step_wall_ms"):
        assert out["metrics"][name]["value"] > 0, name
    # nothing ran on a device here, so no device number is written
    for name in ("ragged_step_device_ms", "step_roofline_share", "device_idle_share",
                 "hbm_peak_gb"):
        assert name not in out["metrics"]


def test_a_cell_that_does_not_judge_the_median_ttft_reads_it_per_layer():
    """``mistral7b-chat-open`` reports no ``ttft_p50_ms`` end to end (its runs
    spread wider than half of the largest bound); the traced run reads the
    same number as ``client_ttft_p50_ms``."""
    cell = cells.resolve("mistral7b-chat-open")
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p95_ms", "tpot_p95_ms", "tokens_per_s", "setup_s"}
    assert "client_ttft_p50_ms" not in cells.resolve("internlm2-toolcalls-open").readers
    out = drive("mistral7b-chat-open", trace=1, seconds=6.0)
    assert out["correct"] is True and out["attempted"] > 0
    assert out["metrics"]["client_ttft_p50_ms"]["value"] > 0
    out = drive("mistral7b-chat-open", trace=0, seconds=6.0)
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "tokens_per_s", "setup_s"}


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    from cordum_tpu.serving.backend import LlamaServingBackend

    real = LlamaServingBackend.step
    calls = {"n": 0}

    def broken(self, entries):
        res = real(self, entries)
        calls["n"] += 1
        if calls["n"] % 40 == 0:
            res = [(r + 1) % 256 if isinstance(r, int) else r for r in res]
        return res

    monkeypatch.setattr(LlamaServingBackend, "step", broken)
    out = drive("mistral7b-batch-closed", bench=with_closed_cell())
    assert calls["n"] >= 40 and out["correct"] is False
    # a saturated closed loop reports no TTFT end to end: its window opens after a ramp
    assert set(out["metrics"]) == {"tpot_p95_ms", "tokens_per_s", "setup_s"}


def test_a_closed_loop_opens_its_window_after_the_ramp():
    out = drive("mistral7b-batch-closed", bench=with_closed_cell())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["setup_s"]["value"] > 24.0  # the ramp counts as set-up
    assert out["metrics"]["tokens_per_s"]["value"] > 0


def test_a_repeat_or_a_gap_in_the_stream_is_caught():
    """The child's own accounting of the tap: a packet that repeats an
    offset, or skips one, marks the request, and the run is then incorrect."""
    from benchmarks.harness import loadgen, reference

    run = loadgen.Run(client=None, cmd={"loop": "open", "t0": 0.0, "window_s": 1.0,
                                        "requests": []}, tag="t")
    rec = {"i": 0, "job_id": "j", "first": None, "last": None, "done": None, "n_tokens": 0,
           "tokens": [], "packets": [], "dups": 0, "gaps": 0, "want": 5, "state": "SUCCEEDED",
           "stream_equals_result": True}
    run.recs["j"] = rec

    def stream(offset, tokens):
        run.on_packet({"kind": "job_progress", "payload": {
            "job_id": "j", "status_hint": "stream", "offset": offset, "tokens": tokens}})

    stream(0, [5, 6])
    stream(2, [7])
    assert rec["tokens"] == [5, 6, 7] and not rec["dups"] and not rec["gaps"]
    assert rec["first"] is not None and [n for _, n in rec["packets"]] == [2, 1]
    stream(1, [6, 7])            # a replayed offset: both tokens are repeats
    assert rec["tokens"] == [5, 6, 7] and rec["dups"] == 2
    stream(5, [9])               # offsets 3 and 4 never came
    assert rec["gaps"] == 1 and rec["tokens"] == [5, 6, 7]
    rec["n_tokens"] = 5
    assert [f["i"] for f in reference.stream_faults([rec])] == [0]
