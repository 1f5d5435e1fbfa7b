"""The ``afmoe`` family's cell: it resolves through files found by name, its
configuration keeps to the model-configs guide's rule for ``reduced`` (depth,
dense layers, layer kinds, context, experts held, vocabulary; never a width),
its readers return nothing on a run without their counters, the family's
roofline count equals a hand count, and a rehearsal reaches its last line."""
import argparse
import asyncio
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.families import afmoe as fam
from benchmarks.harness import cells, roofline_afmoe

BENCH = cells.load_benchmark()
CELL = "trinity-large-mixedlen-open"
NEW_READERS = ("moe_here_share", "moe_experts_touched_share", "moe_load_imbalance",
               "kv_window_held_share", "window_blocks_share", "afmoe_step_roofline_share",
               "moe_experts_roofline_share")
#: what a cut to one chip may change (guide, section 4); every other key is a width or a rule
MAY_BE_REDUCED = {"num_hidden_layers", "num_dense_layers", "layer_types",
                  "max_position_embeddings", "num_experts", "vocab_size"}
WIDTHS = {"hidden_size": 3072, "intermediate_size": 12288, "moe_intermediate_size": 3072,
          "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
          "num_experts_per_tok": 4, "num_shared_experts": 1, "sliding_window": 4096,
          "route_scale": 2.448, "rope_theta": 10000, "num_experts_routed": 256}


def test_the_cell_resolves_and_reports_what_the_contract_asks():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "afmoe"
    cell.family.validate(dict(cell.config))
    # TTFT is not judged here: the driver's check found it too noisy for its bound (PERF.md section 2)
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"setup_s", "tokens_per_s", "tpot_p95_ms"}
    assert {m["moves"] for m in cell.per_layer} <= reported
    assert set(cell.readers) == {m["name"] for m in cell.per_layer} >= set(NEW_READERS)
    assert [w["chips"] for w in BENCH["workloads"]] == [1, 1, 1]
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]


def test_reduced_names_cuts_of_scale_and_never_a_width():
    entry = next(c for c in BENCH["configs"] if c["name"] == "trinity-large-preview-ep8")
    doc = json.load(open(os.path.join(cells.REPO_ROOT, entry["file"])))
    assert doc["reduced"] == entry["reduced"] and doc["source"] == entry["source"]
    assert set(doc["reduced"]) <= MAY_BE_REDUCED
    for key in doc["reduced"]:
        assert doc[key] != doc["source_values"][key] and key in doc["reduced_why"]
    for key, value in WIDTHS.items():
        assert doc[key] == value and key not in doc["reduced"], key
    # the floors of a cut: a whole period after the dense layer, 8+ experts, an eighth of the vocabulary
    assert doc["layer_types"][doc["num_dense_layers"]:] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert doc["num_experts"] >= 8 and doc["vocab_size"] * 8 >= doc["source_values"]["vocab_size"]
    assert abs(fam.n_params(dict(doc)) / 1e9 - 4.32) < 0.01
    assert {"gap_mean_limit", "gap_max_limit", "derivation"} <= set(doc["check"])


def test_rehearsal_widths_settle_into_a_consistent_tiny_model():
    doc = dict(cells.load_config("trinity-large-preview-ep8"))
    doc.update(bench_run.TINY)
    cfg = fam.program_config(doc)
    assert cfg.layer_types == ("sliding_attention", "full_attention") and cfg.n_dense_layers == 1
    assert (cfg.d_model, cfg.d_expert, cfg.window, cfg.n_experts, cfg.experts_held) == (
        64, 32, 64, 256, 32)
    assert doc["layer_types"] == list(cfg.layer_types)  # the reference reads the same file


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_without_its_counters(name):
    del fam.STEPS[:]
    run = {"config": {}, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.0, "t1": 2.0},
           "trace": {"module_runs_s": {"jit_ragged_program": [0.01]}, "device_ops": []},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    assert cells.load_reader(name).read(run) is None
    assert cells.load_reader(name).read({**run, "slice": {}, "trace": {}, "peaks": None}) is None


def test_readers_read_the_noted_steps():
    # two expert layers of four held experts: loads [0, 3, 0, 0] and [2, 0, 0, 1]
    counters = {"moe_assignments": 12 * 4 * 2, "moe_assignments_here": 6,
                "moe_experts_touched": 3, "moe_max_expert_load": 3 + 2}
    del fam.STEPS[:]
    fam.STEPS.append({"at": 1.5, "rows": [(10, 0, 1), (2, 50, 1)], "counters": counters,
                      "window_blocks": 2, "full_blocks": 8, "window_pages": 30, "full_pages": 120})
    fam.STEPS.append({"at": 99.0, "rows": [(1, 0, 1)], "counters": counters, "window_blocks": 1,
                      "full_blocks": 1, "window_pages": 1, "full_pages": 1})  # after the window
    run = {"config": {"num_hidden_layers": 3, "num_dense_layers": 1, "num_experts": 4},
           "t0": 0.0, "window_s": 10.0}
    read = lambda name: cells.load_reader(name).read(run)  # noqa: E731
    assert read("moe_here_share") == pytest.approx(100.0 * 6 / (12 * 4 * 2))
    assert read("moe_experts_touched_share") == pytest.approx(100.0 * 3 / 8)
    assert read("moe_load_imbalance") == pytest.approx((3 + 2) / (0.75 + 0.75))
    assert read("kv_window_held_share") == pytest.approx(25.0)
    assert read("window_blocks_share") == pytest.approx(25.0)
    del fam.STEPS[:]


TINY_DOC = {"hidden_size": 8, "intermediate_size": 16, "moe_intermediate_size": 4,
            "num_hidden_layers": 3, "num_dense_layers": 1,
            "layer_types": ["sliding_attention", "sliding_attention", "full_attention"],
            "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 32,
            "sliding_window": 6, "num_experts_routed": 16, "num_shared_experts": 1}


def test_roofline_counts_equal_a_hand_count():
    doc = TINY_DOC
    attn = 2 * 8 * 8 + 2 * 8 * 4 + 8 * 8  # wq and gate, wk and wv, wo
    expert = 3 * 8 * 4
    unrouted = 3 * attn + 3 * 8 * 16 + 2 * (8 * 16 + expert)
    assert roofline_afmoe.attn_params(doc) == attn and roofline_afmoe.expert_params(doc) == expert
    assert roofline_afmoe.unrouted_params(doc) == unrouted
    # a row of 3 tokens fed from position 7 (sees 8, 9, 10 keys whole; 6 each under the
    # window), one position sampled; a decode row at position 2
    rows = [(3, 7, 1), (1, 2, 1)]
    assert roofline_afmoe.seen_positions(doc, 3, 7, "full_attention") == 27
    assert roofline_afmoe.seen_positions(doc, 3, 7, "sliding_attention") == 18
    assert roofline_afmoe.cached_positions(doc, 3, 7, "full_attention") == 10
    assert roofline_afmoe.cached_positions(doc, 3, 7, "sliding_attention") == 8  # keys 2..9
    counters = {"moe_assignments_here": 7, "moe_experts_touched": 3}
    seen = 2 * (18 + 3) + (27 + 3)
    flops = 2 * unrouted * 4 + 2 * expert * 7 + 4 * 2 * 4 * seen + 2 * 8 * 32 * 2
    assert roofline_afmoe.step_flops(doc, rows, 7) == flops
    kv_read = (2 * (8 + 3) + (10 + 3)) * 2 * 4 * 2
    nbytes = (unrouted + 3 * expert) * 2 + 8 * 32 * 2 + 4 * 8 * 2 + kv_read + 4 * 2 * 4 * 3 * 2
    assert roofline_afmoe.step_bytes(doc, rows, 3) == nbytes
    peaks = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e9}
    assert roofline_afmoe.step_least_seconds(doc, rows, counters, peaks) == (flops / 1e3, "flops")
    assert roofline_afmoe.experts_flops(doc, 7) == 2 * expert * 7
    assert roofline_afmoe.experts_bytes(doc, 7, 3) == 3 * expert * 2 + 7 * (2 * 8 * 2 + 3 * 4 * 2 + 8 * 4)
    peaks = {"bf16_flops": 1e15, "hbm_bytes_per_s": 1.0}
    assert roofline_afmoe.experts_least_seconds(doc, counters, peaks)[1] == "bandwidth"


def test_the_grouped_products_are_found_by_their_recorded_name():
    """Operation names as a v5e trace of the cell gave them (recorded, reduced
    by ``trace_reduce.op_kind``): the reader's prefix finds the products."""
    from benchmarks.layer_metrics import moe_experts_roofline_share as reader

    ops = json.load(open(os.path.join(os.path.dirname(__file__), "data", "afmoe_device_ops.json")))
    found = [name for name, _ in ops["device_ops"] if name.startswith(reader.OP_PREFIX)]
    assert found and reader.products_seconds({"trace": ops}) > 0


def test_a_rehearsal_of_the_new_cell_reaches_its_last_line():
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 26, seconds=4.0, trace=1,
                              rehearse=True, rate=1.5, control=0)
    out = asyncio.run(bench_run.run_cell(args, cells.resolve(CELL)))
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["failed"] == 0 and out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    for name in ("moe_here_share", "moe_experts_touched_share", "moe_load_imbalance",
                 "kv_window_held_share", "window_blocks_share", "step_cycle_ms"):
        assert out["metrics"][name]["value"] > 0, name
    for name in ("afmoe_step_roofline_share", "moe_experts_roofline_share", "hbm_peak_gb"):
        assert name not in out["metrics"]  # nothing ran on a device here
