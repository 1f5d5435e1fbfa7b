"""The ``axk1`` family's cell: it resolves through files found by name, its
configuration keeps to the model-configs guide's rule for ``reduced`` (depth,
experts held, vocabulary, context; never a width), its readers return nothing
on a run without their counters, the family's roofline count equals a hand
count, and a rehearsal with a second turn in it reaches its last line."""
import argparse
import asyncio
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.families import axk1 as fam
from benchmarks.harness import cells, roofline_mla

BENCH = cells.load_benchmark()
CELL = "axk1-docsessions-open"
CONFIG = "a.x-k1-ep16"
NEW_READERS = ("prefix_hit_token_share", "walk_live_slot_share", "mla_walk_busy_share",
               "mla_walk_roofline_share", "mla_step_roofline_share")
SHARED_READERS = ("moe_here_share", "moe_experts_touched_share", "moe_load_imbalance",
                  "moe_experts_roofline_share", "step_cycle_ms", "step_host_share")
#: what a cut to one chip may change (guide, section 4); every other key is a width or a rule
MAY_BE_REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"}
WIDTHS = {"hidden_size": 7168, "intermediate_size": 18432, "moe_intermediate_size": 2048,
          "num_attention_heads": 64, "num_key_value_heads": 64, "q_lora_rank": 1536,
          "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
          "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4, "n_shared_experts": 1,
          "routed_scaling_factor": 2.5, "rope_theta": 10000, "num_experts_routed": 192,
          "first_k_dense_replace": 1}


def test_the_cell_resolves_and_reports_what_the_contract_asks():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "axk1"
    cell.family.validate(dict(cell.config))
    reported = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "tokens_per_s", "tpot_p95_ms"} <= reported
    assert "ttft_p95_ms" not in reported  # PR 26: a few dozen heavy-tailed prompts
    assert {m["moves"] for m in cell.per_layer} <= reported
    assert set(cell.readers) == {m["name"] for m in cell.per_layer} >= set(NEW_READERS + SHARED_READERS)
    assert [w["chips"] for w in BENCH["workloads"]] == [1, 1, 1, 1]
    assert BENCH["workloads"][-1]["name"] == CELL and BENCH["configs"][-1]["name"] == CONFIG
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
    # no reader of rings or of the afmoe family's own count is asked of this cell
    assert not {"kv_window_held_share", "window_blocks_share",
                "afmoe_step_roofline_share"} & set(cell.readers)


def test_reduced_names_cuts_of_scale_and_never_a_width():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    doc = json.load(open(os.path.join(cells.REPO_ROOT, entry["file"])))
    assert doc["reduced"] == entry["reduced"] and doc["source"] == entry["source"]
    assert set(doc["reduced"]) == MAY_BE_REDUCED
    for key in doc["reduced"]:
        assert doc[key] != doc["source_values"][key] and key in doc["reduced_why"]
    for key, value in WIDTHS.items():
        assert doc[key] == value and key not in doc["reduced"], key
    assert doc["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
                                   "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                                   "type": "yarn"}
    # the floors of a cut: four expert layers after the dense one, 8+ experts, an eighth of the vocabulary
    assert doc["num_hidden_layers"] - doc["first_k_dense_replace"] >= 4
    assert doc["n_routed_experts"] >= 8 and doc["vocab_size"] * 8 >= doc["source_values"]["vocab_size"]
    assert doc["num_experts"] == doc["n_routed_experts"] and doc["num_dense_layers"] == 1
    assert doc["head_dim"] == doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
    assert abs(fam.n_params(dict(doc)) / 1e9 - 4.166) < 0.001
    assert {"gap_mean_limit", "gap_max_limit", "derivation"} <= set(doc["check"])
    assert {"topk_method", "rope_pairing", "yarn", "norms"} <= set(doc["assumed"])
    pool = doc["pool"]
    assert pool["prefix_cache"] is True and pool["speculative"] is False
    assert pool["pages"] == pool["max_sessions"] * doc["max_position_embeddings"] // pool["page_size"]


def test_the_traffic_is_sessions_whose_schedule_does_not_move_with_the_seed():
    from benchmarks.harness import traffic

    tr = cells.load_traffic("docsessions-open")
    assert tr["sessions"]["turns"] == [3, 5] and tr["sessions"]["think_s"] == [2.0, 2.0]
    kw = dict(seconds=51, vocab=20480, context=32768, max_new_cap=384)
    a, b = traffic.generate(tr, seed=1, **kw), traffic.generate(tr, seed=2 ** 31 + 5, **kw)
    shape = lambda rs: [(r["session"], r["turn"], len(r["tokens"]), r["max_new_tokens"],  # noqa: E731
                         r.get("due_s"), r.get("think_s")) for r in rs]
    assert shape(a) == shape(b) and [r["tokens"] for r in a] != [r["tokens"] for r in b]
    turns = [sum(1 for r in a if r["session"] == s) for s in {r["session"] for r in a}]
    assert min(turns) >= 3 and max(turns) <= 5
    assert all(64 <= len(r["tokens"]) <= 12288 and 16 <= r["max_new_tokens"] <= 384 for r in a)


def test_rehearsal_widths_settle_into_a_consistent_tiny_model():
    doc = dict(cells.load_config(CONFIG))
    doc.update(bench_run.TINY)
    cfg = fam.program_config(doc)
    assert (cfg.d_model, cfg.n_heads, cfg.q_rank, cfg.kv_rank, cfg.nope_dim, cfg.rope_dim,
            cfg.v_dim, cfg.d_expert) == (64, 4, 32, 32, 16, 8, 16, 32)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_experts, cfg.experts_held, cfg.n_group,
            cfg.topk_group, cfg.top_k) == (2, 1, 192, 12, 8, 4, 8)
    assert doc["kv_lora_rank"] == 32  # the reference reads the same file
    full = fam.program_config(dict(cells.load_config(CONFIG)))
    assert (full.latent_dim, full.latent_width, full.n_kv_heads) == (576, 640, 1)
    assert abs(full.softmax_scale - 0.13086) < 1e-5


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_without_its_counters(name):
    del fam.STEPS[:]
    run = {"config": {}, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.0, "t1": 2.0},
           "trace": {"module_runs_s": {"jit_ragged_program": [0.01]}, "busy_s": 1.0,
                     "device_ops": [["while s32[]", 0.5]]},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    assert cells.load_reader(name).read(run) is None
    assert cells.load_reader(name).read({**run, "slice": {}, "trace": {}, "peaks": None}) is None
    # another family's noted steps (no key of this family's) are nothing to read either
    fam.STEPS.append({"at": 1.5, "rows": [(4, 0, 1)], "counters": {"moe_assignments": 1},
                      "window_blocks": 1, "full_blocks": 1, "window_pages": 1, "full_pages": 1})
    assert cells.load_reader(name).read(run) is None
    del fam.STEPS[:]


def noted(at, rows, **kw):
    base = {"at": at, "rows": rows,
            "counters": {"moe_assignments": 80, "moe_assignments_here": 5,
                         "moe_experts_touched": 4, "moe_max_expert_load": 2},
            "window_blocks": 0, "full_blocks": 3, "window_pages": 0, "full_pages": 9,
            "slots_computed": 64, "slots_live": 16, "prefix_hit_tokens": 0, "prefill_tokens": 0,
            "prefix_hits": 0, "cow_copies": 0}
    return {**base, **kw}


def test_readers_read_the_noted_steps():
    del fam.STEPS[:]
    fam.STEPS.extend([
        noted(1.2, [(10, 0, 0)], prefix_hit_tokens=100, prefill_tokens=50),
        noted(1.5, [(10, 10, 1), (1, 70, 1)], slots_computed=128, slots_live=48,
              prefix_hit_tokens=164, prefill_tokens=60),
        noted(1.8, [(1, 20, 1)], prefix_hit_tokens=420, prefill_tokens=210),
        noted(99.0, [(1, 0, 1)], prefix_hit_tokens=9999),  # after the window
    ])
    doc = dict(cells.load_config(CONFIG))
    run = {"config": doc, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.4, "t1": 1.9},
           "trace": {"module_runs_s": {"jit_ragged_program(1)": [0.010, 0.012]}, "busy_s": 2.0,
                     "device_ops": [["fusion f32[64]", 0.9], ["while s32[]", 0.6]]},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: cells.load_reader(name).read(run)  # noqa: E731
    assert read("prefix_hit_token_share") == pytest.approx(100.0 * 320 / (320 + 160))
    assert read("walk_live_slot_share") == pytest.approx(100.0 * (16 + 48 + 16) / (64 + 128 + 64))
    # half the line: an inner loop inside an outer one, both of that name
    assert read("mla_walk_busy_share") == pytest.approx(100.0 * 0.3 / 2.0)
    peaks = run["peaks"]
    in_slice = [[(10, 10, 1), (1, 70, 1)], [(1, 20, 1)]]
    walk = [roofline_mla.walk_least_seconds(doc, rows, peaks)[0] for rows in in_slice]
    assert read("mla_walk_roofline_share") == pytest.approx(100.0 * (sum(walk) / 2) / (0.3 / 2))
    step = [roofline_mla.step_least_seconds(doc, rows, noted(0, [])["counters"], peaks)[0]
            for rows in in_slice]
    assert read("mla_step_roofline_share") == pytest.approx(100.0 * (sum(step) / 2) / 0.011)
    assert 0 < read("mla_step_roofline_share") and read("moe_here_share") == pytest.approx(6.25)
    del fam.STEPS[:]


TINY_DOC = {"hidden_size": 8, "intermediate_size": 16, "moe_intermediate_size": 4,
            "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_attention_heads": 2,
            "q_lora_rank": 6, "kv_lora_rank": 4, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2,
            "v_head_dim": 3, "vocab_size": 32, "num_experts_routed": 16, "n_shared_experts": 1}


def test_roofline_counts_equal_a_hand_count():
    doc = TINY_DOC
    # Wqa 8x6, Wqb 6 x 2 x 5, Wkva 8 x 6, Wkvb 4 x 2 x 6, Wo 6 x 8
    attn = 48 + 60 + 48 + 48 + 48
    expert = 3 * 8 * 4
    unrouted = 3 * attn + 3 * 8 * 16 + 2 * (8 * 16 + expert)
    assert roofline_mla.attn_params(doc) == attn and roofline_mla.expert_params(doc) == expert
    assert roofline_mla.unrouted_params(doc) == unrouted
    # a slot against a key: 2 heads x (score over 6 columns + value over 4), x 2
    assert roofline_mla.slot_key_flops(doc) == 2 * 2 * (6 + 4)
    # a row of 3 tokens fed from position 7 sees 8, 9, 10 keys; a decode row at position 2 sees 3
    rows = [(3, 7, 1), (1, 2, 1)]
    assert roofline_mla.seen_positions(3, 7) == 27 and roofline_mla.seen_positions(1, 2) == 3
    assert roofline_mla.walk_flops(doc, rows) == 40 * 3 * (27 + 3)
    # each row's latent (6 numbers) once a layer up to its last fed position, the new ones written
    assert roofline_mla.walk_bytes(doc, rows) == 6 * 2 * 3 * ((10 + 3) + 4)
    flops = 2 * unrouted * 4 + 2 * expert * 7 + 40 * 3 * 30 + 2 * 8 * 32 * 2
    assert roofline_mla.step_flops(doc, rows, 7) == flops
    nbytes = (unrouted + 3 * expert) * 2 + 8 * 32 * 2 + 4 * 8 * 2 + 6 * 2 * 3 * 17
    assert roofline_mla.step_bytes(doc, rows, 3) == nbytes
    counters = {"moe_assignments_here": 7, "moe_experts_touched": 3}
    peaks = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e9}
    assert roofline_mla.step_least_seconds(doc, rows, counters, peaks) == (flops / 1e3, "flops")
    peaks = {"bf16_flops": 1e15, "hbm_bytes_per_s": 1.0}
    assert roofline_mla.walk_least_seconds(doc, rows, peaks) == (6 * 2 * 3 * 17, "bandwidth")
    # at the published widths: 139 kFLOP a slot, key and layer; 1152 B a position and layer
    full = cells.load_config(CONFIG)
    assert roofline_mla.slot_key_flops(full) == 139264 and roofline_mla.latent_dim(full) * 2 == 1152


def test_the_walks_and_the_products_are_found_by_their_recorded_names():
    """Operation names as a v5e trace of the cell gave them (recorded, reduced
    by ``trace_reduce.op_kind``): the readers' names find the walks and the
    grouped products."""
    from benchmarks.layer_metrics import mla_walk_busy_share as walks
    from benchmarks.layer_metrics import moe_experts_roofline_share as products

    ops = json.load(open(os.path.join(os.path.dirname(__file__), "data", "axk1_device_ops.json")))
    names = [name for name, _ in ops["device_ops"]]
    assert walks.OP_NAME in names
    assert any(name.startswith(products.OP_PREFIX) for name in names)
    assert products.products_seconds({"trace": ops}) > 0


def test_a_rehearsal_of_the_new_cell_with_a_second_turn_reaches_its_last_line():
    """The cell's own control flow on the CPU: sessions, later turns that hit
    the prefix cache, the tap, the readers.  The CPU backend copies the whole
    arena every step (no donation there), so the resolved cell's pool and
    lengths are cut to what it serves in seconds; the rates, the turns and
    everything the harness does stay the cell's."""
    cell = cells.resolve(CELL)
    cell.config = {**cell.config, "max_position_embeddings": 2048,
                   "pool": {**cell.config["pool"], "pages": 2048, "max_new_tokens": 12}}
    cell.traffic = {**cell.traffic,
                    "prompt_tokens": {"dist": "lognormal", "median": 48, "sigma": 0.8,
                                      "min": 16, "max": 160},
                    "new_tokens": {"dist": "uniform", "min": 4, "max": 12},
                    "sessions": {**cell.traffic["sessions"], "think_s": [0.5, 0.5]}}
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 30, seconds=14.0, trace=1,
                              rehearse=True, rate=0.5, control=0)
    out = asyncio.run(bench_run.run_cell(args, cell))
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["failed"] == 0 and out["attempted"] > 7 and out["device"]["platform"] == "cpu"
    later = [s for s in fam.STEPS if s.get("prefix_hits")]
    assert later and later[-1]["prefix_hit_tokens"] > 0  # a later turn hit its history
    for name in ("prefix_hit_token_share", "walk_live_slot_share", "moe_here_share",
                 "moe_experts_touched_share", "moe_load_imbalance", "step_cycle_ms"):
        assert out["metrics"][name]["value"] > 0, name
    for name in ("mla_walk_busy_share", "mla_walk_roofline_share", "mla_step_roofline_share",
                 "moe_experts_roofline_share", "hbm_peak_gb"):
        assert name not in out["metrics"]  # nothing ran on a device here
