"""The ``longcat`` family's cell: it resolves through files found by name, its
configuration holds every published width and keeps to the model-configs
guide's rule for ``reduced`` (depth, experts held, vocabulary, context; never
a width), its readers return nothing on a run without their counters, the
family's roofline count equals a hand count, and a rehearsal reaches its last
line with the reference agreeing with the program at tiny widths."""
import argparse
import asyncio
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.families import longcat as fam
from benchmarks.harness import cells, roofline_longcat

BENCH = cells.load_benchmark()
CELL = "longcat-longanswers-open"
CONFIG = "longcat-flash-chat-ep32"
NEW_READERS = ("longcat_step_roofline_share", "moe_zero_pick_share", "moe_real_picks_spread")
SHARED_READERS = ("moe_here_share", "moe_experts_touched_share", "moe_load_imbalance",
                  "moe_experts_roofline_share", "walk_live_slot_share", "mla_walk_busy_share",
                  "step_cycle_ms", "step_assemble_ms", "step_feed_ms", "step_wait_ms",
                  "step_emit_ms", "step_host_share")
#: what a cut to one chip may change (guide, section 4); every other key is a width or a rule
MAY_BE_REDUCED = {"num_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"}
#: the catalog row's ``config`` (architectures.jsonl, row 37), whole
PUBLISHED = {"attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
             "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
             "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
             "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
             "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
             "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
             "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
             "zero_expert_type": "identity", "moe_topk": 12}


def test_the_cell_resolves_and_reports_what_the_contract_asks():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "longcat"
    cell.family.validate(dict(cell.config))
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"setup_s", "tokens_per_s", "tpot_p95_ms"}
    assert {m["moves"] for m in cell.per_layer} <= reported
    assert set(cell.readers) == {m["name"] for m in cell.per_layer} >= set(NEW_READERS + SHARED_READERS)
    row = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert (row["config"], row["traffic"], row["chips"]) == (CONFIG, "longanswers-open", 1)
    assert len(row["why"]) <= 200 and len(entry["why"]) <= 200
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
        elif m["name"] in SHARED_READERS:
            assert CELL in m["workloads"]
    # roofline_mla.py knows one attention a layer: its two readers are not asked of this cell
    assert not {"mla_walk_roofline_share", "mla_step_roofline_share", "afmoe_step_roofline_share",
                "prefix_hit_token_share", "kv_window_held_share"} & set(cell.readers)


def test_every_published_width_is_kept_and_reduced_names_cuts_of_scale():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    doc = json.load(open(os.path.join(cells.REPO_ROOT, entry["file"])))
    assert doc["reduced"] == entry["reduced"] and doc["source"] == entry["source"]
    assert set(doc["reduced"]) == MAY_BE_REDUCED
    for key, value in PUBLISHED.items():
        if key in MAY_BE_REDUCED:
            assert doc["source_values"][key] == value != doc[key] and key in doc["reduced_why"]
        else:
            assert doc[key] == value and type(doc[key]) is type(value), key
    # the floors of a cut: four layers, 8+ experts, an eighth of the vocabulary
    assert doc["num_layers"] >= 4 and doc["n_routed_experts"] >= 8
    assert doc["vocab_size"] * 8 >= doc["source_values"]["vocab_size"]
    # the names other readers read are the file's own under another name
    assert doc["num_experts_routed"] == 512 + doc["zero_expert_num"] == 768
    assert (doc["num_experts"], doc["moe_intermediate_size"], doc["num_dense_layers"]) == (
        doc["n_routed_experts"], doc["expert_ffn_hidden_size"], 0)
    assert (doc["num_hidden_layers"], doc["intermediate_size"], doc["head_dim"],
            doc["num_key_value_heads"]) == (doc["num_layers"], doc["ffn_hidden_size"], 192, 1)
    assert fam.n_params(dict(doc)) == 5_172_749_312
    assert {"gap_mean_limit", "gap_max_limit", "derivation", "sample_tokens"} <= set(doc["check"])
    assert {"selection_bias", "rope_pairing", "norms", "rank_scaling", "layout", "router",
            "weights"} <= set(doc["assumed"])
    assert "thirty-two chips" in doc["deployment"] and "not modelled" in doc["deployment"].lower()
    pool = doc["pool"]
    assert pool["prefix_cache"] is True and pool["speculative"] is False
    assert pool["pages"] == pool["max_sessions"] * doc["max_position_embeddings"] // pool["page_size"]
    assert pool["max_sessions"] + pool["prefill_budget"] == 128 < 171
    cfg = fam.program_config(dict(doc))
    assert (cfg.n_experts, cfg.n_identity, cfg.experts_held, cfg.top_k) == (512, 256, 16, 12)
    assert (cfg.n_sublayers, cfg.latent_dim, cfg.latent_width) == (8, 576, 640)
    assert (cfg.q_scale, round(cfg.kv_scale, 4), cfg.route_scale) == (2.0, 3.4641, 6.0)


def test_the_step_roofline_readers_count_is_a_lower_bound_of_this_models():
    """``step_roofline_share`` is read in every cell and counts a dense
    grouped-query layer: under the keys the file states for it, its
    operations and bytes lie below the family's own count for any rows."""
    from benchmarks.harness import roofline

    doc = cells.load_config(CONFIG)
    assert roofline.layer_matmul_params(doc) * 4 < roofline_longcat.unrouted_params(doc)
    for rows in ([(1, 700, 1)] * 28, [(96, 0, 0)] + [(1, 3000, 1)] * 20, [(40, 1900, 1)]):
        rr = [roofline.Row(n=n, start=s, head=h) for n, s, h in rows]
        assert roofline.step_flops(doc, rr) < roofline_longcat.step_flops(doc, rows, 0, 0)
        assert roofline.step_bytes(doc, rr) < roofline_longcat.step_bytes(doc, rows, 0)


def test_the_traffic_is_one_turn_requests_whose_schedule_does_not_move_with_the_seed():
    from benchmarks.harness import traffic

    tr = cells.load_traffic("longanswers-open")
    assert tr["sessions"]["turns"] == [1, 1] and tr["loop"] == "open"
    knee = tr["knee"]
    assert len(knee["sweep"]) >= 5 and tr["rate_rps"] == pytest.approx(0.8 * knee["requests_per_s"])
    kw = dict(seconds=51, vocab=16384, context=8192, max_new_cap=1024)
    a, b = traffic.generate(tr, seed=1, **kw), traffic.generate(tr, seed=2 ** 31 + 5, **kw)
    shape = lambda rs: [(len(r["tokens"]), r["max_new_tokens"], r.get("due_s")) for r in rs]  # noqa: E731
    assert shape(a) == shape(b) and [r["tokens"] for r in a] != [r["tokens"] for r in b]
    assert all(32 <= len(r["tokens"]) <= 2048 and 64 <= r["max_new_tokens"] <= 1024 for r in a)
    answers = sorted(r["max_new_tokens"] for r in a)
    assert 300 < answers[len(answers) // 2] < 480  # median 384


def test_rehearsal_widths_settle_into_a_consistent_tiny_model():
    doc = dict(cells.load_config(CONFIG))
    doc.update(bench_run.TINY)
    cfg = fam.program_config(doc)
    assert (cfg.d_model, cfg.n_heads, cfg.q_rank, cfg.kv_rank, cfg.nope_dim, cfg.rope_dim,
            cfg.v_dim, cfg.d_ff, cfg.d_expert) == (64, 4, 32, 32, 16, 8, 16, 128, 32)
    assert (cfg.n_layers, cfg.n_experts, cfg.n_identity, cfg.experts_held, cfg.top_k) == (
        2, 512, 256, 16, 12)
    assert doc["kv_lora_rank"] == 32 and doc["num_layers"] == 2  # the reference reads the same file


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_without_its_counters(name):
    del fam.STEPS[:]
    run = {"config": {}, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.0, "t1": 2.0},
           "trace": {"module_runs_s": {"jit_ragged_program": [0.01]}, "busy_s": 1.0,
                     "device_ops": [["while s32[]", 0.5]]},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    assert cells.load_reader(name).read(run) is None
    assert cells.load_reader(name).read({**run, "slice": {}, "trace": {}, "peaks": None}) is None
    # another family's noted steps (no counter of identity picks) are nothing to read either
    fam.STEPS.append({"at": 1.5, "rows": [(4, 0, 1)],
                      "counters": {"moe_assignments": 8, "moe_assignments_here": 1,
                                   "moe_experts_touched": 1, "moe_max_expert_load": 1},
                      "window_blocks": 0, "full_blocks": 1, "window_pages": 0, "full_pages": 1})
    assert cells.load_reader(name).read({**run, "config": cells.load_config(CONFIG)}) is None
    del fam.STEPS[:]


def noted(at, rows, **counters):
    base = {"moe_assignments": 96, "moe_assignments_here": 3, "moe_experts_touched": 2,
            "moe_max_expert_load": 2, "moe_zero_assignments": 30, "moe_real_picks_max": 40,
            "moe_real_picks_min": 20}
    return {"at": at, "rows": rows, "counters": {**base, **counters}, "window_blocks": 0,
            "full_blocks": 1, "window_pages": 0, "full_pages": 9, "slots_computed": 64,
            "slots_live": 16, "prefix_hit_tokens": 0, "prefill_tokens": 0, "prefix_hits": 0,
            "cow_copies": 0}


def test_readers_read_the_noted_steps():
    del fam.STEPS[:]
    fam.STEPS.extend([
        noted(1.2, [(2, 0, 0)]),
        noted(1.5, [(1, 10, 1), (1, 70, 1)], moe_zero_assignments=36, moe_real_picks_max=44,
              moe_real_picks_min=12),
        noted(99.0, [(1, 0, 1)], moe_zero_assignments=96),  # after the window
    ])
    doc = dict(cells.load_config(CONFIG))
    run = {"config": doc, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.4, "t1": 1.9},
           "trace": {"module_runs_s": {"jit_ragged_program(1)": [0.010, 0.012]}, "busy_s": 2.0,
                     "device_ops": [["fusion f32[128]", 0.9], ["while s32[]", 0.6]]},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: cells.load_reader(name).read(run)  # noqa: E731
    assert read("moe_zero_pick_share") == pytest.approx(100.0 * 66 / 192)
    # (most - fewest) summed over the four layers a step, over steps x layers
    assert read("moe_real_picks_spread") == pytest.approx((20 + 32) / (2 * 4))
    (least, bound), = [roofline_longcat.step_least_seconds(
        doc, [(1, 10, 1), (1, 70, 1)], noted(0, [], moe_zero_assignments=36)["counters"],
        run["peaks"])]
    assert bound == "bandwidth"
    assert read("longcat_step_roofline_share") == pytest.approx(100.0 * least / 0.011)
    assert read("moe_here_share") == pytest.approx(100.0 * 6 / 192)
    assert read("walk_live_slot_share") == pytest.approx(25.0)
    assert read("mla_walk_busy_share") == pytest.approx(100.0 * 0.3 / 2.0)
    del fam.STEPS[:]


TINY_DOC = {"hidden_size": 8, "ffn_hidden_size": 16, "expert_ffn_hidden_size": 4, "num_layers": 3,
            "num_attention_heads": 2, "q_lora_rank": 6, "kv_lora_rank": 4, "qk_nope_head_dim": 3,
            "qk_rope_head_dim": 2, "v_head_dim": 3, "vocab_size": 32, "num_experts_routed": 24}


def test_roofline_counts_equal_a_hand_count():
    doc = TINY_DOC
    # a sublayer: Wqa 8x6, Wqb 6 x 2 x 5, Wkva 8 x 6, Wkvb 4 x 2 x 6, Wo 6 x 8; its FFN 3 x 8 x 16
    attn, ffn, expert = 48 + 60 + 48 + 48 + 48, 3 * 8 * 16, 3 * 8 * 4
    unrouted = 3 * (2 * (attn + ffn) + 8 * 24)
    assert roofline_longcat.expert_params(doc) == expert
    assert roofline_longcat.unrouted_params(doc) == unrouted and roofline_longcat.sublayers(doc) == 6
    # a row of 3 tokens fed from position 7 sees 8, 9, 10 keys; a decode row at position 2 sees 3;
    # a slot against a key: 2 heads x (score over 6 columns + value over 4), x 2, once a SUBLAYER
    rows = [(3, 7, 1), (1, 2, 1)]
    assert roofline_longcat.walk_flops(doc, rows) == 40 * 6 * (27 + 3)
    # each row's latent (6 numbers) once a sublayer up to its last fed position, the new ones written
    assert roofline_longcat.walk_bytes(doc, rows) == 6 * 2 * 6 * ((10 + 3) + 4)
    # 7 assignments to held experts, 11 identity picks: one multiply-add a number of the hidden size
    flops = 2 * unrouted * 4 + 2 * expert * 7 + 2 * 8 * 11 + 40 * 6 * 30 + 2 * 8 * 32 * 2
    assert roofline_longcat.step_flops(doc, rows, 7, 11) == flops
    nbytes = (unrouted + 3 * expert) * 2 + 8 * 32 * 2 + 4 * 8 * 2 + 6 * 2 * 6 * 17
    assert roofline_longcat.step_bytes(doc, rows, 3) == nbytes
    counters = {"moe_assignments_here": 7, "moe_experts_touched": 3, "moe_zero_assignments": 11}
    peaks = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e9}
    assert roofline_longcat.step_least_seconds(doc, rows, counters, peaks) == (flops / 1e3, "flops")
    peaks = {"bf16_flops": 1e15, "hbm_bytes_per_s": 1.0}
    assert roofline_longcat.step_least_seconds(doc, rows, counters, peaks) == (nbytes, "bandwidth")
    # at the published widths: 638.8 M unrouted parameters a layer, 37.75 M an expert
    full = cells.load_config(CONFIG)
    assert roofline_longcat.unrouted_params(full) == 4 * 638_844_928
    assert roofline_longcat.expert_params(full) == 37_748_736


def test_a_rehearsal_of_the_new_cell_reaches_its_last_line_and_agrees_with_the_reference():
    """The cell's own control flow on the CPU at tiny widths: the tap, the
    readers, the check against the plain reference (the program runs in bf16
    there as on the chip, so the gaps are held to the file's limits, not to
    0).  The CPU backend copies the whole arena every step (no donation
    there), so the resolved cell's pool and lengths are cut to what it serves
    in seconds; everything the harness does stays the cell's."""
    cell = cells.resolve(CELL)
    cell.config = {**cell.config, "max_position_embeddings": 1024,
                   "pool": {**cell.config["pool"], "pages": 2048, "max_new_tokens": 24}}
    cell.traffic = {**cell.traffic,
                    "prompt_tokens": {"dist": "lognormal", "median": 48, "sigma": 0.8,
                                      "min": 16, "max": 160},
                    "new_tokens": {"dist": "uniform", "min": 8, "max": 24}}
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 32, seconds=12.0, trace=1,
                              rehearse=True, rate=1.5, control=0)
    out = asyncio.run(bench_run.run_cell(args, cell))
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 10
    assert out["device"]["platform"] == "cpu"
    for name in ("moe_zero_pick_share", "moe_real_picks_spread", "walk_live_slot_share",
                 "moe_here_share", "moe_experts_touched_share", "moe_load_imbalance",
                 "step_cycle_ms", "batch_occupancy"):
        assert out["metrics"][name]["value"] > 0, name
    assert 25 < out["metrics"]["moe_zero_pick_share"]["value"] < 42  # 256 of 768: about a third
    for name in ("longcat_step_roofline_share", "mla_walk_busy_share",
                 "moe_experts_roofline_share", "hbm_peak_gb"):
        assert name not in out["metrics"]  # nothing ran on a device here
