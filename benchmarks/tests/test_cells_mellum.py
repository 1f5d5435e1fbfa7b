"""The ``mellum`` family's cell: it resolves through files found BY NAME (so a
later cell does not fail it), its configuration holds every key of the catalog
row unchanged but the cuts of depth and context, the expert set and the
vocabulary whole, the pool follows the house rule, the traffic keeps ISSUE
46's parameters at 0.8 of the knee its own sweep found, the one new reader
returns nothing on a run without its counters and reads noted steps, the
family's roofline count equals a hand count, and a rehearsal reaches its last
line with the reference agreeing with the program at tiny widths."""
import argparse
import asyncio
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.families import mellum as fam
from benchmarks.harness import cells, roofline, roofline_afmoe, roofline_mellum, traffic

BENCH = cells.load_benchmark()
CELL = "mellum2-idechat-open"
CONFIG = "mellum2-12b-a2.5b-pp"
TRAFFIC = "idechat-open"
NEW_READERS = ("mellum_step_roofline_share",)
SHARED_READERS = ("step_cycle_ms", "step_assemble_ms", "step_feed_ms", "step_wait_ms",
                  "step_emit_ms", "step_host_share", "setup_compute_s", "setup_state_s",
                  "setup_trace_lower_s", "setup_load_s", "setup_first_step_s", "setup_serving_s",
                  "setup_cache_hit_share", "moe_here_share", "moe_experts_touched_share",
                  "moe_load_imbalance", "moe_grouped_roofline_share", "kv_window_held_share",
                  "window_blocks_share", "head_walk_busy_share")
#: what this cut changes: depth (the two lists of layer kinds with it) and context
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types", "max_position_embeddings"]
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
#: the catalog row's ``config`` (architectures.jsonl, ``Mellum2-12B-A2.5B-Instruct``), whole
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 7168, "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32,
                           "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304,
    "use_sliding_window": True}


def test_the_cell_resolves_and_reports_what_the_contract_asks():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "mellum"
    cell.family.validate(dict(cell.config))
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"setup_s", "tokens_per_s", "tpot_p95_ms"}  # TTFT: PERF.md section 7
    assert {m["moves"] for m in cell.per_layer} <= reported
    assert set(cell.readers) == {m["name"] for m in cell.per_layer} >= set(NEW_READERS + SHARED_READERS)
    row = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert (row["config"], row["traffic"], row["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(row["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["source"] == ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
                               "main/config.json")
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        elif m["name"] in SHARED_READERS:
            assert m["workloads"][-1] == CELL  # appended, nothing else moved
    # no latent walk, no state slot, no other family's count, and not the reader PR 41 silenced
    assert not {"moe_experts_roofline_share", "walk_live_slot_share", "mla_walk_busy_share",
                "afmoe_step_roofline_share", "state_slots_held_share", "prefix_hit_token_share",
                "moe_zero_pick_share"} & set(cell.readers)
    assert "step_roofline_share" in cell.readers  # it has no list: read in every cell
    assert [w["chips"] for w in BENCH["workloads"]] == [1] * len(BENCH["workloads"])


def test_every_published_key_is_kept_and_reduced_names_depth_and_context_alone():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    doc = json.load(open(os.path.join(cells.REPO_ROOT, entry["file"])))
    assert doc["reduced"] == entry["reduced"] == REDUCED and doc["source"] == entry["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert doc["source_values"][key] == value != doc[key] and key in doc["reduced_why"]
        else:
            assert doc[key] == value and type(doc[key]) is type(value), key
    # the cut: published layers 0-7, two whole periods; the expert set and the vocabulary whole
    assert doc["num_hidden_layers"] == 8 and doc["kept_layers"] == list(range(8))
    assert doc["layer_types"] == PERIOD * 2 and doc["mlp_layer_types"] == ["sparse"] * 8
    assert (doc["first_expert"], doc["num_experts"], doc["num_dense_layers"]) == (0, 64, 0)
    assert doc["max_position_embeddings"] == 20480 >= 16384 + 1024
    assert fam.n_params(dict(doc)) == 3_794_968_832  # 7.59 GB in bfloat16
    assert {"gap_mean_limit", "gap_max_limit", "derivation", "sample_tokens",
            "sample_requests"} <= set(doc["check"])
    assert {"qk_norm", "rotation_layout", "yarn_truncate", "window_edge", "router",
            "no_prediction_head", "weights"} <= set(doc["assumed"])
    assert "pipeline" in doc["deployment"] and "WHOLE" in doc["deployment"]
    assert doc["guarantees"] == cells.load_config("trinity-large-preview-ep8")["guarantees"]
    pool = doc["pool"]
    assert not any(pool[k] for k in ("speculative", "prefix_cache", "hibernation", "migration"))
    assert pool["pages"] == pool["max_sessions"] * doc["max_position_embeddings"] // pool["page_size"]
    assert (pool["page_size"], pool["max_sessions"], pool["max_new_tokens"]) == (16, 32, 1024)
    assert pool["prefill_budget"] == 224 and "ISSUE 46 asked for 96" in doc["pool_why"]["prefill_budget"]
    cfg = fam.program_config(dict(doc))
    assert (cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_expert) == (
        8, 32, 4, 128, 896)
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.top_k) == (64, 64, 0, 8)
    assert (cfg.route_score, cfg.route_scale, cfg.route_norm, cfg.n_shared) == ("softmax", 1.0, True, 0)
    assert cfg.full_layers == (3, 7) and len(cfg.window_layers) == 6 and cfg.window == 1024
    assert cfg.rope_sliding.factor == 1.0 and cfg.rope_sliding.theta == cfg.rope_full.theta == 5e5
    assert (cfg.rope_full.factor, cfg.rope_full.original_len) == (16.0, 8192)
    assert cfg.rope_full.attention_factor == 1.2772588722239782
    spec = cfg.serving_spec()
    assert spec.kv_positional and spec.kv_by_head and not spec.kv_whole_row
    assert spec.aux_shape == (8, 64)


def test_the_traffic_keeps_the_issues_parameters_at_four_fifths_of_its_own_knee():
    tr = cells.load_traffic(TRAFFIC)
    assert (tr["loop"], tr["arrivals"]) == ("open", {"process": "poisson"})
    assert tr["sessions"] == {"turns": [1, 1], "shared_prefix_tokens": 0, "think_s": [0.0, 0.0]}
    assert tr["schedule_seed"] == 46
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.8,
                                   "min": 512, "max": 16384}
    assert tr["new_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.7,
                                "min": 32, "max": 1024}
    knee = tr["knee"]
    assert tr["rate_rps"] == pytest.approx(0.8 * knee["requests_per_s"], rel=0.03)
    assert len(knee["sweep"]) >= 4 and all(
        {"rate", "prefill_budget", "tokens_per_s", "completions_per_s",
         "in_flight_half_close"} <= set(p) for p in knee["sweep"])
    doc = cells.load_config(CONFIG)
    window = BENCH["run_seconds"]
    kw = dict(seconds=window, vocab=doc["vocab_size"], context=doc["max_position_embeddings"],
              max_new_cap=doc["pool"]["max_new_tokens"])
    a, b = traffic.generate(tr, seed=1, **kw), traffic.generate(tr, seed=2 ** 31 + 5, **kw)
    shape = lambda rs: [(len(r["tokens"]), r["max_new_tokens"], r["due_s"]) for r in rs]  # noqa: E731
    assert shape(a) == shape(b) and [r["tokens"] for r in a] != [r["tokens"] for r in b]
    assert len(a) == round(tr["rate_rps"] * window) >= 80
    prompts = sorted(len(r["tokens"]) for r in a)
    # every prompt is at least half the window, the median about twice it, the longest the cap
    assert prompts[0] >= 512 and 1900 < prompts[len(prompts) // 2] < 2200
    assert prompts[-1] == 16384 and sum(p > 8192 for p in prompts) >= 2  # beyond YaRN's original
    assert 2600 < sum(prompts) / len(prompts) < 3000
    assert all(len(r["tokens"]) + r["max_new_tokens"] <= doc["max_position_embeddings"] for r in a)
    assert all(32 <= r["max_new_tokens"] <= 1024 for r in a)
    # 112 of the 122 rows outgrow the 1024 window and 102 lap their ring of 81 pages of 16
    # (ISSUE 46 said every one: a 512-token prompt with a short answer ends at 651)
    ends = [len(r["tokens"]) + r["max_new_tokens"] for r in a]
    assert sum(e > 1024 for e in ends) >= 0.9 * len(a) and sum(e > 81 * 16 for e in ends) >= 0.8 * len(a)


def test_rehearsal_widths_settle_into_a_consistent_tiny_model():
    doc = dict(cells.load_config(CONFIG))
    doc.update(bench_run.TINY)
    cfg = fam.program_config(doc)
    assert cfg.layer_types == ("sliding_attention", "full_attention")
    assert (cfg.d_model, cfg.d_expert, cfg.window, cfg.n_experts, cfg.experts_held, cfg.top_k) == (
        64, 32, 64, 64, 64, 8)
    assert doc["layer_types"] == list(cfg.layer_types)  # the reference reads the same file
    assert doc["mlp_layer_types"] == ["sparse", "sparse"]
    assert cfg.rope_full.factor == 16.0  # the rotations stay as published


def test_the_new_reader_returns_nothing_without_its_counters():
    """On the parent's program, on another family's run and on a slice that
    noted no step the reader finds nothing to read and does not raise."""
    del fam.STEPS[:]
    run = {"config": {}, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.0, "t1": 2.0},
           "trace": {"module_runs_s": {"jit_ragged_program": [0.01]}, "device_ops": []},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    reader = cells.load_reader(NEW_READERS[0])
    assert reader.read(run) is None
    assert reader.read({**run, "slice": {}, "trace": {}, "peaks": None}) is None
    # another sparse family's noted steps are nothing to read either
    fam.STEPS.append({"at": 1.5, "rows": [(4, 0, 1)], "counters": {
        "moe_assignments": 8, "moe_assignments_here": 2, "moe_experts_touched": 2}})
    assert reader.read({**run, "config": cells.load_config("trinity-large-preview-ep8")}) is None
    del fam.STEPS[:]
    assert reader.read({**run, "config": cells.load_config(CONFIG)}) is None  # no step in the slice


def test_readers_read_the_noted_steps_of_a_synthetic_trace_reduction():
    del fam.STEPS[:]
    doc = dict(cells.load_config(CONFIG))
    decode = [(1, 2000 + 300 * i, 1) for i in range(6)]
    chunk = decode + [(224, 4096, 0)]
    # a decode-only step touches 30 experts a layer, a chunk step all 64
    noted = lambda at, rows, touched: {  # noqa: E731
        "at": at, "rows": rows, "window_blocks": 10, "full_blocks": 40, "window_pages": 400,
        "full_pages": 1600,
        "counters": {"moe_assignments": 64 * sum(n for n, _, _ in rows),
                     "moe_assignments_here": 64 * sum(n for n, _, _ in rows),
                     "moe_experts_touched": 8 * touched, "moe_max_expert_load": 8 * 20}}
    fam.STEPS.extend([noted(1.5, decode, 30), noted(1.7, chunk, 64), noted(99.0, decode, 30)])
    run = {"config": doc, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.4, "t1": 1.9},
           "trace": {"module_runs_s": {"jit_ragged_program(1)": [0.008, 0.014]}, "busy_s": 0.4,
                     "device_ops": [["expert_mlp f32[2048,2304]", 0.012], ["head_walk", 0.004]]},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: cells.load_reader(name).read(run)  # noqa: E731
    least = [roofline_mellum.step_least_seconds(doc, s["rows"], s["counters"], run["peaks"])
             for s in fam.STEPS[:2]]
    assert [bound for _, bound in least] == ["bandwidth", "bandwidth"]
    # by the bytes alone: about 4.7 ms with 30 of 64 experts a layer, 9.0 ms with all 64
    assert 0.0042 < least[0][0] < 0.0052 and 0.0085 < least[1][0] < 0.0095
    assert read("mellum_step_roofline_share") == pytest.approx(
        100.0 * (sum(t for t, _ in least) / 2) / 0.011)
    assert read("mellum_step_roofline_share") < 100
    assert read("moe_here_share") == 100.0
    assert read("moe_experts_touched_share") == pytest.approx(100.0 * (30 + 64) / 128)
    assert read("window_blocks_share") == 25.0 and read("kv_window_held_share") == 25.0
    assert read("head_walk_busy_share") == pytest.approx(1.0)
    assert 0 < read("moe_grouped_roofline_share") < 100
    # the dense count step_roofline_share makes of this file leaves the experts out
    rr = [roofline.Row(n=n, start=s, head=h) for n, s, h in chunk]
    assert roofline.least_seconds(doc, rr, run["peaks"])[0] < 0.5 * least[1][0]
    del fam.STEPS[:]


TINY_DOC = {"hidden_size": 8, "moe_intermediate_size": 4, "num_hidden_layers": 4,
            "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
            "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 32,
            "sliding_window": 6, "num_experts": 16}


def test_roofline_counts_equal_a_hand_count():
    doc = TINY_DOC
    attn = 8 * 8 + 2 * 8 * 4 + 8 * 8  # wq, wk and wv, wo: no gate
    expert = 3 * 8 * 4
    unrouted = 4 * (attn + 8 * 16)  # and the router: no dense layer, no shared expert
    assert roofline_mellum.attn_params(doc) == attn and roofline_afmoe.expert_params(doc) == expert
    assert roofline_mellum.unrouted_params(doc) == unrouted
    # a row of 3 tokens fed from position 7 (sees 8, 9, 10 keys whole; 6 each under the
    # window), one position sampled; a decode row at position 2
    rows = [(3, 7, 1), (1, 2, 1)]
    counters = {"moe_assignments_here": 7, "moe_experts_touched": 3}
    seen = 3 * (18 + 3) + (27 + 3)
    flops = 2 * unrouted * 4 + 2 * expert * 7 + 4 * 2 * 4 * seen + 2 * 8 * 32 * 2
    assert roofline_mellum.step_flops(doc, rows, 7) == flops
    kv_read = (3 * (8 + 3) + (10 + 3)) * 2 * 4 * 2
    nbytes = (unrouted + 3 * expert) * 2 + 8 * 32 * 2 + 4 * 8 * 2 + kv_read + 4 * 2 * 4 * 4 * 2
    assert roofline_mellum.step_bytes(doc, rows, 3) == nbytes
    assert roofline_mellum.step_least_seconds(
        doc, rows, counters, {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e9}) == (flops / 1e3, "flops")
    assert roofline_mellum.step_least_seconds(
        doc, rows, counters, {"bf16_flops": 1e15, "hbm_bytes_per_s": 1.0}) == (nbytes, "bandwidth")
    # at the published widths the count holds every parameter of the file but the norms
    full = cells.load_config(CONFIG)
    norms = 8 * (2 * 2304 + 2 * 128) + 2304
    assert (roofline_mellum.unrouted_params(full) + 8 * 64 * roofline_afmoe.expert_params(full)
            + 2 * 98304 * 2304 + norms) == fam.n_params(dict(full))
    assert roofline_afmoe.expert_params(full) == 6_193_152


def test_a_rehearsal_of_the_new_cell_reaches_its_last_line_and_agrees_with_the_reference():
    """The cell's own control flow on the CPU at tiny widths, its prompts cut
    so that the CPU ends them inside the drain (a row still laps its ring of
    the tiny window): the gateway, the tap, the readers, the check against the
    plain reference (bf16 there as on the chip: the gaps are held to the
    file's limits, not to 0)."""
    cell = cells.resolve(CELL)
    cell.traffic = {**cell.traffic,
                    "prompt_tokens": {"dist": "uniform", "min": 200, "max": 900},
                    "new_tokens": {"dist": "uniform", "min": 8, "max": 24}}
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 46, seconds=6.0, trace=1,
                              rehearse=True, rate=1.5, control=0)
    out = asyncio.run(bench_run.run_cell(args, cell))
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 9
    assert out["device"]["platform"] == "cpu"
    assert out["metrics"]["moe_here_share"]["value"] == 100.0
    for name in ("moe_experts_touched_share", "moe_load_imbalance", "kv_window_held_share",
                 "window_blocks_share", "step_cycle_ms", "batch_occupancy", "setup_state_s"):
        assert out["metrics"][name]["value"] > 0, name
    for name in (*NEW_READERS, "moe_grouped_roofline_share", "head_walk_busy_share",
                 "step_roofline_share", "hbm_peak_gb"):
        assert name not in out["metrics"]  # nothing ran on a device here
