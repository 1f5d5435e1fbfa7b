"""The ``xing`` family's cell: it resolves through files found BY NAME (and
asks nothing of its place in a list, so a later cell does not fail it), its
configuration holds every key of the catalog row unchanged but the cuts of
depth and context, the expert set and the vocabulary whole, the pool follows
the house rule, the traffic keeps ISSUE 49's parameters at 0.8 of the knee its
own sweep found, the three new readers return nothing on a run without their
events and read noted steps, the family's roofline count equals a hand count,
and a rehearsal reaches its last line with the reference agreeing with the
program at tiny widths."""
import argparse
import asyncio
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.families import xing as fam
from benchmarks.harness import cells, roofline_mla, roofline_xing, traffic

BENCH = cells.load_benchmark()
CELL = "xing4-ragqa-open"
CONFIG = "xing4.0-29b-a4b-pp"
TRAFFIC = "ragqa-open"
NEW_READERS = ("mhc_busy_share", "mhc_roofline_share", "xing_step_roofline_share")
SHARED_READERS = ("step_cycle_ms", "step_assemble_ms", "step_feed_ms", "step_wait_ms",
                  "step_emit_ms", "step_host_share", "setup_compute_s", "setup_state_s",
                  "setup_trace_lower_s", "setup_load_s", "setup_first_step_s", "setup_serving_s",
                  "setup_cache_hit_share", "moe_here_share", "moe_experts_touched_share",
                  "moe_load_imbalance", "moe_grouped_roofline_share", "walk_live_slot_share",
                  "mla_walk_busy_share")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "max_position_embeddings",
           "num_nextn_predict_layers"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def published() -> dict:
    """The catalog row's ``config`` (architectures.jsonl, ``Xing4.0-29B-A4B``), whole."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not beside this checkout")
    rows = [json.loads(line) for line in open(path) if '"Xing4.0-29B-A4B"' in line]
    return rows[0]["config"]


def test_the_cell_resolves_and_reports_what_the_contract_asks():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "xing"
    cell.family.validate(dict(cell.config))
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"setup_s", "tokens_per_s", "tpot_p95_ms"}  # TTFT: PERF.md section 7
    assert {m["moves"] for m in cell.per_layer} <= reported
    assert set(cell.readers) == {m["name"] for m in cell.per_layer} >= set(NEW_READERS + SHARED_READERS)
    row = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert (row["config"], row["traffic"], row["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(row["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["source"] == ("https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
                               "config.json")
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["source"] == "device_trace" and m["unit"] == "%"
        elif m["name"] in SHARED_READERS:
            assert CELL in m["workloads"]
    # not the two readers PERF.md keeps out (the one PR 41 silenced, the one that reads twice
    # the truth since the kernel), no state slot, no window, no other family's count
    assert not {"moe_experts_roofline_share", "mla_walk_roofline_share", "mla_step_roofline_share",
                "state_slots_held_share", "kv_window_held_share", "prefix_hit_token_share",
                "moe_zero_pick_share", "head_walk_busy_share"} & set(cell.readers)
    assert "step_roofline_share" in cell.readers  # it has no list: read in every cell


def test_every_published_key_is_kept_and_reduced_names_depth_and_context_alone():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    doc = json.load(open(os.path.join(cells.REPO_ROOT, entry["file"])))
    assert doc["reduced"] == entry["reduced"] == REDUCED and doc["source"] == entry["source"]
    for key, value in published().items():
        if key in REDUCED:
            assert doc["source_values"][key] == value != doc[key] and key in doc["reduced_why"]
        else:
            assert doc[key] == value and type(doc[key]) is type(value), key
    # the cut: one dense layer and five expert layers; the expert set and the vocabulary whole
    assert (doc["num_hidden_layers"], doc["first_k_dense_replace"], doc["num_dense_layers"]) == (6, 1, 1)
    assert (doc["first_expert"], doc["n_routed_experts"], doc["num_experts_routed"],
            doc["num_experts"]) == (0, 64, 64, 64)
    assert doc["vocab_size"] == 131072 and doc["max_position_embeddings"] == 16384 >= 12288 + 384
    assert (doc["hc_mult"], doc["hc_sinkhorn_iters"], doc["hc_eps"]) == (4, 20, 1e-6)
    assert fam.n_params(dict(doc)) == 4_792_669_828  # 9.59 GB in bfloat16
    assert {"gap_mean_limit", "gap_max_limit", "derivation", "sample_tokens",
            "sample_requests"} <= set(doc["check"])
    assert {"mhc_equations", "mhc_entry_exit", "mhc_norm", "mhc_sinkhorn_order", "mhc_seeding",
            "stream_dtype", "selection_bias", "rope_pairing", "yarn", "norms", "layout",
            "weights"} <= set(doc["assumed"])
    assert "pipeline" in doc["deployment"] and "WHOLE" in doc["deployment"]
    assert doc["guarantees"] == cells.load_config("a.x-k1-ep16")["guarantees"]
    pool = doc["pool"]
    assert pool["prefix_cache"] and not any(pool[k] for k in ("speculative", "hibernation", "migration"))
    assert pool["pages"] == pool["max_sessions"] * doc["max_position_embeddings"] // pool["page_size"]
    assert (pool["page_size"], pool["max_new_tokens"]) == (16, 384)
    assert 96 <= pool["prefill_budget"] <= 224 and pool["max_sessions"] in (16, 32)
    cfg = fam.program_config(dict(doc))
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_heads, cfg.d_model, cfg.d_expert, cfg.d_ff) == (
        6, 1, 32, 3584, 1024, 9216)
    assert (cfg.q_rank, cfg.kv_rank, cfg.nope_dim, cfg.rope_dim, cfg.v_dim) == (768, 512, 128, 64, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.top_k, cfg.n_group) == (64, 64, 0, 4, 1)
    assert (cfg.route_score, cfg.route_scale, cfg.route_norm, cfg.n_shared) == ("sigmoid", 2.0, True, 1)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_clamp_min, cfg.hc_clamp_max) == (4, 20, -30.0, 30.0)
    assert cfg.rope_factor == 64.0 and abs(cfg.softmax_scale - 0.14468) < 1e-5
    spec = cfg.serving_spec()
    assert spec.kv_positional and spec.kv_whole_row and not spec.kv_by_head
    assert spec.aux_shape == (6, 64)
    assert dict(spec.kernels("tpu", 1)) == {"walk": "latent_walk", "expert": "expert_mlp",
                                            "residual": "mhc_open+mhc_close"}


def test_the_traffic_keeps_the_issues_parameters_at_four_fifths_of_its_own_knee():
    tr = cells.load_traffic(TRAFFIC)
    assert (tr["loop"], tr["arrivals"]) == ("open", {"process": "poisson"})
    assert tr["sessions"] == {"turns": [1, 1], "shared_prefix_tokens": 0, "think_s": [0.0, 0.0]}
    assert tr["schedule_seed"] == 49
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 3072, "sigma": 0.8,
                                   "min": 512, "max": 12288}
    assert tr["new_tokens"] == {"dist": "lognormal", "median": 96, "sigma": 0.6,
                                "min": 16, "max": 384}
    knee = tr["knee"]
    assert tr["rate_rps"] == pytest.approx(0.8 * knee["requests_per_s"], rel=0.03)
    assert "found" in knee and all(  # the sweep's points, once a session has reached a chip
        {"rate", "prefill_budget", "tokens_per_s", "completions_per_s",
         "in_flight_half_close"} <= set(p) for p in knee["sweep"])
    doc = cells.load_config(CONFIG)
    window = BENCH["run_seconds"]
    kw = dict(seconds=window, vocab=doc["vocab_size"], context=doc["max_position_embeddings"],
              max_new_cap=doc["pool"]["max_new_tokens"])
    a, b = traffic.generate(tr, seed=1, **kw), traffic.generate(tr, seed=2 ** 31 + 5, **kw)
    shape = lambda rs: [(len(r["tokens"]), r["max_new_tokens"], r["due_s"]) for r in rs]  # noqa: E731
    assert shape(a) == shape(b) and [r["tokens"] for r in a] != [r["tokens"] for r in b]
    # ISSUE 49 expected over 100 a window from a knee of 3-4; the chip found 1.2 (knee.found)
    assert len(a) == round(tr["rate_rps"] * window) >= 45
    prompts = sorted(len(r["tokens"]) for r in a)
    assert prompts[0] >= 512 and 2800 < prompts[len(prompts) // 2] < 3400 and prompts[-1] == 12288
    assert 3600 < sum(prompts) / len(prompts) < 4400
    assert all(len(r["tokens"]) + r["max_new_tokens"] <= doc["max_position_embeddings"] for r in a)
    assert all(16 <= r["max_new_tokens"] <= 384 for r in a)


def test_rehearsal_widths_settle_into_a_consistent_tiny_model():
    doc = dict(cells.load_config(CONFIG))
    doc.update(bench_run.TINY)
    cfg = fam.program_config(doc)
    assert (cfg.d_model, cfg.q_rank, cfg.kv_rank, cfg.d_expert, cfg.n_layers, cfg.n_dense_layers) == (
        64, 32, 32, 32, 2, 1)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k, cfg.hc_mult) == (64, 64, 4, 4)
    assert doc["q_lora_rank"] == 32 and doc["num_dense_layers"] == 1  # the reference reads the same file
    params = fam.make_params(doc, 3)
    assert params["layers"][0]["hc_attn"]["phi"].shape == (24, 256)
    assert params["layers"][1]["router_bias"].dtype.name == "float32"
    assert not params["layers"][1]["router_bias"].any()  # zero, with its reason
    assert fam.n_params(doc) == sum(x.size for x in __import__("jax").tree.leaves(params))


def test_the_new_readers_return_nothing_without_their_events():
    """On the parent's program, on another family's run and on a slice that
    noted no step the readers find nothing to read and do not raise."""
    del fam.STEPS[:]
    run = {"config": {}, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.0, "t1": 2.0},
           "trace": {"module_runs_s": {"jit_ragged_program": [0.01]}, "device_ops": [],
                     "busy_s": 0.5}, "peaks": PEAKS}
    for name in NEW_READERS:
        reader = cells.load_reader(name)
        assert reader.read(run) is None
        assert reader.read({**run, "slice": {}, "trace": {}, "peaks": None}) is None
    # A.X-K1's noted steps and trace are nothing to read either
    fam.STEPS.append({"at": 1.5, "rows": [(4, 0, 1)], "counters": {
        "moe_assignments": 8, "moe_assignments_here": 2, "moe_experts_touched": 2}})
    other = {**run, "config": cells.load_config("a.x-k1-ep16"),
             "trace": {**run["trace"], "device_ops": [["latent_walk bf16[32,256,512]", 0.1]]}}
    assert [cells.load_reader(name).read(other) for name in NEW_READERS] == [None] * 3
    del fam.STEPS[:]
    mine = {**run, "config": cells.load_config(CONFIG),
            "trace": {**run["trace"], "device_ops": [["mhc_open f32[256,3584]", 0.1]]}}
    # ONE of the two kernels among the ten heaviest is a partial sum: nothing is read
    assert cells.load_reader("mhc_busy_share").read(mine) is None
    mine["trace"]["device_ops"].append(["mhc_close f32[256,14336]", 0.1])
    assert cells.load_reader("mhc_busy_share").read(mine) == pytest.approx(40.0)
    assert cells.load_reader("mhc_roofline_share").read(mine) is None  # no step in the slice
    assert cells.load_reader("xing_step_roofline_share").read(mine) is None


def test_readers_read_the_noted_steps_of_a_synthetic_trace_reduction():
    del fam.STEPS[:]
    doc = dict(cells.load_config(CONFIG))
    decode = [(1, 2000 + 300 * i, 1) for i in range(6)]
    chunk = decode + [(224, 4096, 0)]
    noted = lambda at, rows, touched: {  # noqa: E731
        "at": at, "rows": rows, "slots_computed": 512, "slots_live": 230,
        "counters": {"moe_assignments": 20 * sum(n for n, _, _ in rows),
                     "moe_assignments_here": 20 * sum(n for n, _, _ in rows),
                     "moe_experts_touched": 5 * touched, "moe_max_expert_load": 5 * 20,
                     "mhc_slots": 240 * 12, "mhc_live": 12 * sum(n for n, _, _ in rows)}}
    fam.STEPS.extend([noted(1.5, decode, 20), noted(1.7, chunk, 64), noted(99.0, decode, 20)])
    run = {"config": doc, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.4, "t1": 1.9},
           "trace": {"module_runs_s": {"jit_ragged_program(1)": [0.009, 0.015]}, "busy_s": 0.4,
                     "device_ops": [["expert_mlp f32[960,3584]", 0.012],
                                    ["mhc_close f32[240,14336]", 0.0012],
                                    ["mhc_open f32[240,3584]", 0.0008], ["while s32[]", 0.004]]},
           "peaks": PEAKS}
    read = lambda name: cells.load_reader(name).read(run)  # noqa: E731
    assert read("mhc_busy_share") == pytest.approx(100.0 * 0.002 / 0.4)
    maps = [roofline_xing.mhc_least_seconds(doc, s["rows"], PEAKS) for s in fam.STEPS[:2]]
    # six decode rows wait on the 12 phi of 0.69 MB, a chunk on its operations
    # (862080 a live token and sublayer); the stream's bytes stay on the chip
    assert [bound for _, bound in maps] == ["bandwidth", "flops"]
    assert maps[0][0] == pytest.approx(12 * 688128 / PEAKS["hbm_bytes_per_s"])
    assert maps[1][0] == pytest.approx(12 * 862080 * 230 / PEAKS["bf16_flops"])
    assert read("mhc_roofline_share") == pytest.approx(
        100.0 * (sum(t for t, _ in maps) / 2) / (0.002 / 2))
    assert 0 < read("mhc_roofline_share") < 100
    least = [roofline_xing.step_least_seconds(doc, s["rows"], s["counters"], PEAKS)
             for s in fam.STEPS[:2]]
    assert [bound for _, bound in least] == ["bandwidth", "bandwidth"]
    # by the bytes alone: about 4.6 ms with 20 of 64 experts a layer, 10.9 ms with all 64
    assert 0.004 < least[0][0] < 0.0055 and 0.0100 < least[1][0] < 0.0118
    assert read("xing_step_roofline_share") == pytest.approx(
        100.0 * (sum(t for t, _ in least) / 2) / 0.012)
    assert read("xing_step_roofline_share") < 100
    assert read("moe_here_share") == 100.0 and read("walk_live_slot_share") == pytest.approx(100 * 230 / 512)
    assert read("mla_walk_busy_share") == pytest.approx(0.5)
    assert 0 < read("moe_grouped_roofline_share") < 100
    del fam.STEPS[:]


TINY_DOC = {"hidden_size": 8, "intermediate_size": 12, "moe_intermediate_size": 4,
            "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_attention_heads": 2,
            "q_lora_rank": 6, "kv_lora_rank": 4, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
            "v_head_dim": 4, "vocab_size": 32, "num_experts_routed": 16, "n_shared_experts": 1,
            "hc_mult": 2, "hc_sinkhorn_iters": 3}


def test_roofline_counts_equal_a_hand_count():
    doc = TINY_DOC
    rows = [(3, 7, 1), (1, 2, 1)]  # 4 live tokens
    # two streams of 8: 8 numbers of maps a token over 16 of stream; 6 sublayers
    per_token_flops = 2 * 16 * 8 + 2 * 16 + 2 * 4 * 8 + 2 * 16 + 3 * 2 * 3 * 4
    assert roofline_xing.mhc_flops(doc, rows) == 6 * per_token_flops * 4
    # from HBM: each sublayer's phi (8 rows x 16, bf16) once; the stream stays on the chip
    assert roofline_xing.mhc_bytes(doc, rows) == 6 * 8 * 16 * 2
    assert roofline_xing.mhc_bytes(doc, []) == 0
    counters = {"moe_assignments_here": 7, "moe_experts_touched": 3}
    assert roofline_xing.step_flops(doc, rows, 7) == (
        roofline_mla.step_flops(doc, rows, 7) + roofline_xing.mhc_flops(doc, rows))
    assert roofline_xing.step_bytes(doc, rows, 3) == (
        roofline_mla.step_bytes(doc, rows, 3) + roofline_xing.mhc_bytes(doc, rows))
    assert roofline_xing.step_least_seconds(doc, rows, counters, {
        "bf16_flops": 1e15, "hbm_bytes_per_s": 1.0}) == (roofline_xing.step_bytes(doc, rows, 3),
                                                         "bandwidth")
    # at the published widths the count holds every parameter of the file but the norms,
    # the maps' 27 scalars a sublayer and the selection bias
    full = cells.load_config(CONFIG)
    norms = 6 * (2 * 3584 + 768 + 512) + 3584
    maps = 12 * 24 * 14336
    assert (roofline_mla.unrouted_params(full) + 5 * 64 * roofline_mla.expert_params(full)
            + 2 * 131072 * 3584 + norms + maps + 12 * 27 + 5 * 64) == fam.n_params(dict(full))
    assert roofline_xing.mhc_bytes(full, [(1, 0, 1)]) == 12 * 688128


def test_a_rehearsal_of_the_new_cell_reaches_its_last_line_and_agrees_with_the_reference():
    """The cell's own control flow on the CPU at tiny widths, its pool and
    prompts cut so that the CPU (which copies the whole arena every step)
    ends them inside the drain: the gateway, the tap, the readers, the check
    against the plain reference (bf16 there as on the chip: the gaps are held
    to the file's limits, not to 0)."""
    cell = cells.resolve(CELL)
    cell.config = {**cell.config, "max_position_embeddings": 2048,
                   "pool": {**cell.config["pool"], "pages": 2048, "max_new_tokens": 24}}
    cell.traffic = {**cell.traffic,
                    "prompt_tokens": {"dist": "uniform", "min": 100, "max": 700},
                    "new_tokens": {"dist": "uniform", "min": 8, "max": 24}}
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 49, seconds=6.0, trace=1,
                              rehearse=True, rate=1.5, control=0)
    out = asyncio.run(bench_run.run_cell(args, cell))
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 9
    assert out["device"]["platform"] == "cpu"
    assert out["metrics"]["moe_here_share"]["value"] == 100.0
    assert fam.STEPS and all(s["counters"]["mhc_live"] == 4 * sum(n for n, _, _ in s["rows"])
                             for s in fam.STEPS)
    for name in ("moe_experts_touched_share", "moe_load_imbalance", "walk_live_slot_share",
                 "step_cycle_ms", "batch_occupancy", "setup_state_s"):
        assert out["metrics"][name]["value"] > 0, name
    for name in (*NEW_READERS, "moe_grouped_roofline_share", "mla_walk_busy_share",
                 "step_roofline_share", "hbm_peak_gb"):
        assert name not in out["metrics"]  # nothing ran on a device here
