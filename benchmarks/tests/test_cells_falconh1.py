"""The ``falcon_h1`` family's cell: it resolves through files found BY NAME
(so a later cell does not fail it), its configuration holds every key of the
catalog row unchanged but the three cuts of scale, the pool follows the house
rule, the traffic file's burst arithmetic holds, its three readers return
nothing on a run without their events and read a synthetic trace reduction,
the family's roofline count equals a hand count, and a rehearsal reaches its
last line with the reference agreeing with the program at tiny widths."""
import argparse
import asyncio
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.families import falcon_h1 as fam
from benchmarks.harness import cells, roofline, roofline_falconh1, traffic

BENCH = cells.load_benchmark()
CELL = "falconh1-chatbursts-open"
CONFIG = "falcon-h1-34b-pp8"
TRAFFIC = "chatbursts-open"
NEW_READERS = ("falconh1_step_roofline_share", "ssd_roofline_share", "ssd_busy_share")
SHARED_READERS = ("step_cycle_ms", "step_assemble_ms", "step_feed_ms", "step_wait_ms",
                  "step_emit_ms", "step_host_share", "setup_compute_s", "setup_state_s",
                  "setup_trace_lower_s", "setup_load_s", "setup_first_step_s", "setup_serving_s",
                  "setup_cache_hit_share", "state_slots_held_share")
#: what a cut to one chip may change (guide, section 4); every other key is a width or a rule
MAY_BE_REDUCED = {"num_hidden_layers", "vocab_size", "max_position_embeddings"}
#: the catalog row's ``config`` (architectures.jsonl, ``Falcon-H1-34B-Instruct``), whole
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None, "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
    "mamba_n_heads": 32, "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284], "model_type": "falcon_h1",
    "num_attention_heads": 20, "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}


def test_the_cell_resolves_and_reports_what_the_contract_asks():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "falcon_h1"
    cell.family.validate(dict(cell.config))
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"setup_s", "tokens_per_s", "tpot_p95_ms"}  # TTFT: PERF.md section 7
    assert {m["moves"] for m in cell.per_layer} <= reported
    assert set(cell.readers) == {m["name"] for m in cell.per_layer} >= set(NEW_READERS + SHARED_READERS)
    row = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert (row["config"], row["traffic"], row["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(row["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["source"] == ("https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
                               "config.json")
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        elif m["name"] in SHARED_READERS:
            assert CELL in m["workloads"]
    # no expert layer, no latent walk, no other family's count is asked of this cell
    assert not {"moe_here_share", "moe_experts_roofline_share", "moe_grouped_roofline_share",
                "walk_live_slot_share", "bailing_step_roofline_share", "kda_roofline_share",
                "kda_busy_share", "prefix_hit_token_share"} & set(cell.readers)
    assert "step_roofline_share" in cell.readers  # it has no list: read in every cell


def test_every_published_key_is_kept_and_reduced_names_cuts_of_scale():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    doc = json.load(open(os.path.join(cells.REPO_ROOT, entry["file"])))
    assert doc["reduced"] == entry["reduced"] and doc["source"] == entry["source"]
    assert set(doc["reduced"]) == MAY_BE_REDUCED
    for key, value in PUBLISHED.items():
        if key in MAY_BE_REDUCED:
            assert doc["source_values"][key] == value != doc[key] and key in doc["reduced_why"]
        else:
            assert doc[key] == value and type(doc[key]) is type(value), key
    # the floors of a cut: four layers of a period of one, an eighth of the vocabulary
    assert doc["num_hidden_layers"] == 9 == len(doc["kept_layers"]) and doc["kept_layers"][0] == 0
    assert doc["vocab_size"] * 8 == doc["source_values"]["vocab_size"]
    assert doc["num_hidden_layers"] * 8 == doc["source_values"]["num_hidden_layers"]
    assert fam.n_params(dict(doc)) == 4_205_319_008
    assert {"gap_mean_limit", "gap_max_limit", "derivation", "sample_tokens", "state"} <= set(
        doc["check"])
    assert {"in_proj_spans", "conv", "dt_limits", "recurrence", "gated_norm", "attention",
            "ssm_init", "weights"} <= set(doc["assumed"])
    assert "eight chips" in doc["deployment"] and "pipeline stage" in doc["deployment"]
    assert "no compile inside the window" in doc["guarantees"]
    pool = doc["pool"]
    assert not any(pool[k] for k in ("speculative", "prefix_cache", "hibernation", "migration"))
    assert pool["pages"] == pool["max_sessions"] * doc["max_position_embeddings"] // pool["page_size"]
    assert (pool["max_sessions"], pool["prefill_budget"], pool["max_new_tokens"]) == (80, 128, 512)
    cfg = fam.program_config(dict(doc))
    assert (cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff) == (
        9, 20, 4, 128, 21504)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.conv_dim) == (
        32, 128, 256, 2, 5120)
    # what a session holds: 9 x 4 MiB of state and its tails, 18.4 KB of K and V a position
    spec = cfg.serving_spec()
    assert not spec.kv_positional and spec.kv_by_head and spec.kv_whole_row
    assert roofline_falconh1.state_elements(doc) * 4 == 4 * 1024 * 1024
    assert 9 * (4 * 1024 * 1024 + roofline_falconh1.tail_bytes(doc)) == 38_025_216


def test_the_weights_are_drawn_at_the_spread_the_multipliers_are_made_for():
    """The family module's spreads are the program's own rule, stated twice
    (``models/falcon_h1.py`` ``spread``), and give every branch unit gain."""
    from cordum_tpu.models import falcon_h1 as model

    doc = dict(cells.load_config(CONFIG))
    mine, theirs = fam.spreads(doc), model.spread(fam.program_config(doc))
    for name in ("embed", "wo", "w_out", "w_gate", "w_up", "w_down", "lm_head"):
        assert mine[name] == pytest.approx(theirs[name])
    assert mine["w_qkv"] == pytest.approx((theirs["w_q"], theirs["w_k"], theirs["w_q"]))
    for std, m in zip(mine["w_in"], doc["ssm_multipliers"]):
        assert std * doc["ssm_in_multiplier"] * m * 5120 ** 0.5 == pytest.approx(1.0)
    assert mine["lm_head"] * doc["lm_head_multiplier"] * 5120 ** 0.5 == pytest.approx(1.0)
    assert mine["w_qkv"][1] * doc["key_multiplier"] * 5120 ** 0.5 == pytest.approx(1.0)


def test_the_traffic_is_bursts_whose_schedule_does_not_move_with_the_seed():
    tr = cells.load_traffic(TRAFFIC)
    arr = tr["arrivals"]
    assert (tr["loop"], arr["process"], arr["size"]) == ("open", "bursts", 24)
    assert tr["sessions"]["turns"] == [1, 1] and tr["schedule_seed"] == 42
    assert tr["prompt_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 1.0,
                                   "min": 32, "max": 1024}
    assert tr["new_tokens"] == {"dist": "lognormal", "median": 96, "sigma": 0.6,
                                "min": 16, "max": 512}
    period, knee = arr["period_s"], tr["knee"]
    # the committed period offers 0.8 of the knee's rate; every line of the sweep is kept
    assert tr["rate_rps"] == pytest.approx(24 / period)
    assert 24 / period == pytest.approx(0.8 * 24 / knee["period_s"], rel=0.03)
    assert len(knee["sweep"]) >= 4 and all(
        {"period_s", "seconds", "requests", "in_flight_at_bursts", "tokens_per_s"} <= set(p)
        for p in knee["sweep"])
    window = BENCH["run_seconds"]
    kw = dict(seconds=window, vocab=32640, context=1536, max_new_cap=512)
    a, b = traffic.generate(tr, seed=1, **kw), traffic.generate(tr, seed=2 ** 31 + 5, **kw)
    shape = lambda rs: [(len(r["tokens"]), r["max_new_tokens"], r["due_s"]) for r in rs]  # noqa: E731
    assert shape(a) == shape(b) and [r["tokens"] for r in a] != [r["tokens"] for r in b]
    bursts = int(window // period)
    assert len(a) == 24 * bursts == knee["requests_a_window"]
    dues = sorted({r["due_s"] for r in a})
    assert dues == pytest.approx([i * period for i in range(bursts)])
    assert all(sum(1 for r in a if r["due_s"] == d) == 24 for d in dues)
    # the last burst is at least one burst-absorption time before the close
    assert window - dues[-1] >= knee["absorb_s"] > 0
    assert all(32 <= len(r["tokens"]) <= 1024 and 16 <= r["max_new_tokens"] <= 512 for r in a)
    assert all(len(r["tokens"]) + r["max_new_tokens"] <= 1536 for r in a)
    assert all(1 <= t < 32640 for r in a for t in r["tokens"])
    prompts = sorted(len(r["tokens"]) for r in a)
    assert 170 < prompts[len(prompts) // 2] < 215  # median 192
    per_burst = [sum(len(r["tokens"]) for r in a if r["due_s"] == d) for d in dues]
    assert 4000 < min(per_burst) and max(per_burst) < 10000  # about 7k prompt tokens a burst


def test_rehearsal_widths_settle_into_a_consistent_tiny_model():
    doc = dict(cells.load_config(CONFIG))
    doc.update(bench_run.TINY)
    cfg = fam.program_config(doc)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers) == (
        64, 4, 2, 16, 128, 2)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups) == (8, 8, 16, 2)
    assert doc["mamba_d_ssm"] == 64 and doc["kept_layers"] == [0, 1]  # the reference reads the file
    assert cfg.key_multiplier == PUBLISHED["key_multiplier"]  # the multipliers stay as published


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_without_its_events(name):
    """On the parent's program, on another family's run and on a trace without
    the kernel the readers find nothing to read and do not raise."""
    del fam.STEPS[:]
    run = {"config": {}, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.0, "t1": 2.0},
           "trace": {"module_runs_s": {"jit_ragged_program": [0.01]}, "busy_s": 1.0,
                     "device_ops": [["while s32[]", 0.5]]},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    reader = cells.load_reader(name)
    assert reader.read(run) is None
    assert reader.read({**run, "slice": {}, "trace": {}, "peaks": None}) is None
    # another family's noted steps with state slots (Ling's) are nothing to read either
    fam.STEPS.append({"at": 1.5, "rows": [(4, 0, 1)], "counters": {"moe_assignments": 8},
                      "state_slots": 3, "state_slots_total": 64})
    assert reader.read({**run, "config": cells.load_config("ling-3.0-flash-ep4")}) is None
    if name != "ssd_busy_share":  # this family's file, but no step noted in the slice
        del fam.STEPS[:]
        assert reader.read({**run, "config": cells.load_config(CONFIG)}) is None
    del fam.STEPS[:]


def noted(at, rows, slots=40):
    return {"at": at, "rows": rows,
            "counters": {"state_rows_advanced": len(rows), "state_rows_fresh": 0,
                         "state_tokens_scanned": sum(n for n, _, _ in rows)},
            "state_slots": slots, "state_slots_total": 80}


def test_readers_read_the_noted_steps_of_a_synthetic_trace_reduction():
    del fam.STEPS[:]
    decode = [(1, 300 + i, 1) for i in range(30)]
    fam.STEPS.extend([noted(1.2, [(128, 0, 0)]), noted(1.5, decode, slots=52),
                      noted(1.7, decode + [(100, 128, 1)], slots=61),
                      noted(99.0, [(1, 0, 1)], slots=80)])  # after the window
    doc = dict(cells.load_config(CONFIG))
    run = {"config": doc, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.4, "t1": 1.9},
           "trace": {"module_runs_s": {"jit_ragged_program(1)": [0.018, 0.022]}, "busy_s": 0.4,
                     "device_ops": [["fusion f32[208]", 0.2], ["ssd_step f32[208,32,128]", 0.012]]},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: cells.load_reader(name).read(run)  # noqa: E731
    steps = [decode, decode + [(100, 128, 1)]]
    least = [roofline_falconh1.step_least_seconds(doc, rows, run["peaks"]) for rows in steps]
    assert [bound for _, bound in least] == ["bandwidth", "bandwidth"]
    assert read("falconh1_step_roofline_share") == pytest.approx(
        100.0 * (sum(t for t, _ in least) / 2) / 0.020)
    ssd_least = [roofline_falconh1.ssd_least_seconds(doc, rows, run["peaks"])[0] for rows in steps]
    assert read("ssd_roofline_share") == pytest.approx(100.0 * (sum(ssd_least) / 2) / (0.012 / 2))
    assert read("ssd_busy_share") == pytest.approx(100.0 * 0.012 / 0.4)
    assert read("state_slots_held_share") == pytest.approx(100.0 * 61 / 80)
    # a decode-only step at these widths: 30 rows' 8.4 MB of state a layer is a quarter of
    # the 8.1 GB of weights, and the shares stay under 100 at any plausible device time
    t30 = least[0][0]
    assert 0.0125 < t30 < 0.0135 and read("ssd_roofline_share") < 100
    # the dense count that step_roofline_share makes of this file reads under the family's own
    rr = [roofline.Row(n=n, start=s, head=h) for n, s, h in decode]
    assert roofline.least_seconds(doc, rr, run["peaks"])[0] < 0.75 * t30
    del fam.STEPS[:]


TINY_DOC = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 3,
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2, "vocab_size": 32,
            "mamba_d_ssm": 12, "mamba_n_heads": 3, "mamba_d_head": 4, "mamba_d_state": 5,
            "mamba_n_groups": 1, "mamba_d_conv": 4}


def test_roofline_counts_equal_a_hand_count():
    doc = TINY_DOC
    # W_qkv 8 x (8 + 4 + 4), Wo 8 x 8, W_in 8 x (12 + 12 + 5 + 5 + 3), W_out 12 x 8, FFN 3 x 8 x 16
    matmul = 128 + 64 + 8 * 37 + 96 + 384
    small = 5 * 22 + 3 * 3 + 12 + 2 * 8  # taps and bias over 22 channels, A / dt / D, three norms
    assert roofline_falconh1.conv_dim(doc) == 22 and roofline_falconh1.in_width(doc) == 37
    assert roofline_falconh1.layer_matmul_params(doc) == matmul
    assert roofline_falconh1.layer_small_params(doc) == small
    assert roofline_falconh1.state_elements(doc) == 60
    # a chunk of 3 tokens fed from position 7, a decode row at position 2, a row that feeds nothing
    rows = [(3, 7, 1), (1, 2, 1), (0, 0, 0)]
    assert roofline_falconh1.ssd_flops(doc, rows) == 5 * 60 * 3 * 4
    # two fed rows: 60 floats in and out a layer; a token: x 12, y 12, B 5, C 5, dt 3 floats
    ssd_bytes = 3 * (2 * 60 * 4 * 2 + 37 * 4 * 4)
    assert roofline_falconh1.ssd_bytes(doc, rows) == ssd_bytes
    # the chunk's tokens see 8, 9, 10 keys, the decode row 3: 4 x heads x head_dim a key and layer
    attn = 4 * 4 * 2 * 3 * (27 + 3)
    assert roofline_falconh1.attention_flops(doc, rows) == attn
    # K and V: 2 x 2 heads x 2 x 2 B a position and layer; read to 10 and 3, 4 written
    kv = 16 * 3 * (10 + 3) + 16 * 3 * 4
    assert roofline_falconh1.kv_bytes(doc, rows) == kv
    flops = 2 * matmul * 3 * 4 + attn + 5 * 60 * 3 * 4 + 2 * 8 * 32 * 2
    assert roofline_falconh1.step_flops(doc, rows) == flops
    tails = 2 * (3 * 22 * 2) * 3 * 2  # each fed row's tail read and written a layer
    nbytes = ((matmul + small) * 3 + 8) * 2 + 8 * 32 * 2 + 4 * 8 * 2 + ssd_bytes + tails + kv
    assert roofline_falconh1.step_bytes(doc, rows) == nbytes
    assert roofline_falconh1.step_least_seconds(
        doc, rows, {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e9}) == (flops / 1e3, "flops")
    assert roofline_falconh1.step_least_seconds(
        doc, rows, {"bf16_flops": 1e15, "hbm_bytes_per_s": 1.0}) == (nbytes, "bandwidth")
    # at the published widths: 430.1 M parameters a layer, 4 MiB of state, 75.5 MB a decode row
    full = cells.load_config(CONFIG)
    assert roofline_falconh1.layer_matmul_params(full) == 430_080_000
    assert (roofline_falconh1.layer_matmul_params(full) + roofline_falconh1.layer_small_params(full)
            ) * 9 + 2 * 32640 * 5120 + 5120 == fam.n_params(dict(full))
    assert roofline_falconh1.ssd_bytes(full, [(1, 5, 1)]) == 9 * (2 * 4 * 1024 * 1024 + 36_992)
    # the dense reader's count of this file: 361.8 M of a layer's 430.1 M, and no state
    assert roofline.layer_matmul_params(full) == 361_758_720


def test_a_rehearsal_of_the_new_cell_reaches_its_last_line_and_agrees_with_the_reference():
    """The cell's own control flow on the CPU at tiny widths: bursts of 24
    through the gateway, the tap, the readers, the check against the plain
    reference (the program runs in bf16 there as on the chip, so the gaps are
    held to the file's limits, not to 0)."""
    cell = cells.resolve(CELL)
    cell.traffic = {**cell.traffic, "arrivals": {**cell.traffic["arrivals"], "period_s": 4.0},
                    "new_tokens": {"dist": "uniform", "min": 8, "max": 24}}
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 42, seconds=8.0, trace=1,
                              rehearse=True, rate=0.0, control=0)
    out = asyncio.run(bench_run.run_cell(args, cell))
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 48
    assert out["device"]["platform"] == "cpu"
    for name in ("state_slots_held_share", "step_cycle_ms", "batch_occupancy", "setup_state_s"):
        assert out["metrics"][name]["value"] > 0, name
    for name in (*NEW_READERS, "step_roofline_share", "hbm_peak_gb"):
        assert name not in out["metrics"]  # nothing ran on a device here
