"""The ``bailing`` family's cell: it resolves through files found by name, its
configuration holds every number of the catalog's ``config`` and keeps to the
model-configs guide's rule for ``reduced`` (depth, experts held, vocabulary,
context, the unloaded prediction block and ONE count for a reader; never a
width), its readers return nothing on a run without their counters, the
family's roofline count equals a hand count, and a rehearsal reaches its last
line with the reference agreeing with the program at tiny widths."""
import argparse
import asyncio
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.families import bailing as fam
from benchmarks.harness import cells, roofline_bailing

BENCH = cells.load_benchmark()
CELL = "ling3-reasoning-open"
CONFIG = "ling-3.0-flash-ep4"
NEW_READERS = ("bailing_step_roofline_share", "kda_roofline_share", "kda_busy_share",
               "state_slots_held_share")
SHARED_READERS = ("moe_here_share", "moe_experts_touched_share", "moe_load_imbalance",
                  "moe_experts_roofline_share", "walk_live_slot_share",
                  "step_cycle_ms", "step_assemble_ms", "step_feed_ms", "step_wait_ms",
                  "step_emit_ms", "step_host_share", "setup_compute_s", "setup_state_s",
                  "setup_trace_lower_s", "setup_load_s", "setup_first_step_s", "setup_serving_s",
                  "setup_cache_hit_share")
#: what this cut changes (guide, section 4): scale, never a width
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings",
           "num_nextn_predict_layers"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def published() -> dict:
    """The catalog row's ``config``, whole (skipped where the catalog is not
    installed beside the repo)."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "Ling-3.0-flash":
            return row["config"]
    raise AssertionError("the catalog has no Ling-3.0-flash row")


def test_the_cell_resolves_and_reports_what_the_contract_asks():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "bailing"
    cell.family.validate(dict(cell.config))
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"setup_s", "tokens_per_s", "tpot_p95_ms"}
    assert {m["moves"] for m in cell.per_layer} <= reported
    assert set(cell.readers) == {m["name"] for m in cell.per_layer} >= set(NEW_READERS + SHARED_READERS)
    row = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert (row["config"], row["traffic"], row["chips"]) == (CONFIG, "reasoning-open", 1)
    assert len(row["why"]) <= 200 and len(entry["why"]) <= 200
    assert BENCH["workloads"][-1] is row and BENCH["configs"][-1] is entry  # appended, not inserted
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
        elif m["name"] in SHARED_READERS:
            assert m["workloads"][-1] == CELL
    # the two walk readers read half and twice the truth since PR 39 (PERF.md section 7), and
    # roofline_mla.py knows one latent attention in every layer: none is asked of this cell
    assert not {"mla_walk_busy_share", "mla_walk_roofline_share", "mla_step_roofline_share",
                "afmoe_step_roofline_share", "longcat_step_roofline_share",
                "prefix_hit_token_share", "kv_window_held_share"} & set(cell.readers)


def test_every_published_number_is_kept_and_reduced_names_cuts_of_scale():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    doc = json.load(open(os.path.join(cells.REPO_ROOT, entry["file"])))
    assert doc["reduced"] == entry["reduced"] and doc["source"] == entry["source"]
    assert set(doc["reduced"]) == REDUCED == set(doc["source_values"]) == set(doc["reduced_why"])
    for key, value in published().items():
        if key in REDUCED:
            assert doc["source_values"][key] == value != doc[key]
        else:
            assert doc[key] == value and type(doc[key]) is type(value), key
    # no width among the cuts
    assert not any(k.endswith(("_dim", "_rank")) or "hidden" in k.replace("num_hidden", "")
                   or "intermediate" in k or k == "num_experts_per_tok" for k in REDUCED)
    # the floors of a cut: a whole period and four layers behind the dense one, 8+ experts, an
    # eighth of the vocabulary
    kinds = fam.layer_kinds(doc)
    assert [k for k, _ in kinds] == ["kda"] * 6 + ["mla"] and [d for _, d in kinds] == [True] + [False] * 6
    assert doc["kept_layers"][1:] == list(range(6, 12)) and doc["num_hidden_layers"] == 7
    assert doc["num_experts"] >= 8 and doc["vocab_size"] * 8 >= doc["source_values"]["vocab_size"]
    assert doc["vocab_size"] * 4 == doc["source_values"]["vocab_size"]
    assert doc["num_experts"] * 4 == doc["num_experts_routed"] == doc["source_values"]["num_experts"]
    assert doc["num_dense_layers"] == 1
    assert all(doc["expert_swiglu_limit_list"][i] == 0 == doc["share_expert_swiglu_limit_list"][i]
               for i in doc["kept_layers"])
    # the file's bytes are the program's: n_params from the shapes the program reads
    assert fam.n_params(dict(doc)) == 5_169_366_976 and "5,169,366,976" in doc["bytes"]
    assert {"gap_mean_limit", "gap_max_limit", "derivation", "sample_tokens", "decay"} <= set(doc["check"])
    assert {"layer_rule", "kda", "decay", "delta_rule", "mla", "rope_pairing", "router",
            "swiglu_limit", "no_effect", "norms", "weights"} <= set(doc["assumed"])
    assert "four chips" in doc["deployment"]
    # the cell's comparison cannot tell a bfloat16 state from a float32 one (check.state says
    # why), so the file does not list the state's precision among what a run guarantees
    assert "float32" not in " ".join(doc["guarantees"]) and "control_bailing.py state" in doc["check"]["state"]
    pool = doc["pool"]
    assert pool["prefix_cache"] is False and pool["speculative"] is False
    assert pool["pages"] == pool["max_sessions"] * doc["max_position_embeddings"] // pool["page_size"]
    assert pool["max_sessions"] + pool["prefill_budget"] == 128
    cfg = fam.program_config(dict(doc))
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k, cfg.n_group, cfg.topk_group) == (
        512, 128, 8, 8, 4)
    assert (cfg.kda_layers, cfg.mla_layers, cfg.dense_layers) == ((0, 1, 2, 3, 4, 5), (6,), (0,))
    assert (cfg.kda_dk, cfg.kda_dv, cfg.latent_dim, cfg.latent_width) == (128, 128, 576, 640)


def scheduled_steps(tr: dict, doc: dict, cycle_s: float = 0.0178) -> list:
    """``(seconds into the window, [(n, start, head)])`` of every step of the
    committed schedule, by the engine's rule at a fixed cycle (the chip's
    median: PERF.md section 5): decode rows first, then the prompts in order
    of arrival out of the prefill budget."""
    from benchmarks.harness import traffic

    pool = doc["pool"]
    due = [(r["due_s"], len(r["tokens"]), r["max_new_tokens"]) for r in traffic.generate(
        tr, seed=1, seconds=51, vocab=doc["vocab_size"], context=doc["max_position_embeddings"],
        max_new_cap=pool["max_new_tokens"])]
    steps, prefill, decode, t = [], [], [], 0.0
    while t < 51:
        while due and due[0][0] <= t:
            _, p, n = due.pop(0)
            prefill.append([p, 0, n])
        rows, budget, first = [(1, p + made, 1) for p, made, _ in decode], pool["prefill_budget"], []
        for r in prefill:
            take = min(budget, r[0] - r[1])
            if take <= 0:
                break
            rows.append((take, r[1], int(r[1] + take == r[0])))
            r[1] += take
            budget -= take
        decode = [[p, made + 1, n] for p, made, n in decode if made + 1 < n]
        decode += [[p, 1, n] for p, fed, n in prefill if fed == p]
        prefill = [r for r in prefill if r[1] < r[0]]
        steps.append((t, rows))
        t += cycle_s
    return steps


def test_the_step_roofline_reader_counts_a_cache_this_model_does_not_keep():
    """``step_roofline_share`` is read in every cell and counts a dense
    grouped-query layer from the PUBLISHED keys (32 key heads of 128, one FFN
    of 6144 in all seven layers): 114,688 B a cached position where the
    program holds 1,280 B in one latent layer.  On the committed schedule's
    steps (the same for every ``--seed``) its least time stays under 6 ms in
    the traced slice and under 9 ms anywhere in the window, against a device
    step whose mean over the slice is 12-17 ms (PERF.md section 5: it read
    28.3 % at the committed rate): under 100.
    A step of the deepest rows the pool allows would read far over it: the
    traffic keeps the share under 100, not the count."""
    from benchmarks.harness import roofline

    doc, tr = cells.load_config(CONFIG), cells.load_traffic("reasoning-open")
    assert doc["num_key_value_heads"] == 32 and doc["intermediate_size"] == 6144
    assert roofline.layer_matmul_params(doc) * 7 > 2 * roofline_bailing.unrouted_params(doc) / 3
    steps = scheduled_steps(tr, doc)
    assert 2700 < len(steps) < 3000 and max(len(rows) for _, rows in steps) <= 64
    least = lambda rows: roofline.least_seconds(  # noqa: E731
        doc, [roofline.Row(n=n, start=s, head=h) for n, s, h in rows], PEAKS)[0]
    in_slice = [least(rows) for at, rows in steps if 0.45 * 51 <= at < 0.45 * 51 + 3 and rows]
    assert 0.004 < sum(in_slice) / len(in_slice) < 0.006
    assert max(least(rows) for _, rows in steps if rows) < 0.009
    assert least([(1, 18000, 1)] * 64) > 0.1  # what the pool allows, and no traffic here sends


def test_the_traffic_is_one_turn_requests_whose_schedule_does_not_move_with_the_seed():
    from benchmarks.harness import traffic

    tr = cells.load_traffic("reasoning-open")
    assert tr["sessions"]["turns"] == [1, 1] and tr["loop"] == "open" and tr["schedule_seed"] == 40
    knee = tr["knee"]
    # the knee is the highest rate at which the requests in flight are SHOWN to level over 102 s,
    # with the committed weights (the fitted selection bias); the rate is 0.8 of it
    assert tr["rate_rps"] == pytest.approx(0.8 * knee["requests_per_s"])
    level = {(e["rate"], e["seconds"]): e["in_flight_half_close"] for e in knee["sweep"]}
    assert max(level)[0] == knee["requests_per_s"] and all(c <= h for h, c in level.values())
    drawn = knee["under_the_drawn_bias"]  # the first form's weights: kept as what was measured
    assert len(drawn["sweep"]) >= 9 and drawn["requests_per_s"] == 1.1
    kw = dict(seconds=51, vocab=39296, context=24576, max_new_cap=2048)
    a, b = traffic.generate(tr, seed=1, **kw), traffic.generate(tr, seed=2 ** 31 + 5, **kw)
    shape = lambda rs: [(len(r["tokens"]), r["max_new_tokens"], r.get("due_s")) for r in rs]  # noqa: E731
    assert shape(a) == shape(b) and [r["tokens"] for r in a] != [r["tokens"] for r in b]
    assert all(128 <= len(r["tokens"]) <= 16384 and 128 <= r["max_new_tokens"] <= 2048 for r in a)
    assert all(0 <= t < 39296 for r in b for t in r["tokens"])
    prompts = sorted(len(r["tokens"]) for r in a)
    assert 800 < prompts[len(prompts) // 2] < 1300 and prompts[-1] > 8000  # median 1024, a long tail


def test_rehearsal_widths_settle_into_a_consistent_tiny_model():
    doc = dict(cells.load_config(CONFIG))
    doc.update(bench_run.TINY)
    cfg = fam.program_config(doc)
    assert (cfg.d_model, cfg.n_heads, cfg.kda_dk, cfg.kda_dv, cfg.kv_rank, cfg.nope_dim,
            cfg.rope_dim, cfg.v_dim, cfg.d_ff, cfg.d_expert) == (64, 4, 16, 16, 32, 16, 8, 16, 128, 32)
    assert (cfg.layer_kinds, cfg.dense_layers, cfg.n_experts, cfg.experts_held) == (
        ("kda", "mla"), (0,), 64, 16)
    assert doc["kept_layers"] == [1, 5] and doc["num_dense_layers"] == 1  # the readers' and the reference's file


def test_the_selection_bias_is_fitted_to_even_shares_and_is_the_seeds():
    """``make_params`` fits the bias (``balance_routers``): on ids the fit
    never saw the experts' shares of the picks lie far nearer the even share
    than under a bias of zero, and the same seed gives the same bias."""
    import jax.numpy as jnp
    import numpy as np

    doc = dict(cells.load_config(CONFIG))
    doc.update(bench_run.TINY)
    fam.program_config(doc)  # settles the tiny widths in place
    params = fam.make_params(doc, 2 ** 31 + 7)
    again = fam.make_params(doc, 2 ** 31 + 7)
    ref = fam.reference.Reference(doc, 1024)
    li, w = next((i, w) for i, w in enumerate(params["layers"]) if "router" in w)
    assert np.array_equal(np.asarray(w["router_bias"]), np.asarray(again["layers"][li]["router_bias"]))
    assert w["router_bias"].dtype == jnp.float32 and float(jnp.std(w["router_bias"])) > 0
    x = ref.embed(params, np.random.default_rng(5).integers(1, doc["vocab_size"], 1024))
    for lj, wj in enumerate(params["layers"][:li]):
        x = ref.layer(x, wj, lj)
    m = ref.attention_part(x, w, li)[1]

    def spread(bias):
        sel = np.asarray(fam.reference.route(m, w["router"], bias, **ref.route_kw)[0])
        load = np.bincount(sel.ravel(), minlength=doc["num_experts_routed"])
        return load.std() / load.mean()
    assert spread(w["router_bias"]) < 0.5 * spread(jnp.zeros_like(w["router_bias"]))


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_without_its_counters(name):
    del fam.STEPS[:]
    run = {"config": {}, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.0, "t1": 2.0},
           "trace": {"module_runs_s": {"jit_ragged_program": [0.01]}, "busy_s": 1.0,
                     "device_ops": [["while s32[]", 0.5]]}, "peaks": PEAKS}
    assert cells.load_reader(name).read(run) is None
    assert cells.load_reader(name).read({**run, "slice": {}, "trace": {}, "peaks": None}) is None
    # another family's noted steps (no state slots) are nothing to read either, whatever the file
    fam.STEPS.append({"at": 1.5, "rows": [(4, 0, 1)],
                      "counters": {"moe_assignments": 8, "moe_assignments_here": 1,
                                   "moe_experts_touched": 1, "moe_max_expert_load": 1},
                      "window_blocks": 0, "full_blocks": 1, "window_pages": 0, "full_pages": 1})
    assert cells.load_reader(name).read({**run, "config": cells.load_config(CONFIG)}) is None
    assert cells.load_reader(name).read({**run, "config": cells.load_config("a.x-k1-ep16")}) is None
    del fam.STEPS[:]


def noted(at, rows, slots, **counters):
    base = {"moe_assignments": 96, "moe_assignments_here": 24, "moe_experts_touched": 20,
            "moe_max_expert_load": 3}
    return {"at": at, "rows": rows, "counters": {**base, **counters}, "window_blocks": 0,
            "full_blocks": 1, "window_pages": 0, "full_pages": 9, "slots_computed": 64,
            "slots_live": 16, "prefix_hit_tokens": 0, "prefill_tokens": 0, "prefix_hits": 0,
            "cow_copies": 0, "state_slots": slots, "state_slots_total": 64}


def test_readers_read_the_noted_steps():
    del fam.STEPS[:]
    fam.STEPS.extend([
        noted(1.2, [(64, 0, 0)], 9),
        noted(1.5, [(1, 10, 1), (1, 70, 1), (30, 64, 0)], 41),
        noted(99.0, [(1, 0, 1)], 64),  # after the window
    ])
    doc = dict(cells.load_config(CONFIG))
    run = {"config": doc, "t0": 0.0, "window_s": 10.0, "slice": {"t0": 1.4, "t1": 1.9},
           "trace": {"module_runs_s": {"jit_ragged_program(1)": [0.010, 0.012]}, "busy_s": 2.0,
                     "device_ops": [["fusion f32[128]", 0.9], ["kda_step f32[128,32,128]", 0.0016],
                                    ["while s32[]", 0.6]]},
           "peaks": PEAKS}
    read = lambda name: cells.load_reader(name).read(run)  # noqa: E731
    assert read("state_slots_held_share") == pytest.approx(100.0 * 41 / 64)
    rows = [(1, 10, 1), (1, 70, 1), (30, 64, 0)]
    (least, bound), = [roofline_bailing.step_least_seconds(
        doc, rows, noted(0, [], 0)["counters"], PEAKS)]
    assert bound == "bandwidth"
    assert read("bailing_step_roofline_share") == pytest.approx(100.0 * least / 0.011)
    assert read("kda_busy_share") == pytest.approx(100.0 * 0.0016 / 2.0)
    kda_least, kda_bound = roofline_bailing.kda_least_seconds(doc, rows, PEAKS)
    # three fed rows: their states read and written once in each of six KDA layers
    assert kda_bound == "bandwidth" and kda_least > 6 * 3 * 2 * 2_097_152 / 819e9
    assert read("kda_roofline_share") == pytest.approx(100.0 * kda_least / (0.0016 / 2))
    assert read("moe_here_share") == pytest.approx(100.0 * 48 / 192)
    assert read("walk_live_slot_share") == pytest.approx(25.0)
    del fam.STEPS[:]


TINY_DOC = {"hidden_size": 8, "intermediate_size": 16, "moe_intermediate_size": 4,
            "moe_shared_expert_intermediate_size": 4, "num_attention_heads": 2, "head_dim": 3,
            "short_conv_kernel_size": 4, "kv_lora_rank": 4, "qk_nope_head_dim": 3,
            "qk_rope_head_dim": 2, "v_head_dim": 3, "vocab_size": 32, "num_experts_routed": 24,
            "kept_layers": [1, 3, 4, 5], "layer_group_size": 6, "first_k_dense_replace": 2}


def test_roofline_counts_equal_a_hand_count():
    doc = TINY_DOC
    assert roofline_bailing.kinds(doc) == [("kda", True), ("kda", False), ("kda", False), ("mla", False)]
    # a KDA layer (c = 2 x 3 = 6): Wqkv 8 x 18, taps 4 x 18, Wa 8 x 6, A 2, b 6, Wb and Wg 8 x 2
    # each, the output norm 3, Wo 6 x 8
    kda = 144 + 72 + 48 + 2 + 6 + 32 + 3 + 48
    # the latent layer: Wq 8 x 2 x 5, Wkva 8 x 6, Wkvb 4 x 2 x 6, Wg 8 x 2, Wo 6 x 8
    mla = 80 + 48 + 48 + 16 + 48
    dense, expert, router_shared = 3 * 8 * 16, 3 * 8 * 4, 8 * 24 + 3 * 8 * 4
    unrouted = 3 * kda + mla + dense + 3 * router_shared
    assert (roofline_bailing.kda_params(doc), roofline_bailing.mla_params(doc)) == (kda, mla)
    assert roofline_bailing.expert_params(doc) == expert
    assert roofline_bailing.unrouted_params(doc) == unrouted
    # a row keeps a KDA layer 2 x 3 x 3 float32 of state and 3 x 18 bf16 of tail
    assert roofline_bailing.state_bytes(doc) == 72 + 108
    rows = [(3, 7, 1), (1, 2, 1), (0, 5, 0)]
    # 7 x d_k x d_v operations a token and head, three KDA layers, four tokens
    assert roofline_bailing.kda_flops(doc, rows) == 7 * 2 * 9 * 3 * 4
    # two FED rows' states read and written, a token's six [2, 3] float32 operands
    assert roofline_bailing.kda_bytes(doc, rows) == 3 * (2 * 180 * 2 + 6 * 6 * 4 * 4)
    # the one latent layer's walk: a slot against a key 2 heads x (6 + 4) x 2; 27 + 3 seen keys
    assert roofline_bailing.walk_flops(doc, rows) == 40 * 1 * (27 + 3)
    assert roofline_bailing.walk_bytes(doc, rows) == 6 * 2 * ((10 + 3 + 5) + 4)
    flops = 2 * unrouted * 4 + 2 * expert * 7 + 7 * 2 * 9 * 3 * 4 + 40 * 30 + 2 * 8 * 32 * 2
    assert roofline_bailing.step_flops(doc, rows, 7) == flops
    nbytes = ((unrouted + 5 * expert) * 2 + 8 * 32 * 2 + 4 * 8 * 2
              + 3 * (2 * 180 * 2 + 6 * 6 * 4 * 4) + 6 * 2 * 22)
    assert roofline_bailing.step_bytes(doc, rows, 5) == nbytes
    counters = {"moe_assignments_here": 7, "moe_experts_touched": 5}
    assert roofline_bailing.step_least_seconds(
        doc, rows, counters, {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e9}) == (flops / 1e3, "flops")
    assert roofline_bailing.step_least_seconds(
        doc, rows, counters, {"bf16_flops": 1e15, "hbm_bytes_per_s": 1.0}) == (nbytes, "bandwidth")
    # at the published widths: 52.65 M a KDA layer, 31.97 M the latent layer, 639.5 M unrouted
    full = cells.load_config(CONFIG)
    assert roofline_bailing.kda_params(full) == 52_646_048
    assert roofline_bailing.mla_params(full) == 31_965_184
    assert roofline_bailing.expert_params(full) == 5_898_240
    assert roofline_bailing.state_bytes(full) == 2_097_152 + 73_728
    # everything but the norms' gains (2 x 2560 a layer, 512 on the latent, 2560 at the end)
    assert fam.n_params(dict(full)) == (roofline_bailing.unrouted_params(full)
                                        + 6 * 128 * 5_898_240 + 2 * 39296 * 2560
                                        + 7 * 2 * 2560 + 512 + 2560 + 6 * 512)


def test_a_rehearsal_of_the_new_cell_reaches_its_last_line_and_agrees_with_the_reference():
    """The cell's own control flow on the CPU at tiny widths: the state slots
    turning over, the tap, the readers, the check against the plain reference
    (the program runs in bf16 there as on the chip, so the gaps are held to
    the file's limits, not to 0).  The CPU backend copies every cache array
    every step (no donation there), so the resolved cell's pool and lengths
    are cut to what it serves in seconds; everything the harness does stays
    the cell's."""
    cell = cells.resolve(CELL)
    cell.config = {**cell.config, "max_position_embeddings": 1024,
                   "pool": {**cell.config["pool"], "pages": 1024, "max_sessions": 16,
                            "prefill_budget": 48, "max_new_tokens": 24}}
    cell.traffic = {**cell.traffic,
                    "prompt_tokens": {"dist": "lognormal", "median": 48, "sigma": 0.8,
                                      "min": 16, "max": 160},
                    "new_tokens": {"dist": "uniform", "min": 8, "max": 24}}
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 40, seconds=12.0, trace=1,
                              rehearse=True, rate=1.5, control=0)
    out = asyncio.run(bench_run.run_cell(args, cell))
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 10
    assert out["device"]["platform"] == "cpu"
    for name in ("state_slots_held_share", "walk_live_slot_share", "moe_here_share",
                 "moe_experts_touched_share", "moe_load_imbalance", "step_cycle_ms",
                 "batch_occupancy"):
        assert out["metrics"][name]["value"] > 0, name
    assert out["metrics"]["state_slots_held_share"]["value"] <= 100
    for name in ("bailing_step_roofline_share", "kda_roofline_share", "kda_busy_share",
                 "moe_experts_roofline_share", "hbm_peak_gb"):
        assert name not in out["metrics"]  # nothing ran on a device here
