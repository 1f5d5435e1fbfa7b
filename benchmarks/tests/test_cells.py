"""The resolver and the contract: every cell resolves through files found by
name, a missing file is refused, and BENCHMARK.json agrees with the readers."""
import json
import os
import re

import pytest

from benchmarks.harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = cells.load_benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = cells.resolve(workload)
    row = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert cell.config["name"] == row["config"] and cell.traffic["name"] == row["traffic"]
    assert cell.chips == 1
    cell.family.validate(cell.config)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "tokens_per_s"}
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}


def test_missing_files_are_refused(tmp_path):
    with pytest.raises(KeyError):
        cells.resolve("no-such-cell")
    for bad in ({"config": "no-such-config", "traffic": "chat-open"},
                {"config": "mistral-7b-v0.3", "traffic": "no-such-mix"}):
        bench = {**BENCH, "workloads": [{"name": "x", "chips": 1, "why": "", **bad}]}
        with pytest.raises(FileNotFoundError):
            cells.resolve("x", bench)
    bench = {**BENCH, "per_layer": BENCH["per_layer"] + [
        {"name": "no_such_metric", "unit": "ms", "better": "lower", "source": "host_clock",
         "layer": "x", "moves": "setup_s"}]}
    with pytest.raises(FileNotFoundError):
        cells.resolve(BENCH["workloads"][0]["name"], bench)
    with pytest.raises(FileNotFoundError):
        cells.load_family("no_such_family")


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_benchmark_json_agrees_with_each_reader(metric):
    mod = cells.load_reader(metric["name"])
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
        metric["layer"], metric["unit"], metric["better"], metric["source"], metric["moves"])
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_every_reader_file_is_named_in_benchmark_json():
    files = {f[:-3] for f in os.listdir(os.path.join(cells.BENCH_DIR, "layer_metrics"))
             if f.endswith(".py") and f != "__init__.py"}
    assert files == {m["name"] for m in BENCH["per_layer"]}


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    cells_n = 24
    assert 1200 + (2 + 14 * cells_n) * (BENCH["run_seconds"] + 60) + cells_n * 180 <= 43200
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert UNIT.match(m["unit"]) and "bound" not in m
    for c in BENCH["configs"]:
        doc = json.load(open(os.path.join(cells.REPO_ROOT, c["file"])))
        assert doc["reduced"] == c["reduced"] and doc["source"] == c["source"]
        for key in c["reduced"]:  # never a width
            assert key in ("num_hidden_layers", "max_position_embeddings"), key
            assert doc[key] != doc["source_values"][key]
    assert len(json.dumps(BENCH)) < 64 * 1024
