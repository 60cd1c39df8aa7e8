"""BENCHMARK.json resolves, by name alone, to files that exist: adding a
cell, a mix, a configuration or a metric is new files and new entries."""
import importlib
import json
import os
import re

import pytest

from benchmark import harness

MAN = harness.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert os.path.isdir(os.path.join(harness.ROOT, p))
    assert any(m["name"] == "setup_s" for m in MAN["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files(cell):
    r = harness.resolve(MAN, cell)
    assert r["cfg"]["family"] and r["mix"]["role"]
    importlib.import_module(f"benchmark.families.{r['cfg']['family']}")
    importlib.import_module(f"benchmark.drivers.{r['mix']['role']}")
    assert len(r["cell"]["why"]) <= 200
    e2e = {m["name"] for m in r["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert r["per_layer"], "every cell reports a per-layer metric"
    for m in r["per_layer"]:
        assert m["moves"] in e2e, (m["name"], "moves a metric the cell "
                                   "does not report")


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_a_reader(metric):
    assert NAME.match(metric["name"])
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    spec = harness.load_json("metrics", metric["name"] + ".json")
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)
    if "work" in spec.get("params", {}):
        work = importlib.import_module(
            f"benchmark.kernels.{spec['params']['work']}")
        assert callable(work.work)
    for w in metric.get("workloads", []):
        assert w in CELLS
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["layer"] and "\n" not in metric["layer"]


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(conf):
    with open(os.path.join(harness.ROOT, conf["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == conf["source"]
    assert set(conf["reduced"]) == set(cfg["reduced"])
    for key in conf["reduced"]:
        assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                             r"|head)", key), f"{key} is a width"
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "vocab_size"):
        if key in cfg["published"]:
            assert cfg[key] == cfg["published"][key], key
    assert any(w["config"] == conf["name"] for w in MAN["workloads"])


def test_names_and_paths_are_within_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in MAN[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for root, _, files in os.walk(os.path.join(harness.ROOT, "benchmark")):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
    assert MAN["command"][0].startswith("python")
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 4)
