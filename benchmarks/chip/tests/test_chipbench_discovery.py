"""Configurations, mixes and metric readers are found by name, and a new
one is added by adding files and entries only."""

import json
import shutil

import chipbench_support as sup
import pytest

from benchmarks.chip import bench


def test_every_cell_loads_with_its_files():
    spec = json.loads((sup.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = bench.load_cell(sup.ROOT, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert cell["mix"]["deck"] > 0 and cell["mix"]["why"]
        names = {m["name"] for m in cell["per_layer"]}
        assert names
        for m in cell["per_layer"]:
            assert callable(bench.metric_reader(sup.ROOT, m["name"]))


def test_unknown_cell_and_metric_raise():
    with pytest.raises(KeyError):
        bench.load_cell(sup.ROOT, "no-such.cell")
    with pytest.raises(FileNotFoundError):
        bench.metric_reader(sup.ROOT, "no_such_metric")


def test_a_new_entry_is_files_only(tmp_path):
    chip = tmp_path / "benchmarks" / "chip"
    for sub in ("configs", "mixes", "metrics"):
        shutil.copytree(sup.ROOT / "benchmarks/chip" / sub, chip / sub)
    spec = json.loads((sup.ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((chip / "configs/dense64-fine.json").read_text())
    cfg["name"] = "dummy"
    (chip / "configs/dummy.json").write_text(json.dumps(cfg))
    mix = json.loads((chip / "mixes/churn.json").read_text())
    mix["deck"] = 1234
    (chip / "mixes/dummymix.json").write_text(json.dumps(mix))
    (chip / "metrics/dummy_metric.py").write_text(
        "def read(run):\n    return run['decisions'] * 2.0\n")
    spec["configs"].append({"name": "dummy", "source": "https://example.org",
                            "file": "benchmarks/chip/configs/dummy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy.dummymix", "config": "dummy",
                              "traffic": "dummymix", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "dummy_metric", "unit": "count",
                              "better": "lower", "source": "program_counter",
                              "layer": "device", "moves": "decisions_per_s",
                              "workloads": ["dummy.dummymix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = bench.load_cell(tmp_path, "dummy.dummymix")
    assert cell["config"]["name"] == "dummy"
    assert cell["mix"]["deck"] == 1234
    assert [m["name"] for m in cell["per_layer"]] == ["dummy_metric"]
    assert bench.metric_reader(tmp_path, "dummy_metric")({"decisions": 3}) == 6.0
    # the existing cells do not see the new metric
    assert "dummy_metric" not in {
        m["name"] for m in bench.load_cell(tmp_path, "dense64-fine.churn")["per_layer"]}
