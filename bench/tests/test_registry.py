"""A cell, a configuration, a traffic mix or kind, and a per-layer metric
are added as files and entries only: the harness finds them by name."""

import json
import shutil

import pytest

from bench import harness


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of the benchmark's data files that the harness reads from."""
    root = tmp_path / "checkout"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(harness.BENCH / sub, root / "bench" / sub)
    shutil.copy(harness.BENCH / "peaks.json", root / "bench" / "peaks.json")
    shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    monkeypatch.setattr(harness, "ROOT", root)
    return root


def test_new_files_are_found_without_an_edit(bench_copy):
    b = bench_copy / "bench"
    cfg = json.loads((b / "configs" / "jscc4.json").read_text())
    cfg["name"] = "jscc4-twin"
    (b / "configs" / "jscc4-twin.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "campaign-fcfs.json").read_text())
    mix["kind"] = "replay"
    mix["jobs"] = 777
    (b / "traffic" / "campaign-short.json").write_text(json.dumps(mix))
    (b / "traffic" / "replay.py").write_text(
        "class Cell:\n    KIND = 'replay'\n")
    (b / "metrics" / "queue_depth.py").write_text(
        "def read(run):\n    return run['counters'].get('depth')\n")
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "jscc4-twin.campaign-short",
                              "config": "jscc4-twin",
                              "traffic": "campaign-short", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "queue_depth", "unit": "jobs",
                              "better": "lower", "source": "program_counter",
                              "layer": "device", "moves": "setup_s",
                              "workloads": ["jscc4-twin.campaign-short"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("jscc4-twin.campaign-short")
    assert cell["config_data"]["name"] == "jscc4-twin"
    assert cell["traffic_data"]["jobs"] == 777
    assert harness.kind_module(cell).Cell.KIND == "replay"
    assert "queue_depth" in {m["name"] for m in cell["per_layer"]}
    assert harness.reader("queue_depth")({"counters": {"depth": 3}}) == 3


def test_metric_without_workloads_key_reaches_every_cell_of_its_metric():
    spec = harness.spec_file()
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec)
        names = {m["name"] for m in cell["per_layer"]}
        assert "compile_s" in names            # moves setup_s: every cell
        assert cell["end_to_end"] and cell["per_layer"]


def test_every_named_file_exists():
    spec = harness.spec_file()
    for c in spec["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec)
        harness.kind_module(cell)
    for m in spec["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_unknown_device_kind_raises():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
