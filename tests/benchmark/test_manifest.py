"""BENCHMARK.json names only what exists, in legal names and units."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_command_and_paths(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert all(os.path.isdir(os.path.join(ROOT, p))
               for p in manifest["paths"])
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_every_name_and_unit_is_legal(manifest):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        group = [e["name"] for e in manifest[kind]]
        assert len(group) == len(set(group)), kind
        names += group
    names += [c[k] for c in manifest["workloads"]
              for k in ("config", "traffic")]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert all(m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
               for m in metrics)
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in manifest["end_to_end"])


def test_files_exist_for_every_entry(manifest):
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for path in files:
        assert path.startswith("benchmark/configs/")
        with open(os.path.join(ROOT, path)) as f:
            config = json.load(f)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "reference", config["architecture"] + ".py"))
    for cell in manifest["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "drivers", driver + ".py"))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "limits", cell["name"] + ".json"))
    for metric in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", metric["name"] + ".py"))


def test_cells_and_metrics_hang_together(manifest):
    cells = {c["name"]: c for c in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    assert {c["config"] for c in cells.values()} == configs
    pairs = [(c["config"], c["traffic"]) for c in cells.values()]
    assert len(pairs) == len(set(pairs))
    assert sum(c["chips"] == 4 for c in cells.values()) <= max(
        1, len(cells) // 4)
    assert all(c["chips"] in (1, 4) and len(c["why"]) <= 200
               for c in cells.values())
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in end_to_end and "workloads" not in end_to_end["setup_s"]
    assert all(0 < m["bound"] <= 0.1 for m in end_to_end.values())

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    for name in cells:       # setup_s, one more end-to-end, one per-layer
        assert sum(name in reported_in(m) for m in end_to_end.values()) >= 2
        assert any(name in m["workloads"] for m in manifest["per_layer"])
    for metric in manifest["per_layer"]:
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["workloads"] and set(metric["workloads"]) <= set(cells)
        assert set(metric["workloads"]) <= reported_in(
            end_to_end[metric["moves"]]), metric["name"]


def test_no_task_cell_this_round(manifest):
    text = json.dumps(manifest)
    assert "tasks_per_s" not in text and "tasks_pi" not in text
