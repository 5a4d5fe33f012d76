"""Every file the benchmark finds by name loads, ``BENCHMARK.json`` keeps
to its format, and a cell added as files alone is picked up."""
from __future__ import annotations

import json
import os
import re
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

SPEC = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines():
    names = [c["name"] for c in SPEC["configs"]] + CELLS + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(CELLS)) == len(CELLS)
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]


def test_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = {m["name"] for m in harness.end_to_end_metrics(SPEC, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = harness.per_layer_metrics(SPEC, cell)
    assert layers and all(m["moves"] in e2e for m in layers)


def test_per_layer_metrics_name_their_cells_and_layer():
    for m in SPEC["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS)
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert 1 <= len(m["layer"]) <= 200
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_workload_files_load_by_name(cell):
    spec = harness.cell(cell, SPEC)
    assert spec["name"] == cell
    assert callable(harness.driver(spec["driver"]).run)
    assert harness.config(spec["config"])["name"] == spec["config"]


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_files_and_references_load_by_name(config):
    entry = {c["name"]: c for c in SPEC["configs"]}[config]
    assert entry["file"] == f"bench/configs/{config}.json"
    assert harness.config(config)["reduced"] == entry["reduced"]
    assert config in {w["config"] for w in SPEC["workloads"]}
    ref = harness.reference(config)
    assert ref.__doc__


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_readers_load_by_name(metric):
    assert callable(harness.metric_reader(metric).read)


def test_peaks_are_keyed_by_device_kind():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_a_cell_added_as_files_is_picked_up(tmp_path):
    """A later PR adds a cell with files alone: a workload file and its
    entry in BENCHMARK.json.  The copied harness finds it, its driver,
    configuration and per-layer metrics with no other edit."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "lbm-d3q19.256x256x256",
                              "config": "lbm-d3q19",
                              "traffic": "256x256x256", "chips": 1,
                              "why": "a smaller lattice"})
    for m in spec["per_layer"]:
        if "lbm-d3q19.512x256x256" in m["workloads"]:
            m["workloads"].append("lbm-d3q19.256x256x256")
    for m in spec["end_to_end"]:
        if "lbm-d3q19.512x256x256" in m.get("workloads", []):
            m["workloads"].append("lbm-d3q19.256x256x256")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    wl = json.loads((tmp_path / "bench" / "workloads"
                     / "lbm-d3q19.512x256x256.json").read_text())
    wl["traffic"] = "256x256x256"
    (tmp_path / "bench" / "workloads" / "lbm-d3q19.256x256x256.json"
     ).write_text(json.dumps(wl))
    copy = harness.load_module(tmp_path / "bench" / "harness.py",
                               "bench_harness_copy")
    assert copy.BENCH == tmp_path / "bench"
    spec2 = copy.benchmark()
    cell = copy.cell("lbm-d3q19.256x256x256", spec2)
    assert cell["driver"] == "lbm" and cell["chips"] == 1
    assert {m["name"] for m in copy.end_to_end_metrics(
        spec2, "lbm-d3q19.256x256x256")} == {"lbm_mlups", "setup_s"}
    assert "lbm_collide_roofline" in {m["name"] for m in
                                      copy.per_layer_metrics(
                                          spec2, "lbm-d3q19.256x256x256")}
    assert callable(copy.driver(cell["driver"]).run)


def run_cli(root, env_extra=None):
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "lbm-d3q19.512x256x256", "--seed", "2147483999",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=root)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    out = run_cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A checkout holding only BENCHMARK.json and ``bench/`` has no system
    under test: the run fails and prints no result."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = run_cli(str(tmp_path), {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
