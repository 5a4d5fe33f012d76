"""Every file the benchmark finds by name loads, ``BENCHMARK.json`` keeps
to its format, and a cell or a configuration added as files alone is
picked up."""
from __future__ import annotations

import dataclasses
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
WORKLOAD_FILES = sorted(p.stem for p in (harness.BENCH / "workloads").glob(
    "*.json"))


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


@pytest.mark.parametrize("name", WORKLOAD_FILES)
def test_workload_files_state_their_limits(name):
    """Listed or prepared, each workload file gives a limit for every
    number its driver compares, and for no other."""
    spec = harness.read_json(harness.BENCH / "workloads" / f"{name}.json")
    limits = spec["limits"]
    assert set(limits) == set(harness.driver(spec["driver"]).CHECKS)
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in limits.values())


def test_qwen2_model_config_field_for_field():
    """Qwen2's mapping, moved into its reference, builds the model the
    harness built before it moved."""
    from repro.models.config import ModelConfig

    want = ModelConfig(
        name="qwen2-0.5b", family="dense", n_layers=24, d_model=896,
        n_heads=14, n_kv_heads=2, d_ff=4864, vocab_size=151936,
        qkv_bias=True, tie_embeddings=True, rope_theta=1000000.0,
        norm_eps=1e-06, dtype="bfloat16")
    cfg = harness.config("qwen2-0.5b")
    got = harness.reference("qwen2-0.5b").model_config(cfg)
    assert dataclasses.asdict(got) == dataclasses.asdict(want)


HYBRID_CONFIG = {
    "name": "tiny-hybrid", "source": "a test", "reduced": [],
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "intermediate_size": 128, "vocab_size": 256, "state_size": 16,
    "mamba_headdim": 16, "hybrid_period": 2}

HYBRID_REFERENCE = '''"""A Mamba2 stack with a shared attention block, added as files.

A test's stand-in: its logits are the system's own forward pass, so a
run shows the path a configuration takes, not that the model is right.
"""
import jax.numpy as jnp


def model_config(cfg):
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        ssm_state=cfg["state_size"], ssm_head_dim=cfg["mamba_headdim"],
        shared_attn_period=cfg["hybrid_period"])


def _model(cfg):
    from repro.models import build_model

    return build_model(model_config(cfg))


def init_weights(key, cfg, dtype=jnp.bfloat16):
    """The served tree; the SSM's decay, skip and step bias stay float32."""
    return _model(cfg).init(key)


def logits(w, tokens, cfg, low=False):
    return _model(cfg).forward(w, tokens[None])[0][0].astype(jnp.float32)
'''


def test_a_configuration_added_as_files_is_picked_up(tmp_path, monkeypatch):
    """A later PR adds a configuration of another family with files
    alone: ``configs/<name>.json``, a reference with ``model_config``, and
    a cell.  The copied harness serves it through the serving driver
    (model, weights from the seed, paged batcher, the check) with no edit
    to any file it already has."""
    import jax

    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = tmp_path / "bench"
    (bench / "configs" / "tiny-hybrid.json").write_text(
        json.dumps(HYBRID_CONFIG))
    (bench / "configs" / "tiny-hybrid.py").write_text(HYBRID_REFERENCE)
    wl = json.loads((bench / "workloads" / "qwen2-0.5b.chat.json")
                    .read_text())
    wl["config"] = "tiny-hybrid"
    wl["traffic_mix"].update(
        rate_per_s=8.0, slots=4, max_len=64, lead_s=0.2, drain_s=30.0,
        prompt_len={"median": 10, "sigma": 0.5, "min": 3, "max": 30},
        output_len={"median": 8, "sigma": 0.5, "min": 3, "max": 12})
    (bench / "workloads" / "tiny-hybrid.chat.json").write_text(json.dumps(wl))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "tiny-hybrid.chat",
                              "config": "tiny-hybrid", "traffic": "chat",
                              "chips": 1, "why": "a hybrid model served"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    copy = harness.load_module(bench / "harness.py", "bench_harness_copy")
    monkeypatch.setitem(sys.modules, "harness", copy)   # the drivers' import
    monkeypatch.setattr(copy, "peaks", lambda kind, bench_dir=None: {})
    spec = copy.benchmark()
    cell = copy.cell("tiny-hybrid.chat", spec)
    cfg = copy.config("tiny-hybrid")
    mc = copy.reference("tiny-hybrid").model_config(cfg)
    assert [k for k, _ in mc.stages()] == ["mamba", "shared_attn"]
    runner = copy.load_module(bench / "run.py", "bench_run_copy")
    res = runner.measure(copy, spec, cell, cfg, jax.devices()[:1],
                         seed=2**33 + 5, seconds=0.3, trace=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


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
