"""Whole runs of each driver at a size a test can hold, on the CPU.

Each test skips only the run's look for a chip (``run.main``) and drives
the rest of a run through ``run.measure``: set-up, window, the check
against the plain reference.  A sound run comes out correct; a run with
the measured path broken underneath, or the control in the program's
place, comes out not correct.
"""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 1234


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(harness, "peaks", lambda kind, bench_dir=None: PEAK)


def measure(cell, cfg, **info):
    import jax

    runner = harness.load_module(harness.BENCH / "run.py", "bench_run")
    spec = harness.benchmark()
    return runner.measure(harness, spec, cell, cfg, jax.devices()[:1],
                          seed=SEED, seconds=0.3, trace=False, info=info)


# ---- lbm -------------------------------------------------------------------

def lbm_cell():
    cell = harness.cell("lbm-d3q19.512x256x256", harness.benchmark())
    cell["sweeps_per_call"] = 2
    cfg = harness.config("lbm-d3q19")
    cfg["lattice_per_chip"] = [8, 8, 128]
    return cell, cfg


def test_lbm_sound_run_is_correct():
    res = measure(*lbm_cell())
    assert res["correct"], res["checks"]
    assert res["metrics"]["lbm_mlups"]["value"] > 0
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_lbm_state_left_unchanged_is_not_correct(monkeypatch):
    from repro.kernels.lbm import ops

    monkeypatch.setattr(ops, "lbm_run", lambda f, *a, **k: f)
    assert not measure(*lbm_cell())["correct"]


def test_lbm_altered_answer_is_not_correct(monkeypatch):
    from repro.kernels.lbm import ops

    real = ops.lbm_run
    monkeypatch.setattr(ops, "lbm_run", lambda f, *a, **k:
                        real(f, *a, **k).at[3, 0, 2, 5].multiply(1.001))
    assert not measure(*lbm_cell())["correct"]


def test_lbm_control_is_not_correct():
    """The reference in bfloat16 in the program's place reads above the
    limit the program is held to."""
    cell, cfg = lbm_cell()
    limit = cell["limits"]["lbm_max_rel_err"]
    res = measure(cell, cfg, control=True)
    assert res["info"]["control"] > limit
    assert res["checks"]["lbm_max_rel_err"]["value"] <= limit


# ---- serving ---------------------------------------------------------------

def chat_cell():
    cell = harness.cell("qwen2-0.5b.chat", harness.benchmark())
    cell["traffic_mix"].update(
        rate_per_s=8.0, slots=4, max_len=64, lead_s=0.2, drain_s=30.0,
        prompt_len={"median": 10, "sigma": 0.5, "min": 3, "max": 30},
        output_len={"median": 8, "sigma": 0.5, "min": 3, "max": 12})
    cfg = harness.config("qwen2-0.5b")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=128, num_hidden_layers=2, vocab_size=512,
               initializer_range=0.2)
    return cell, cfg


def test_serving_sound_run_is_correct():
    res = measure(*chat_cell())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    m = res["metrics"]
    assert 0 < m["itl_p95_ms"]["value"] < m["ttft_p95_ms"]["value"] * 10


def test_serving_altered_token_is_not_correct(monkeypatch):
    """Every request's second token is replaced where it is produced."""
    from repro.serving import scheduler

    real = scheduler.ContinuousBatcher.step

    def step(self):
        real(self)
        for r in self.slot_req:
            if r is not None and len(r.generated) == 2:
                r.generated[-1] = (r.generated[-1] + 1) % 512
    monkeypatch.setattr(scheduler.ContinuousBatcher, "step", step)
    assert not measure(*chat_cell())["correct"]


def test_serving_control_is_not_correct():
    cell, cfg = chat_cell()
    limit = cell["limits"]["served_logit_gap"]
    res = measure(cell, cfg, control=True)
    assert res["info"]["control"] > limit
    assert res["checks"]["served_logit_gap"]["value"] <= limit


# ---- training --------------------------------------------------------------

def train_cell(**sizes):
    cell = harness.cell("qwen2-0.5b.train_4k", harness.benchmark())
    cell["training"].update(seq_len=sizes.pop("seq_len", 128), global_batch=2)
    cfg = harness.config("qwen2-0.5b")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=128, num_hidden_layers=2, vocab_size=512)
    cfg.update(sizes)
    return cell, cfg


def test_training_sound_run_is_correct():
    res = measure(*train_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def patched_step(monkeypatch, fault):
    from repro.parallel import steps

    real = steps.make_train_step

    def make(model, opt_cfg, schedule, **kw):
        step = real(model, opt_cfg, schedule, **kw)
        return lambda state, batch: fault(step, state, batch)
    monkeypatch.setattr(steps, "make_train_step", make)


def test_training_state_left_unchanged_is_not_correct(monkeypatch):
    patched_step(monkeypatch, lambda step, s, b: (s, step(s, b)[1]))
    assert not measure(*train_cell())["correct"]


def test_training_control_is_not_correct():
    """The reference with float8 products in the program's place reads
    above a limit the program is held to."""
    # Deep enough for float8's error to build up as it does at 24 layers.
    cell, cfg = train_cell(
        seq_len=256, hidden_size=256, num_attention_heads=4,
        intermediate_size=1024, num_hidden_layers=12, vocab_size=4096)
    res = measure(cell, cfg, control=True)
    ctl = res["info"]["control"]
    assert any(ctl[k] > limit for k, limit in cell["limits"].items()
               if k in ctl), ctl
    assert res["correct"], res["checks"]


def test_training_half_batch_is_not_correct(monkeypatch):
    import jax

    def half(batch):
        return jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
    patched_step(monkeypatch, lambda step, s, b: step(s, half(b)))
    assert not measure(*train_cell())["correct"]


# ---- lbm on four devices (forced host devices, in a child process) ---------

FOUR = r'''
import json, sys
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1] + "/bench"]
import harness, jax
harness.peaks = lambda kind, bench_dir=None: {}
if sys.argv[2] == "no_exchange":
    from repro.kernels.lbm import ops
    import jax.numpy as jnp
    # each shard's halo slabs are its own edge planes: nothing crosses
    ops._halo_exchange_x = lambda f, axes, n, idx: (
        f[jnp.array(ops._PLUS_X)][:, -1:], f[jnp.array(ops._MINUS_X)][:, :1])
spec = harness.benchmark()
name = "lbm-d3q19.2048x256x256.4chip"       # prepared, not yet measured
if name not in [w["name"] for w in spec["workloads"]]:
    spec["workloads"].append({"name": name, "config": "lbm-d3q19",
                              "traffic": "2048x256x256.4chip", "chips": 4})
cell = harness.cell(name, spec)
cell["sweeps_per_call"] = 2
cfg = harness.config("lbm-d3q19")
cfg["lattice_per_chip"] = [4, 8, 128]
run = harness.load_module(harness.BENCH / "run.py", "bench_run")
res = run.measure(harness, spec, cell, cfg, jax.devices()[:4], seed=7,
                  seconds=0.2, trace=False)
print(json.dumps({"correct": res["correct"], "count": res["device"]["count"]}))
'''


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("no_exchange", False)])
def test_lbm_four_devices_need_the_halo_exchange(fault, correct):
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", FOUR, str(harness.ROOT),
                          fault], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"correct": correct, "count": 4}
