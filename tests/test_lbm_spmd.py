"""The X-sharded LBM shard body on forced host devices: the fused sweep of
the local stripe, its two boundary planes finished from the halo slabs,
against the single-device fused run at every plane, and the stripes that
stay on the unfused body against the single-device ``_step_ivjk``.

Runs in a subprocess with 8 forced host devices (the tier-1 process sees
one); the 4x1 mesh takes the first four.  Every case is computed by one
child, and each test reads its own result.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np
from repro import api, obs
from repro.api import spmd
from repro.kernels.lbm import ops, ref
from repro.parallel import rules


def mesh(n):
    return jax.sharding.Mesh(np.asarray(jax.devices()[:n]).reshape(n, 1),
                             ("data", "model"))


def lattice(shape, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    rho = 1.0 + 0.05 * jax.random.uniform(k1, shape[1:], jnp.float32)
    u = 0.02 * jax.random.normal(k2, (3,) + shape[1:], jnp.float32)
    return ref.equilibrium(rho, u)


def paths(sink):
    return [[list(e.shape), e.path, e.reason]
            for e in sink.events("lbm_path")]


# Bitwise equality, over the whole lattice and over the planes of no shard
# cut (stripes of xl planes); closeness; finiteness.
def compare(got, want, xl):
    got, want = np.asarray(got), np.asarray(want)
    inner = [x for x in range(got.shape[1]) if 0 < x % xl < xl - 1]
    return {"equal": bool(np.array_equal(got, want)),
            "inner_equal": bool(np.array_equal(got[:, inner],
                                               want[:, inner])),
            "close": bool(np.allclose(got, want, rtol=2e-5, atol=1e-7)),
            "finite": bool(np.isfinite(got).all())}


out = {"fused": {}, "unfused": {}}
m4 = mesh(4)
for shape in [(19, 16, 8, 128), (19, 20, 16, 256)]:
    f = lattice(shape, 0)
    for sweeps in (1, 3):
        sink = obs.RingBufferSink(capacity=8)
        with obs.session(sink), api.plan_context(mesh=m4):
            got = ops.lbm_run(f, 1.2, sweeps)
        want = ops.lbm_run(f, 1.2, sweeps)
        res = compare(got, want, shape[1] // 4)
        res["paths"] = paths(sink)
        out["fused"][f"{shape}x{sweeps}"] = res

omega = 1.7
f16 = lattice((19, 16, 8, 128), 2)
mask = jax.random.bernoulli(jax.random.PRNGKey(4), 0.7, (16, 8, 128))
m22 = jax.sharding.Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                        ("data", "model"))
both = rules.make_rules(overrides={"batch": ("data", "model")})
cases = {"z8": (lattice((19, 16, 8, 8), 1), None, None, m4),
         "xl2": (lattice((19, 8, 8, 128), 3), None, None, m4),
         "mask": (f16, mask, None, m4),
         "multi_axis": (f16, None, both, m22)}
for name, (f, m, table, grid) in cases.items():
    sink = obs.RingBufferSink(capacity=8)
    with obs.session(sink), rules.use_rules(table, grid), \
            api.plan_context(mesh=grid):
        got = api.launch("lbm.ivjk", f, omega=omega, mask=m)
    plan = api.plan_for("lbm.ivjk", tuple(f.shape), f.dtype)
    want = ops._step_ivjk(f, omega, m, plan=plan)
    res = compare(got, want, f.shape[1] // 4)
    res["paths"] = paths(sink)
    out["unfused"][name] = res

f = jnp.zeros((19, 32, 8, 128), jnp.float32)
with api.plan_context(mesh=mesh(8)):
    rep = spmd.overlap_report(lambda a: api.launch("lbm.ivjk", a, omega=1.7),
                              f)
out["overlap"] = {"n_pallas_calls": rep.n_pallas_calls,
                  "all_overlappable": rep.all_overlappable,
                  "collectives": [[c.primitive, c.result_bytes]
                                  for c in rep.collectives]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=600,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


# The fused cases' lattices and the local stripe each shard sweeps.
FUSED = {"(19, 16, 8, 128)": [19, 4, 8, 128],
         "(19, 20, 16, 256)": [19, 5, 16, 256]}


@pytest.mark.parametrize("sweeps", [1, 3])
@pytest.mark.parametrize("shape", FUSED)
def test_fused_body_matches_single_device_fused_run(results, shape, sweeps):
    """``lbm_run`` on a 4x1 mesh (local stripes (19, 4, 8, 128) and
    (19, 5, 16, 256), the second with two lane chunks) against the
    single-device fused run of the same lattice, every plane, the shard
    cuts and the periodic wrap included.  After one sweep the planes of no
    shard cut are bitwise the single-device run's: they come from the same
    fused kernel.  A cut's planes are collided by ``collide_soa``, whose
    arithmetic the CPU interpreter compiles apart from the fused kernel's
    and may round differently in the last place, so over the whole lattice
    the fused test's own tolerance (rtol 2e-5, atol 1e-7) holds."""
    res = results["fused"][f"{shape}x{sweeps}"]
    assert res["paths"] == [[FUSED[shape], "fused", ""]]
    assert res["finite"] and res["close"]
    if sweeps == 1:
        assert res["inner_equal"]


@pytest.mark.parametrize("case,reason", [
    ("z8", "Z 8 not a multiple of 128"),
    ("xl2", "stripe under 3 planes"),
    ("mask", "mask"),
    ("multi_axis", "multi-axis X"),
])
def test_unfused_body_matches_step_ivjk(results, case, reason):
    """Stripes the fused sweep does not take stay on the unfused body,
    bitwise the single-device ``_step_ivjk``: Z 8 (no 128-lane chunk),
    two-plane stripes, a mask, and X split over two mesh axes (the
    all-gather of the edge slabs)."""
    res = results["unfused"][case]
    assert [p[1:] for p in res["paths"]] == [["unfused", reason]]
    assert res["equal"]


def test_fused_body_halo_slabs_are_overlappable(results):
    """(19, 32, 8, 128) on an 8x1 mesh: two (5, 1, 8, 128) ppermutes, one
    each way, both free of the fused sweep of the stripe, so XLA may run
    them under it."""
    rep = results["overlap"]
    assert rep["n_pallas_calls"] >= 1
    assert rep["all_overlappable"]
    assert rep["collectives"] == [["ppermute", 5 * 8 * 128 * 4]] * 2
