"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Kernels launch through ``repro.api`` (the deprecated per-family shims warn
-- as errors inside this suite -- and stay covered in test_api only); the
experiment variants that are not 1:1 launches (phased/segmented triad,
multi-sweep jacobi, lbm_run) keep their own entry points."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.segmented import SegmentedArray
from repro.kernels.jacobi import ops as jops
from repro.kernels.jacobi import ref as jref
from repro.kernels.lbm import ops as lops
from repro.kernels.lbm import ref as lref
from repro.kernels.stream import ops as sops
from repro.kernels.stream import ref as sref
from repro.kernels.triad import ops as tops
from repro.kernels.triad import ref as tref

SIZES = [1, 7, 128, 1000, 8192, 20000]
DTYPES = [jnp.float32, jnp.bfloat16]


def rnd(shape, dtype, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-6
    )


class TestStream:
    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_triad(self, n, dtype):
        b, c = rnd((n,), dtype, 0), rnd((n,), dtype, 1)
        np.testing.assert_allclose(
            np.asarray(api.launch("stream.triad", b, c, s=3.0), np.float32),
            np.asarray(sref.triad(b, c, 3.0), np.float32), **tol(dtype)
        )

    @pytest.mark.parametrize("n", [128, 5000])
    def test_copy_scale_add(self, n):
        a, b = rnd((n,), jnp.float32, 0), rnd((n,), jnp.float32, 1)
        np.testing.assert_allclose(np.asarray(api.launch("stream.copy", a)),
                                   np.asarray(sref.copy(a)))
        np.testing.assert_allclose(np.asarray(api.launch("stream.scale", a, s=2.0)),
                                   np.asarray(sref.scale(a, 2.0)), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(api.launch("stream.add", a, b)),
                                   np.asarray(sref.add(a, b)), rtol=1e-6)

    def test_bytes_accounting(self):
        """Paper SS2.1: triad RFO traffic is 4/3 of reported."""
        assert sops.bytes_moved_rfo("triad", 100) / sops.bytes_moved(
            "triad", 100
        ) == pytest.approx(4 / 3)


class TestVectorTriad:
    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_aligned(self, n, dtype):
        b, c, d = (rnd((n,), dtype, i) for i in range(3))
        np.testing.assert_allclose(
            np.asarray(api.launch("triad", b, c, d), np.float32),
            np.asarray(tref.triad(b, c, d), np.float32), **tol(dtype)
        )

    @pytest.mark.parametrize("phases", [(0, 0, 0), (0, 32, 64), (16, 48, 80)])
    def test_phased_layouts_preserve_semantics(self, phases):
        """The paper's offsets change *performance*, never results."""
        n = 3000
        b, c, d = (rnd((n,), jnp.float32, i) for i in range(3))
        np.testing.assert_allclose(
            np.asarray(tops.vector_triad_phased(b, c, d, phases=phases)),
            np.asarray(tref.triad(b, c, d)), rtol=1e-6, atol=1e-6
        )

    def test_segmented(self):
        n = 1500
        b, c, d = (rnd((n,), jnp.float32, i) for i in range(3))
        mk = lambda v: SegmentedArray.from_flat(v, 4, align=128, shift=16)
        out = tops.vector_triad_segmented(mk(jnp.zeros(n)), mk(b), mk(c), mk(d))
        np.testing.assert_allclose(np.asarray(out.to_flat()),
                                   np.asarray(tref.triad(b, c, d)),
                                   rtol=1e-6, atol=1e-6)


class TestJacobi:
    @pytest.mark.parametrize("shape", [(16, 16), (130, 260), (257, 129),
                                       (64, 1000)])
    def test_one_sweep(self, shape):
        g = rnd(shape, jnp.float32, 0)
        np.testing.assert_allclose(np.asarray(api.launch("jacobi", g)),
                                   np.asarray(jref.jacobi_step(g)),
                                   rtol=1e-5, atol=1e-6)

    def test_multi_sweep(self):
        g = rnd((66, 130), jnp.float32, 1)
        np.testing.assert_allclose(np.asarray(jops.jacobi_sweeps(g, 7)),
                                   np.asarray(jref.jacobi_sweeps(g, 7)),
                                   rtol=1e-4, atol=1e-5)

    def test_boundary_preserved(self):
        g = rnd((40, 40), jnp.float32, 2)
        out = np.asarray(api.launch("jacobi", g))
        np.testing.assert_array_equal(out[0], np.asarray(g)[0])
        np.testing.assert_array_equal(out[-1], np.asarray(g)[-1])
        np.testing.assert_array_equal(out[:, 0], np.asarray(g)[:, 0])
        np.testing.assert_array_equal(out[:, -1], np.asarray(g)[:, -1])

    def test_balance_numbers(self):
        """Paper SS2.3: 4 B/flop without RFO, 6 with."""
        n = 100
        assert jops.jacobi_bytes(n, n, rfo=False) / jops.jacobi_flops(n, n) \
            == pytest.approx(4.0)
        assert jops.jacobi_bytes(n, n, rfo=True) / jops.jacobi_flops(n, n) \
            == pytest.approx(6.0)


# The mesh cases of ``TestLBM.test_path_event_names_the_path``: a local
# stripe, masked or not, and the mesh it is one of four stripes of, its X
# over "data" (4x1) or over "data" and "model" (2x2); then the path and
# reason its shard body reports.
MESH_PATH_CASES = [
    ((19, 4, 8, 128), False, "4x1", "fused", ""),
    ((19, 4, 8, 8), False, "4x1", "unfused", "Z 8 not a multiple of 128"),
    ((19, 2, 8, 128), False, "4x1", "unfused", "stripe under 3 planes"),
    ((19, 4, 8, 128), True, "4x1", "unfused", "mask"),
    ((19, 4, 8, 128), False, "2x2", "unfused", "multi-axis X"),
]

# Launches each case's whole lattice on four forced host devices and
# prints the ``lbm_path`` events of each.
MESH_PATHS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax, jax.numpy as jnp, numpy as np
from repro import api, obs
from repro.kernels.lbm import ops
from repro.parallel import rules

out = []
for shape, masked, mesh in json.loads(sys.argv[1]):
    d, m = (int(n) for n in mesh.split("x"))
    grid = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(d, m),
                             ("data", "model"))
    table = rules.make_rules(
        overrides={"batch": ("data", "model") if m > 1 else ("data",)})
    glob = (shape[0], 4 * shape[1]) + tuple(shape[2:])
    f = jax.ShapeDtypeStruct(glob, jnp.float32)
    sink = obs.RingBufferSink(capacity=8)
    with obs.session(sink), rules.use_rules(table, grid), \
            api.plan_context(mesh=grid):
        if masked:
            mask = jax.ShapeDtypeStruct(glob[1:], jnp.bool_)
            jax.eval_shape(lambda f, k: api.launch(
                "lbm.ivjk", f, omega=1.2, mask=k), f, mask)
        else:
            jax.eval_shape(lambda f: ops.lbm_run(f, 1.2, 3), f)
    out.append([[e.kernel, list(e.shape), e.dtype, e.path, e.reason]
                for e in sink.events("lbm_path")])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def lbm_mesh_paths():
    """The ``lbm_path`` events of each of ``MESH_PATH_CASES``, from one
    child process."""
    import json
    import os
    import subprocess
    import sys

    cases = [[list(shape), masked, mesh]
             for shape, masked, mesh, _, _ in MESH_PATH_CASES]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", MESH_PATHS,
                          json.dumps(cases)], capture_output=True, text=True,
                         cwd=root, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


class TestLBM:
    @pytest.mark.parametrize("layout", ["soa", "ivjk"])
    @pytest.mark.parametrize("n", [8, 16])
    def test_step_matches_ref(self, layout, n):
        f = lops.init_equilibrium(n, jnp.float32)
        got = api.launch(f"lbm.{layout}", f, omega=1.2)
        want = lref.lbm_step(f, 1.2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=1e-7)

    def test_layouts_agree_with_each_other(self):
        f = lops.init_equilibrium(12, jnp.float32)
        a = lops.lbm_run(f, 1.0, 3, layout="soa")
        b = lops.lbm_run(f, 1.0, 3, layout="ivjk")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)

    def test_mass_and_momentum_conserved(self):
        f = lops.init_equilibrium(16, jnp.float32)
        f5 = lops.lbm_run(f, 1.2, 5, layout="ivjk")
        m0, m5 = float(jnp.sum(f)), float(jnp.sum(f5))
        assert abs(m5 - m0) / m0 < 1e-3
        c = jnp.asarray(lref.C, jnp.float32)
        mom = lambda g: np.asarray(
            jnp.tensordot(c.T, g.reshape(19, -1), axes=(1, 0)).sum(axis=1)
        )
        np.testing.assert_allclose(mom(f5), mom(f), atol=m0 * 2e-3)

    def test_equilibrium_is_fixed_point(self):
        rho = jnp.ones((8, 8, 8))
        u = jnp.zeros((3, 8, 8, 8))
        f = lref.equilibrium(rho, u)
        f1 = api.launch("lbm.ivjk", f, omega=1.7)
        np.testing.assert_allclose(np.asarray(f1), np.asarray(f), atol=1e-6)

    def test_masked_cells_hold(self):
        f = lops.init_equilibrium(12, jnp.float32)
        mask = jnp.ones((12, 12, 12), bool).at[3:6, 3:6, 3:6].set(False)
        out = api.launch("lbm.soa", f, omega=1.2, mask=mask)
        np.testing.assert_array_equal(
            np.asarray(out[:, 3:6, 3:6, 3:6]), np.asarray(f[:, 3:6, 3:6, 3:6])
        )

    # Lattices the fused pull+collide sweep takes: Z a multiple of 128, Y
    # of 8.  The second holds two lane chunks per z line (the z wrap
    # crosses between them) and a middle sweep, updated in place, at 3.
    FUSED = [(19, 4, 8, 128), (19, 6, 16, 256)]

    @staticmethod
    def _lattice(shape, seed=0):
        """Equilibrium of a random density and velocity field."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        rho = 1.0 + 0.05 * jax.random.uniform(k1, shape[1:], jnp.float32)
        u = 0.02 * jax.random.normal(k2, (3,) + shape[1:], jnp.float32)
        return lref.equilibrium(rho, u)

    @pytest.mark.parametrize("sweeps", [1, 3])
    @pytest.mark.parametrize("shape", FUSED, ids=str)
    def test_fused_run_matches_ref(self, shape, sweeps):
        f = self._lattice(shape)
        got = lops.lbm_run(f, 1.2, sweeps)
        want = f
        for _ in range(sweeps):
            want = lref.lbm_step(want, 1.2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=1e-7)

    def test_fused_in_place_sweeps_on_the_tpu_interpreter(self, monkeypatch):
        """The middle sweeps overwrite their input plane by plane; the TPU
        interpreter models that aliasing (the generic one copies), so
        here a sweep that read input plane 0 back after writing output
        plane 0 would fail.  A shape of its own: no trace from the generic
        interpreter is cached for it."""
        from jax.experimental.pallas import tpu as pltpu

        from repro.kernels.lbm import kernel as lkernel

        monkeypatch.setattr(lkernel, "interpret", pltpu.InterpretParams)
        f = self._lattice((19, 5, 8, 128), seed=1)
        got = lops.lbm_run(f, 1.2, 4)
        want = f
        for _ in range(4):
            want = lref.lbm_step(want, 1.2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=1e-7)

    @pytest.mark.parametrize("shape,masked,path,reason,mesh_case", [
        (FUSED[0], False, "fused", "", None),
        (FUSED[1], False, "fused", "", None),
        ((19, 8, 8, 8), False, "unfused", "Z 8 not a multiple of 128", None),
        (FUSED[0], True, "unfused", "mask", None),
    ] + [(shape, masked, path, reason, i)
         for i, (shape, masked, _, path, reason)
         in enumerate(MESH_PATH_CASES)],
        ids=["fused-128", "fused-256", "z8", "masked", "mesh-fused",
             "mesh-z8", "mesh-stripe2", "mesh-masked", "mesh-multi-axis"])
    def test_path_event_names_the_path(self, shape, masked, path, reason,
                                       mesh_case, request):
        """lbm_run, and a launch with a mask, report the path they take
        (traced only: the choice is made from the shape).  Under a mesh
        the shard body reports its local stripe (``shape`` here); those
        cases (``mesh_case`` indexes ``MESH_PATH_CASES``) read
        ``lbm_mesh_paths``."""
        from repro import obs

        if mesh_case is not None:
            events = request.getfixturevalue("lbm_mesh_paths")[mesh_case]
            assert events == [["lbm.ivjk", list(shape), "float32", path,
                               reason]]
            return
        f = jax.ShapeDtypeStruct(shape, jnp.float32)
        sink = obs.RingBufferSink(capacity=8)
        with obs.session(sink):
            if masked:
                mask = jax.ShapeDtypeStruct(shape[1:], jnp.bool_)
                jax.eval_shape(lambda f, m: api.launch(
                    "lbm.ivjk", f, omega=1.2, mask=m), f, mask)
            else:
                jax.eval_shape(lambda f: lops.lbm_run(f, 1.2, 3), f)
        (ev,) = sink.events("lbm_path")
        assert (ev.kernel, ev.shape, ev.dtype) == ("lbm.ivjk", shape,
                                                   "float32")
        assert (ev.path, ev.reason) == (path, reason)

    def test_layout_scores_reproduce_fig7(self):
        """Generic N: ivjk balanced; N % 64 == 0: both ruinous (paper)."""
        best, s = lops.layout_balance_scores(n=100)
        assert best == "ivjk" and s["ivjk"] > 3 * s["soa"]
        _, s64 = lops.layout_balance_scores(n=64)
        assert s64["ivjk"] == pytest.approx(0.25)
        assert s64["soa"] == pytest.approx(0.25)

    def test_site_bytes_is_456(self):
        assert lops.site_bytes() == 456  # paper SS2.4


def xent_plan_with_tiles(t, v, bt, bv):
    """An explicit (bt, bv) online-softmax tile as a pinned plan -- the
    API-native form of the old shim's bt=/bv= overrides."""
    import dataclasses

    from repro import api
    from repro.core.layout import round_up

    base = api.plan_for("xent", (t, v), jnp.float32)
    return dataclasses.replace(
        base, padded_shape=(round_up(t, bt), round_up(v, bv)),
        block_shape=(bt, bv))


class TestXent:
    """Tiled cross-entropy kernel (beyond-paper, SSPerf P0.1 as a kernel)."""

    @pytest.mark.parametrize("t,v,lv,bt,bv", [
        (512, 4096, 4096, 256, 2048),
        (300, 5000, 4777, 64, 1024),   # ragged T + padded vocab masking
        (64, 2048, 2048, 64, 512),
        (128, 1111, 1000, 64, 512),    # ragged vocab + logical < padded
    ])
    def test_matches_ref(self, t, v, lv, bt, bv):
        from repro.kernels.xent import ref as xref

        logits = jax.random.normal(jax.random.PRNGKey(0), (t, v)) * 3
        labels = jax.random.randint(jax.random.PRNGKey(1), (t,), 0, lv)
        got = float(api.launch("xent", logits, labels, logical_v=lv,
                               plan=xent_plan_with_tiles(t, v, bt, bv)))
        want = float(xref.xent(logits, labels, logical_v=lv).mean())
        assert abs(got - want) < 1e-4

    def test_extreme_logits_stable(self):
        from repro.kernels.xent import ref as xref

        logits = jnp.full((64, 1024), 80.0).at[:, 7].set(90.0)
        labels = jnp.full((64,), 7, jnp.int32)
        got = float(api.launch("xent", logits, labels,
                               plan=xent_plan_with_tiles(64, 1024, 64, 512)))
        want = float(xref.xent(logits, labels, logical_v=1024).mean())
        assert abs(got - want) < 1e-4
        assert np.isfinite(got)


class TestRMSNorm:
    """Fused RMSNorm kernel (plain + gated) vs jnp oracle."""

    @pytest.mark.parametrize("shape", [(4, 8, 64), (2, 100), (16, 2304)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_plain(self, shape, dtype):
        from repro.kernels.rmsnorm import ref as rref

        x = rnd(shape, dtype, 0)
        s = rnd(shape[-1:], jnp.float32, 1).astype(dtype) + 1.0
        got = api.launch("rmsnorm", x, s)
        want = rref.rmsnorm(x, s)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol(dtype))

    @pytest.mark.parametrize("shape", [(3, 7, 96), (8, 512)])
    def test_gated(self, shape):
        from repro.kernels.rmsnorm import ref as rref

        x, z = rnd(shape, jnp.float32, 0), rnd(shape, jnp.float32, 1)
        s = jnp.ones(shape[-1:])
        np.testing.assert_allclose(
            np.asarray(api.launch("rmsnorm.gated", x, z, s)),
            np.asarray(rref.gated_rmsnorm(x, z, s)), rtol=1e-5, atol=1e-6)

    def test_matches_model_norm_layer(self, monkeypatch):
        """The kernel agrees with blocks.apply_norm's *jnp* branch (the
        multi-device fallback).  On one device apply_norm routes through
        this very kernel, so the fallback is pinned explicitly -- otherwise
        the comparison is kernel vs itself and the jnp math loses its only
        parity coverage."""
        from repro.models import blocks
        from repro.models.config import ModelConfig

        monkeypatch.setattr(blocks, "use_fused_kernels", lambda: False)
        cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=96,
                          n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                          dtype="float32")
        x = rnd((2, 5, 96), jnp.float32, 0)
        p = {"scale": rnd((96,), jnp.float32, 1) + 1.0}
        np.testing.assert_allclose(
            np.asarray(api.launch("rmsnorm", x, p["scale"], eps=cfg.norm_eps)),
            np.asarray(blocks.apply_norm(p, x, cfg)), rtol=1e-5, atol=1e-6)
