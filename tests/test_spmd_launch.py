"""SPMD kernel launches: shard_map-partitioned registry kernels vs the jnp
reference on a forced multi-device host mesh.

The multi-device half of this file needs 8 CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m pytest -q tests/test_spmd_launch.py

which is exactly what the CI ``multidevice`` job runs -- once per mesh in
its matrix, selected via ``REPRO_SPMD_MESH`` ("DxM" = data x model;
default 2x4, plus 8x1 pure-data and 1x8 pure-model legs).  Under the
normal single-device tier-1 run those tests skip and only the
gating/declaration/comm-model tests execute (conftest deliberately sets
no XLA_FLAGS -- smoke tests must see the real device).

What the mesh tests pin down:

  * ``blocks.use_fused_kernels()`` is *true* on a multi-device mesh --
    such programs no longer silently fall back to jnp;
  * rmsnorm / rmsnorm.gated / xent / stream.triad launched via
    ``api.launch`` match ``api.ref`` to fp32 tolerance, forward and (for
    the model-path kernels) through the ``custom_vjp`` backward;
  * xent is *vocab-parallel* (Megatron layout): divisible vocabs shard
    over the model axis with the cross-shard lse combine, non-divisible
    vocabs fall back to replication with a logged reason;
  * jacobi is *halo-exchange*: grid rows shard over the data axis with
    one-row ppermute halos, exact at every shard boundary;
  * LBM is halo-exchange too: the X axis shards over the data axis with
    *per-direction* halo depth (only the 2x5 D3Q19 directions with
    c_x != 0 travel), bit-exact vs the single-device step;
  * both stencil bodies are *overlapped* (docs/OVERLAP.md): the halo
    ppermutes are independent of the interior Pallas sweep in the jaxpr
    (``api.spmd.overlap_report``), and the planner's
    ``predicted_exposed_comm_bytes`` prices what stays on the critical
    path (``repro.measure.validate --comm --exposed``);
  * each shard plans its own *local* block shape, and the planner's
    ``predicted_comm_bytes`` matches the collective census of the lowered
    program (``repro.measure.validate --comm``).
"""
import logging
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import spmd
from repro.core.planner import clear_plan_cache, plan_cache_keys
from repro.models import blocks
from repro.models.config import ModelConfig
from repro.models.transformer import lm_loss
from repro.parallel import rules

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)

MESH_SPEC = os.environ.get("REPRO_SPMD_MESH", "2x4")


def mesh_shape() -> tuple[int, int]:
    d, m = (int(x) for x in MESH_SPEC.lower().split("x"))
    return d, m


def make_mesh(d: int, m: int):
    return jax.sharding.Mesh(
        np.asarray(jax.devices()[:d * m]).reshape(d, m), ("data", "model")
    )


def env_mesh():
    """The matrix mesh this CI leg runs under (REPRO_SPMD_MESH)."""
    return make_mesh(*mesh_shape())


def mesh_key(mesh) -> tuple:
    return tuple(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))


def rnd(shape, seed, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def local_keys(kernel):
    return [k for k in plan_cache_keys() if k[0] == kernel and k[-1] is True]


def shard_dim(n: int, k: int) -> int:
    """Per-shard extent after the divisibility fallback."""
    return n // k if n % k == 0 else n


# ---------------------------------------------------------------------------
# Single-device: declarations and gating (run in tier-1 too)
# ---------------------------------------------------------------------------

class TestDeclarations:
    def test_every_registered_kernel_declares_partitioning(self):
        """Shipped kernels carry an explicit Partitioning -- replicated is a
        declaration too, the absence of one is only for third parties."""
        for name in api.list_kernels():
            entry = api.get_kernel(name)
            # ad-hoc registrations and the repro.analyze hazard fixtures
            # (deliberately undeclared) are not shipped surface
            if (not entry.body.__module__.startswith("repro.")
                    or entry.body.__module__.startswith("repro.analyze.")):
                continue
            assert isinstance(entry.partitioning, api.Partitioning), name

    def test_xent_declares_vocab_parallel(self):
        """The Megatron layout is declared, not emergent: logits shard over
        (batch, vocab) and the kernel owns its shard body (lse combine)."""
        entry = api.get_kernel("xent")
        assert entry.partitioning.in_axes[0] == ("batch", "vocab")
        assert entry.spmd_body is not None

    def test_jacobi_declares_halo_exchange(self):
        entry = api.get_kernel("jacobi")
        assert entry.partitioning.in_axes[0] == ("batch", None)
        assert entry.partitioning.out_axes == ("batch", None)
        assert entry.spmd_body is not None

    def test_lbm_declares_halo_exchange(self):
        """Both LBM layouts shard the X axis and own their per-direction
        halo exchange (the lattice is no longer replicated)."""
        for name in ("lbm.soa", "lbm.ivjk"):
            entry = api.get_kernel(name)
            assert entry.spmd_body is not None, name
            assert entry.partitioning.in_axes[0] == (None, "batch", None,
                                                     None), name
            assert entry.partitioning.out_axes == (None, "batch", None,
                                                   None), name

    def test_lbm_directional_halo_depths(self):
        """D3Q19 splits 5/5/9 over c_x: only the +x / -x direction groups
        cross an X cut, so the halo slab is (5, 1, Y, Z) per side -- the
        per-direction depth the comm model prices."""
        from repro.kernels.lbm import ops as lops
        from repro.kernels.lbm import ref as lref

        assert len(lops._PLUS_X) == 5
        assert len(lops._MINUS_X) == 5
        assert len(lops._ZERO_X) == 9
        for v in lops._PLUS_X:
            assert int(lref.C[v][0]) == 1
        for v in lops._MINUS_X:
            assert int(lref.C[v][0]) == -1
        for v in lops._ZERO_X:
            assert int(lref.C[v][0]) == 0

    def test_template_expansion(self):
        assert spmd._expand(("batch", ..., None), 2) == ("batch", None)
        assert spmd._expand(("batch", ..., None), 4) == (
            "batch", None, None, None)
        assert spmd._expand((...,), 3) == (None, None, None)
        assert spmd._expand(("batch",), 1) == ("batch",)
        with pytest.raises(ValueError, match="rank"):
            spmd._expand(("batch", ..., None), 1)
        with pytest.raises(ValueError, match="rank"):
            spmd._expand(("batch", None), 3)

    def test_scalar_out_requires_reduce(self):
        with pytest.raises(ValueError, match="cross-shard reduce"):
            api.Partitioning(in_axes=(("batch", None),), out_axes=spmd.SCALAR)
        with pytest.raises(ValueError, match="only applies to SCALAR"):
            api.Partitioning(in_axes=(("batch",),), out_axes=("batch",),
                             reduce="mean")
        with pytest.raises(ValueError, match="reduce must be one of"):
            api.Partitioning(in_axes=(("batch",),), out_axes=spmd.SCALAR,
                             reduce="max")

    def test_registry_rejects_non_partitioning(self):
        from repro.kernels.util import plan_args_1d

        with pytest.raises(TypeError, match="must be a"):
            @api.register_kernel(
                "stream.bad_part",
                signature=api.get_kernel("stream.copy").signature,
                ref=lambda a: a, plan_args=plan_args_1d,
                partitioning={"in_axes": ()})
            def _bad(plan, a):
                return a

    def test_registry_rejects_orphan_spmd_body(self):
        from repro.kernels.util import plan_args_1d

        with pytest.raises(TypeError, match="spmd_body without"):
            @api.register_kernel(
                "stream.bad_spmd_body",
                signature=api.get_kernel("stream.copy").signature,
                ref=lambda a: a, plan_args=plan_args_1d,
                spmd_body=lambda ctx, a: a)
            def _bad(plan, a):
                return a


class TestGating:
    """spmd_mesh() decides the route; every gate has a reason."""

    def test_no_context_mesh_means_no_spmd(self):
        assert spmd.spmd_mesh() is None

    def test_mapping_mesh_plans_but_does_not_place(self):
        with api.plan_context(mesh={"model": 4}):
            assert spmd.spmd_mesh() is None

    def test_single_device_mesh_is_not_spmd(self):
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        with api.plan_context(mesh=mesh):
            assert spmd.spmd_mesh() is None

    def test_spmd_false_opts_out(self):
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()).reshape(-1), ("data",))
        with api.plan_context(mesh=mesh, spmd=False):
            assert spmd.spmd_mesh() is None

    def test_use_fused_kernels_single_device(self):
        # No mesh anywhere: a one-device program (one chip of a multi-chip
        # host included) takes the fused kernels.
        assert blocks.use_fused_kernels()


class TestCommModel:
    """predicted_comm_bytes: the planner prices the SPMD collectives in
    closed form (ring cost model), no devices needed -- a mapping mesh is
    enough, which is also how the golden snapshots pin these numbers."""

    def test_xent_local_plan_prices_lse_combine(self):
        with api.plan_context(mesh={"data": 2, "model": 4}):
            p = api.plan_for("xent", (32, 512), jnp.float32, local=True)
        # pmax(m) + psum(l) + psum(ll): 3 x 32 fp32 over model=4, plus the
        # 4-byte scalar pmean over data=2, both at ring 2(N-1)/N.
        lse = int(2 * (4 - 1) / 4 * (3 * 32 * 4))
        scalar = int(2 * (2 - 1) / 2 * 4)
        assert p.predicted_comm_bytes == lse + scalar

    def test_jacobi_local_plan_prices_halo_rows(self):
        with api.plan_context(mesh={"data": 8}):
            p = api.plan_for("jacobi", (32, 258), jnp.float32, local=True)
        # one (1, 258) fp32 row ppermuted up and one down per sweep
        assert p.predicted_comm_bytes == 2 * 258 * 4

    def test_lbm_local_plan_prices_directional_halo(self):
        """Per-direction depth: only the 2x5 c_x != 0 directions cross an
        X cut, one (5, 1, Y, Z) slab each way -- not 19 full planes."""
        with api.plan_context(mesh={"data": 8}):
            ps = api.plan_for("lbm.soa", (19, 4, 8, 8), jnp.float32,
                              local=True)
            pi = api.plan_for("lbm.ivjk", (19, 4, 8, 8), jnp.float32,
                              local=True)
        assert ps.predicted_comm_bytes == 2 * 5 * 8 * 8 * 4
        assert pi.predicted_comm_bytes == ps.predicted_comm_bytes

    def test_unsharded_axes_price_zero(self):
        with api.plan_context(mesh={"data": 1, "model": 8}):
            p = api.plan_for("jacobi", (32, 258), jnp.float32, local=True)
            pl = api.plan_for("lbm.soa", (19, 32, 8, 8), jnp.float32,
                              local=True)
        assert p.predicted_comm_bytes == 0
        assert pl.predicted_comm_bytes == 0
        assert p.predicted_exposed_comm_bytes == 0

    def test_global_plans_price_zero(self):
        """A global plan describes the single-device direct path."""
        with api.plan_context(mesh={"data": 2, "model": 4}):
            p = api.plan_for("xent", (64, 512), jnp.float32)
        assert not p.local
        assert p.predicted_comm_bytes == 0

    def test_batch_parallel_families_price_zero(self):
        with api.plan_context(mesh={"data": 2, "model": 4}):
            p = api.plan_for("rmsnorm", (64, 129), jnp.float32, local=True)
        assert p.predicted_comm_bytes == 0

    def test_exposed_comm_partial_overlap(self):
        """Halo families subtract the interior hiding window: a thin
        jacobi stripe hides part of its two-row halo, the rest stays on
        the critical path."""
        from repro.core import planner

        with api.plan_context(mesh={"data": 8}):
            p = api.plan_for("jacobi", (8, 258), jnp.float32, local=True)
        total = 2 * 258 * 4
        assert p.predicted_comm_bytes == total
        # window = 2 streams x 6 interior rows x 258 cols x 4 B, hidden at
        # the ICI/HBM bandwidth ratio, never more than the total
        window = 2 * 6 * 258 * 4
        hidden = min(total, int(window * planner._ICI_BW / planner._HBM_BW))
        assert p.predicted_exposed_comm_bytes == total - hidden
        assert 0 < p.predicted_exposed_comm_bytes < total

    def test_exposed_comm_fully_hidden(self):
        """A tall stripe's interior window covers the whole halo: nothing
        stays exposed."""
        with api.plan_context(mesh={"data": 2}):
            p = api.plan_for("jacobi", (32, 258), jnp.float32, local=True)
        assert p.predicted_comm_bytes == 2 * 258 * 4
        assert p.predicted_exposed_comm_bytes == 0

    def test_exposed_comm_no_halo_model_is_fully_exposed(self):
        """Families without a HALO_MODEL entry (xent's lse combine has no
        interior stripe to hide behind) expose every wire byte."""
        with api.plan_context(mesh={"data": 2, "model": 4}):
            p = api.plan_for("xent", (32, 512), jnp.float32, local=True)
        assert p.predicted_comm_bytes > 0
        assert p.predicted_exposed_comm_bytes == p.predicted_comm_bytes

    def test_explain_reports_comm(self):
        with api.plan_context(mesh={"data": 2, "model": 4}):
            p = api.plan_for("xent", (32, 512), jnp.float32, local=True)
        txt = p.explain()
        assert f"comm {p.predicted_comm_bytes}B" in txt
        assert f"exposed {p.predicted_exposed_comm_bytes}B" in txt
        assert "local shard plan" in txt


class TestSpecReport:
    """rules.spec_report: the divisibility fallback comes with a reason."""

    def test_divisibility_fallback_is_reported(self):
        from repro.parallel import rules

        sizes = {"data": 2, "model": 4}
        s, fb = rules.spec_report("batch", "vocab", rules=rules.DEFAULT_RULES,
                                  shape=(64, 1111), axis_sizes=sizes)
        assert s == jax.sharding.PartitionSpec("data")
        assert len(fb) == 1
        assert "'vocab'" in fb[0] and "1111" in fb[0]
        assert "model" in fb[0]

    def test_clean_shard_reports_nothing(self):
        from repro.parallel import rules

        sizes = {"data": 2, "model": 4}
        s, fb = rules.spec_report("batch", "vocab", rules=rules.DEFAULT_RULES,
                                  shape=(64, 512), axis_sizes=sizes)
        assert s == jax.sharding.PartitionSpec("data", "model")
        assert fb == []


# ---------------------------------------------------------------------------
# Multi-device: the CI `multidevice` job's substance
# ---------------------------------------------------------------------------

@multidevice
class TestSpmdForward:
    def test_fused_gate_flips_on_mesh(self):
        mesh = env_mesh()
        assert blocks.use_fused_kernels()       # 8 devices, no mesh: one
        with api.plan_context(mesh=mesh):       # device's program
            assert spmd.spmd_mesh() is mesh
            assert blocks.use_fused_kernels()
        with api.plan_context(mesh=mesh, spmd=False):
            assert not blocks.use_fused_kernels()
        with rules.use_rules(rules.DEFAULT_RULES, mesh=mesh), \
                api.plan_context(spmd=False):
            assert not blocks.use_fused_kernels()

    def test_rmsnorm_shard_map_parity_and_local_plan(self):
        mesh = env_mesh()
        d, _ = mesh_shape()
        x = rnd((8, 16, 64), 0)
        s = rnd((64,), 1) + 1.5
        clear_plan_cache()
        with api.plan_context(mesh=mesh):
            got = api.launch("rmsnorm", x, s, eps=1e-6)
        want = api.ref("rmsnorm", x, s, eps=1e-6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        # per-shard plan: batch 8 split over the data axis
        keys = local_keys("rmsnorm")
        assert any(k[1] == (shard_dim(8, d) * 16, 64) for k in keys), keys
        assert all(k[3] == mesh_key(mesh) for k in keys)

    def test_local_plan_width_not_tp_widened(self):
        mesh = make_mesh(2, 4)
        with api.plan_context(mesh=mesh):
            glob = api.plan_for("rmsnorm", (64, 129), jnp.float32)
            loc = api.plan_for("rmsnorm", (64, 129), jnp.float32, local=True)
        assert glob.width == 512     # round_up(129, 128 * tp=4)
        assert loc.width == 256      # round_up(129, 128): shard has no cut
        assert loc.width < glob.width

    def test_gated_rmsnorm_parity(self):
        mesh = env_mesh()
        x, z = rnd((6, 8, 129), 0), rnd((6, 8, 129), 1)
        s = rnd((129,), 2) + 1.0
        with api.plan_context(mesh=mesh):
            got = api.launch("rmsnorm.gated", x, z, s, eps=1e-6)
        want = api.ref("rmsnorm.gated", x, z, s, eps=1e-6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_xent_parity_and_local_plan(self):
        """Non-divisible vocab (1111): the vocab split falls back to
        replication, tokens still shard, result still exact."""
        mesh = env_mesh()
        d, _ = mesh_shape()
        logits = rnd((64, 1111), 0) * 3
        labels = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, 1000)
        clear_plan_cache()
        with api.plan_context(mesh=mesh):
            got = api.launch("xent", logits, labels, logical_v=1000)
        want = api.ref("xent", logits, labels, logical_v=1000)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        # tokens split over the data axis, vocab whole per shard
        assert any(k[1] == (shard_dim(64, d), 1111)
                   for k in local_keys("xent"))

    def test_stream_triad_sharded_vector(self):
        mesh = env_mesh()
        b, c = rnd((4096,), 0), rnd((4096,), 1)
        with api.plan_context(mesh=mesh):
            got = api.launch("stream.triad", b, c, s=3.0)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(api.ref("stream.triad", b, c,
                                                      s=3.0)),
                                   rtol=1e-6, atol=1e-6)

    def test_lbm_sharded_launch_matches_ref(self):
        """LBM through the sharded halo-exchange path (or its divisibility
        fallback, mesh-dependent) still matches the jnp reference."""
        mesh = env_mesh()
        from repro.kernels.lbm import ops as lops

        f = lops.init_equilibrium(6, jnp.float32)
        with api.plan_context(mesh=mesh):
            lbm = api.launch("lbm.soa", f, omega=1.2)
        np.testing.assert_allclose(np.asarray(lbm),
                                   np.asarray(api.ref("lbm.soa", f,
                                                      omega=1.2)),
                                   rtol=1e-5, atol=1e-6)

    def test_non_divisible_batch_replicates_and_matches(self):
        """7 rows cannot split over the data axis: the spec falls back to
        replication instead of producing ragged shards."""
        mesh = env_mesh()
        x = rnd((7, 129), 0)
        s = rnd((129,), 1) + 1.0
        with api.plan_context(mesh=mesh):
            got = api.launch("rmsnorm", x, s, eps=1e-6)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(api.ref("rmsnorm", x, s,
                                                      eps=1e-6)),
                                   rtol=2e-5, atol=2e-6)

    def test_pinned_plan_skips_spmd(self):
        """An explicit plan pins a single-device launch (the plan describes
        one global layout, not a per-shard one)."""
        mesh = env_mesh()
        b, c = rnd((1024,), 0), rnd((1024,), 1)
        with api.plan_context(mesh=mesh):
            plan = api.plan_for("stream.triad", (1024,), jnp.float32)
            got = api.launch("stream.triad", b, c, s=3.0, plan=plan)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(api.ref("stream.triad", b, c,
                                                      s=3.0)),
                                   rtol=1e-6, atol=1e-6)

    def test_override_warning_names_cell_and_dedupes_per_mesh(self):
        """The SPMD-shadowed-override warning carries the offending cell
        key and a docs pointer, once per (kernel, mesh) -- a second mesh
        re-warns, a second launch on the same mesh does not."""
        from repro.api import dispatch

        b, c = rnd((1024,), 0), rnd((1024,), 1)
        plan = api.plan_for("stream.triad", (1024,), jnp.float32)
        dispatch._SPMD_OVERRIDE_WARNED.clear()
        with api.plan_context(mesh=env_mesh(),
                              plan_overrides={"stream.triad": plan}):
            with pytest.warns(RuntimeWarning) as rec:
                api.launch("stream.triad", b, c, s=3.0)
            assert "stream.triad" in str(rec[0].message)
            assert "docs/SPMD.md" in str(rec[0].message)
            with warnings.catch_warnings():
                warnings.simplefilter("error")  # same mesh: no re-warn
                api.launch("stream.triad", b, c, s=3.0)
        other = make_mesh(*reversed(mesh_shape()))
        with api.plan_context(mesh=other,
                              plan_overrides={"stream.triad": plan}):
            with pytest.warns(RuntimeWarning):
                api.launch("stream.triad", b, c, s=3.0)

    def test_local_keyed_override_does_not_warn(self):
        """A cell keyed at the per-shard *local* shape is the documented
        SPMD sweep workflow: it applies inside the shard body and must not
        be flagged as shadowed."""
        from repro.api import dispatch

        mesh = make_mesh(2, 4)  # data axis > 1 so local != global
        b, c = rnd((1024,), 0), rnd((1024,), 1)
        with api.plan_context(mesh=mesh):
            local = api.plan_for("stream.triad", (512,), jnp.float32,
                                 local=True)
        cell = ("stream.triad", (512,), "float32")
        dispatch._SPMD_OVERRIDE_WARNED.clear()
        with api.plan_context(mesh=mesh, plan_overrides={cell: local}):
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                api.launch("stream.triad", b, c, s=3.0)


@multidevice
class TestVocabParallelXent:
    """The Megatron layout under shard_map: vocab shards over the model
    axis, the lse combine crosses shards, forward and backward."""

    def test_pure_model_mesh_vocab_sharded(self):
        """8-way model-parallel: logits vocab-sharded in the shard body (no
        full-vocab replication), fp32 parity vs the jnp reference."""
        mesh = make_mesh(1, 8)
        logits = rnd((64, 4096), 0) * 3
        labels = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, 4000)
        clear_plan_cache()
        with api.plan_context(mesh=mesh):
            got = api.launch("xent", logits, labels, logical_v=4000)
        want = api.ref("xent", logits, labels, logical_v=4000)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        # the shard body planned on the (64, 512) vocab shard -- the whole
        # point: no local plan at the full 4096 vocab exists
        keys = local_keys("xent")
        assert any(k[1] == (64, 512) for k in keys), keys
        assert not any(k[1] == (64, 4096) for k in keys), keys

    def test_env_mesh_vocab_sharded_with_logical_v(self):
        """On the matrix mesh: divisible vocab shards over whatever model
        axis the leg has; logical_v masking crosses shard boundaries."""
        mesh = env_mesh()
        d, m = mesh_shape()
        logits = rnd((64, 512), 0) * 3
        labels = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, 500)
        clear_plan_cache()
        with api.plan_context(mesh=mesh):
            got = api.launch("xent", logits, labels, logical_v=500)
        want = api.ref("xent", logits, labels, logical_v=500)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        assert any(k[1] == (shard_dim(64, d), shard_dim(512, m))
                   for k in local_keys("xent"))

    def test_small_vocab_shard_narrower_than_lane_tile(self):
        """A 32-wide vocab shard pads to the 128-lane tile; padded local
        columns alias other shards' label ranges and must stay masked."""
        mesh = make_mesh(1, 8)
        logits = rnd((32, 256), 0) * 2
        labels = jax.random.randint(jax.random.PRNGKey(1), (32,), 0, 256)
        with api.plan_context(mesh=mesh):
            got = api.launch("xent", logits, labels, logical_v=256)
        want = api.ref("xent", logits, labels, logical_v=256)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_non_divisible_vocab_falls_back_with_logged_reason(self, caplog):
        mesh = make_mesh(1, 8)
        logits = rnd((16, 1111), 0) * 3
        labels = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 1000)
        spmd._FALLBACK_LOGGED.clear()
        with caplog.at_level(logging.INFO, logger="repro.api.spmd"):
            with api.plan_context(mesh=mesh):
                got = api.launch("xent", logits, labels, logical_v=1000)
        want = api.ref("xent", logits, labels, logical_v=1000)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        msgs = [r.getMessage() for r in caplog.records]
        assert any("'vocab'" in m and "1111" in m and "xent" in m
                   for m in msgs), msgs

    def test_xent_grad_vocab_parallel_matches_jnp(self):
        from repro.kernels.xent import ops as xent_ops

        mesh = make_mesh(1, 8)
        logits = rnd((64, 512), 0) * 3
        labels = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, 500)
        with api.plan_context(mesh=mesh):
            d = xent_ops.xent_grad(logits, labels, jnp.float32(1.0),
                                   logical_v=500)
        _, vjp = jax.vjp(
            lambda l: api.ref("xent", l, labels, logical_v=500), logits)
        np.testing.assert_allclose(np.asarray(d), np.asarray(vjp(
            jnp.float32(1.0))[0]), rtol=2e-5, atol=2e-6)


@multidevice
class TestHaloJacobi:
    """Row-block jacobi with one-row ppermute halos: exact at every shard
    boundary, multi-sweep stable, non-divisible rows fall back."""

    def test_pure_data_mesh_eight_shards(self):
        mesh = make_mesh(8, 1)
        g = rnd((64, 34), 0)
        clear_plan_cache()
        with api.plan_context(mesh=mesh):
            got = api.launch("jacobi", g)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(api.ref("jacobi", g)),
                                   rtol=1e-5, atol=1e-6)
        # the shard body planned on its 8-row stripe, not the full grid
        assert any(k[1] == (8, 34) for k in local_keys("jacobi"))

    def test_shard_boundary_rows_exact(self):
        """The halo rows are the whole point: check the rows adjacent to
        every shard cut bitwise-closely against the reference."""
        mesh = make_mesh(8, 1)
        g = rnd((64, 34), 3)
        with api.plan_context(mesh=mesh):
            got = np.asarray(api.launch("jacobi", g))
        want = np.asarray(api.ref("jacobi", g))
        nl = 64 // 8
        for cut in range(nl, 64, nl):
            np.testing.assert_allclose(got[cut - 1:cut + 1],
                                       want[cut - 1:cut + 1],
                                       rtol=1e-6, atol=1e-7)

    def test_env_mesh_multi_sweep(self):
        mesh = env_mesh()
        g = rnd((64, 37), 1)
        ref_g = g
        with api.plan_context(mesh=mesh):
            out = g
            for _ in range(3):
                out = api.launch("jacobi", out)
        for _ in range(3):
            ref_g = api.ref("jacobi", ref_g)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_g),
                                   rtol=1e-5, atol=1e-6)

    def test_non_divisible_rows_fall_back_with_logged_reason(self, caplog):
        mesh = make_mesh(8, 1)
        g = rnd((65, 34), 2)
        spmd._FALLBACK_LOGGED.clear()
        with caplog.at_level(logging.INFO, logger="repro.api.spmd"):
            with api.plan_context(mesh=mesh):
                got = api.launch("jacobi", g)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(api.ref("jacobi", g)),
                                   rtol=1e-5, atol=1e-6)
        msgs = [r.getMessage() for r in caplog.records]
        assert any("jacobi" in m and "65" in m for m in msgs), msgs


@multidevice
class TestCommValidation:
    """measure/validate --comm: the planner's predicted_comm_bytes vs the
    collective census of the lowered shard_map program."""

    def test_all_families_within_envelope_on_env_mesh(self):
        from repro.measure import validate as validate_lib

        mesh = env_mesh()
        records = validate_lib.validate_comm(mesh)
        assert {r["kernel"] for r in records} == {
            "jacobi", "xent", "lbm.soa", "lbm.ivjk"}
        for r in records:
            assert r["status"] == "ok", r

    def test_exposed_records_within_envelope_on_env_mesh(self):
        """validate --comm --exposed: one exposed_comm record per comm
        kernel, every halo collective structured as overlappable, wire
        bytes left on the critical path within the envelope."""
        from repro.measure import validate as validate_lib

        mesh = env_mesh()
        records = validate_lib.validate_comm(mesh, exposed=True)
        exposed = [r for r in records if r["check"] == "exposed_comm"]
        assert {r["kernel"] for r in exposed} == {
            "jacobi", "xent", "lbm.soa", "lbm.ivjk"}
        for r in records:
            assert r["status"] == "ok", r
        for r in exposed:
            assert r["structure_ok"], r
            if r["kernel"] != "xent" and r["predicted"]["comm_bytes"]:
                # halo families: every collective independent of the
                # interior sweep
                assert all(c["overlappable"]
                           for c in r["measured"]["collectives"]), r

    def test_vocab_parallel_mesh_prices_lse_payload(self):
        from repro.measure import validate as validate_lib

        rec = validate_lib.validate_comm_kernel("xent", make_mesh(1, 8))
        assert rec["status"] == "ok", rec
        assert rec["predicted"]["comm_bytes"] > 0
        # 3 token-length fp32 vectors at ring cost over model=8
        assert rec["predicted"]["comm_bytes"] == int(2 * 7 / 8 * 3 * 64 * 4)

    def test_halo_mesh_prices_two_rows(self):
        from repro.measure import validate as validate_lib

        rec = validate_lib.validate_comm_kernel("jacobi", make_mesh(8, 1))
        assert rec["status"] == "ok", rec
        assert rec["predicted"]["comm_bytes"] == 2 * 258 * 4

    def test_lbm_halo_mesh_prices_directional_slabs(self):
        from repro.measure import validate as validate_lib

        rec = validate_lib.validate_comm_kernel("lbm.soa", make_mesh(8, 1))
        assert rec["status"] == "ok", rec
        # two (5, 1, 8, 8) fp32 slabs per step
        assert rec["predicted"]["comm_bytes"] == 2 * 5 * 8 * 8 * 4

    def test_exposed_comm_event_streams(self):
        """The exposed_comm ValidationEvent carries the record's numbers
        (the obs half of validate --comm --exposed)."""
        from repro import obs
        from repro.measure import validate as validate_lib

        ring = obs.RingBufferSink()
        with obs.session(ring):
            rec = validate_lib.validate_exposed_kernel(
                "jacobi", make_mesh(8, 1))
        (ev,) = ring.events("validation")
        assert ev.kernel == "jacobi"
        assert ev.check == "exposed_comm"
        assert ev.predicted_bytes == float(
            rec["predicted"]["exposed_comm_bytes"])
        assert ev.measured_bytes == float(
            rec["measured"]["exposed_wire_bytes"])
        assert ev.status == rec["status"] == "ok"


@multidevice
class TestOverlapStructure:
    """api.spmd.overlap_report: the jaxpr-level classifier behind
    validate --exposed.  The overlapped shard bodies keep their halo
    collectives independent of the interior Pallas sweep; the PR-5
    exchange-then-compute shape (kept as ``_spmd_jacobi_blocking``) is the
    blocking counter-example."""

    def test_overlapped_jacobi_collectives_are_overlappable(self):
        mesh = make_mesh(8, 1)
        src = jnp.zeros((64, 34), jnp.float32)
        with api.plan_context(mesh=mesh):
            rep = spmd.overlap_report(
                lambda a: api.launch("jacobi", a), src)
        assert rep.n_pallas_calls >= 1
        assert len(rep.collectives) == 2            # one ppermute each way
        assert rep.all_overlappable
        for c in rep.collectives:
            assert c.primitive == "ppermute"
            assert c.result_bytes == 34 * 4         # one local row

    def test_blocking_body_is_classified_blocking(self):
        import dataclasses

        from repro.kernels.jacobi import ops as jops

        mesh = make_mesh(8, 1)
        src = jnp.zeros((64, 34), jnp.float32)
        entry = api.get_kernel("jacobi")
        blocking = dataclasses.replace(
            entry, spmd_body=jops._spmd_jacobi_blocking)
        with api.plan_context(mesh=mesh):
            rep = spmd.overlap_report(
                lambda a: spmd.spmd_launch(blocking, mesh, (a,), {}), src)
        assert rep.n_pallas_calls >= 1
        assert len(rep.collectives) == 2
        assert not rep.all_overlappable
        assert rep.n_overlappable == 0

    def test_lbm_halo_slabs_are_overlappable_and_directional(self):
        mesh = make_mesh(8, 1)
        f = jnp.zeros((19, 32, 8, 8), jnp.float32)
        for kernel in ("lbm.soa", "lbm.ivjk"):
            with api.plan_context(mesh=mesh):
                rep = spmd.overlap_report(
                    lambda a: api.launch(kernel, a, omega=1.7), f)
            assert rep.all_overlappable, kernel
            assert len(rep.collectives) == 2, kernel
            for c in rep.collectives:
                # (5, 1, 8, 8) fp32: five directions, depth one -- the
                # per-direction payload, not 19 full planes
                assert c.result_bytes == 5 * 8 * 8 * 4

    def test_xent_lse_combine_is_blocking(self):
        """No interior stripe to hide behind: the lse combine collectives
        stay on the critical path, matching the planner's fully-exposed
        pricing for families without a HALO_MODEL entry."""
        mesh = make_mesh(1, 8)
        logits = jnp.zeros((64, 4096), jnp.float32)
        labels = jnp.zeros((64,), jnp.int32)
        with api.plan_context(mesh=mesh):
            rep = spmd.overlap_report(
                lambda lg, tg: api.launch("xent", lg, tg), logits, labels)
        assert rep.collectives
        assert rep.n_overlappable == 0


@multidevice
class TestHaloLbm:
    """X-sharded LBM with per-direction ppermute halos: bit-exact vs the
    single-device Pallas step at every shard cut (the overlap criterion),
    periodic wrap included."""

    @staticmethod
    def _single_device(layout, f, omega, mask=None):
        from repro.kernels.lbm import ops as lops

        step = lops._step_soa if layout == "soa" else lops._step_ivjk
        plan = api.plan_for(f"lbm.{layout}", tuple(f.shape), f.dtype)
        return step(f, omega=omega, mask=mask, plan=plan)

    @pytest.mark.parametrize("layout", ["soa", "ivjk"])
    def test_pure_data_mesh_bit_exact(self, layout):
        mesh = make_mesh(8, 1)
        f = rnd((19, 32, 8, 8), 0)
        clear_plan_cache()
        with api.plan_context(mesh=mesh):
            got = api.launch(f"lbm.{layout}", f, omega=1.7)
        want = self._single_device(layout, f, 1.7)
        assert jnp.array_equal(got, want), (
            f"lbm.{layout} sharded step differs from single-device")
        # the shard body planned its local *interior* slab (XL=4 stripe
        # minus the two boundary planes), not the full lattice
        assert any(k[1] == (19, 2, 8, 8)
                   for k in local_keys(f"lbm.{layout}")), (
            local_keys(f"lbm.{layout}"))
        assert not any(k[1] == (19, 32, 8, 8)
                       for k in local_keys(f"lbm.{layout}"))

    @pytest.mark.parametrize("layout", ["soa", "ivjk"])
    def test_env_mesh_bit_exact(self, layout):
        mesh = env_mesh()
        f = rnd((19, 32, 8, 8), 1)
        with api.plan_context(mesh=mesh):
            got = api.launch(f"lbm.{layout}", f, omega=1.2)
        want = self._single_device(layout, f, 1.2)
        assert jnp.array_equal(got, want)

    def test_degenerate_two_plane_shards_bit_exact(self):
        """XL == 2: every plane is a boundary plane, nothing interior."""
        mesh = make_mesh(8, 1)
        f = rnd((19, 16, 4, 4), 2)
        with api.plan_context(mesh=mesh):
            got = api.launch("lbm.soa", f, omega=1.7)
        want = self._single_device("soa", f, 1.7)
        assert jnp.array_equal(got, want)

    def test_masked_launch_bit_exact(self):
        """The obstacle mask is a replicated scalar operand: each shard
        slices its own X window, masked sites keep pre-collision values."""
        mesh = make_mesh(8, 1)
        f = rnd((19, 32, 8, 8), 3)
        mask = jax.random.bernoulli(
            jax.random.PRNGKey(4), 0.7, (32, 8, 8))
        with api.plan_context(mesh=mesh):
            got = api.launch("lbm.soa", f, omega=1.7, mask=mask)
        want = self._single_device("soa", f, 1.7, mask=mask)
        assert jnp.array_equal(got, want)

    def test_periodic_wrap_crosses_domain_edge(self):
        """Pull-scheme streaming is periodic: shard 0's low halo is the
        *last* shard's high boundary (unlike jacobi's zero edges).  A
        lattice with a marked plane at x=31 must land at x=0 after one
        step in the +x directions."""
        from repro.kernels.lbm import ops as lops
        from repro.kernels.lbm import ref as lref

        mesh = make_mesh(8, 1)
        # uniform rest equilibrium (density 1) so collide stays finite,
        # plus a marked +x plane at the domain's last X slice
        w = jnp.asarray(np.asarray(lref.W, dtype=np.float32))
        f = jnp.broadcast_to(w[:, None, None, None],
                             (19, 32, 8, 8)).astype(jnp.float32)
        v = lops._PLUS_X[0]
        f = f.at[v, 31].add(1.0)
        with api.plan_context(mesh=mesh):
            got = api.launch("lbm.soa", f, omega=0.0)  # pure streaming
        want = self._single_device("soa", f, 0.0)
        assert jnp.array_equal(got, want)
        # with omega=0 post == fprop, so the marked plane must have
        # wrapped from x=31 to x=0 (the +1 rides on the w[v] background)
        assert float(jnp.max(jnp.asarray(got)[v, 0])) > float(w[v]) + 0.5


@multidevice
class TestOverlappedJacobiParity:
    """The overlapped jacobi body is bit-exact vs the PR-5
    exchange-then-compute body (ISSUE 9 acceptance criterion)."""

    @staticmethod
    def _blocking_entry():
        import dataclasses

        from repro.kernels.jacobi import ops as jops

        return dataclasses.replace(
            api.get_kernel("jacobi"), spmd_body=jops._spmd_jacobi_blocking)

    @pytest.mark.parametrize("shape", [(64, 34), (16, 130), (8, 34)])
    def test_overlapped_matches_blocking_all_cuts(self, shape):
        mesh = make_mesh(8, 1)
        g = rnd(shape, 5)
        entry = self._blocking_entry()
        with api.plan_context(mesh=mesh):
            overlapped = api.launch("jacobi", g)
            blocking = spmd.spmd_launch(entry, mesh, (g,), {})
        assert jnp.array_equal(overlapped, blocking), shape

    def test_overlapped_matches_blocking_env_mesh(self):
        mesh = env_mesh()
        g = rnd((64, 34), 6)
        entry = self._blocking_entry()
        with api.plan_context(mesh=mesh):
            overlapped = api.launch("jacobi", g)
            blocking = spmd.spmd_launch(entry, mesh, (g,), {})
        assert jnp.array_equal(overlapped, blocking)


@multidevice
class TestSpmdGradients:
    """custom_vjp backward through the shard_map forward (acceptance
    criterion: forward + gradient match jnp to fp32 tolerance)."""

    CFG = dict(name="t", family="dense", n_layers=1, d_model=64, n_heads=2,
               n_kv_heads=2, d_ff=64, vocab_size=128, dtype="float32",
               remat=False)

    def test_rms_fused_grads_match_ref(self):
        mesh = env_mesh()
        x = rnd((8, 16, 64), 0)
        s = rnd((64,), 1) + 1.5

        def fused(xx, ss):
            return blocks._rms_fused(xx, ss, 1e-6).astype(jnp.float32).sum()

        def ref(xx, ss):
            return blocks._rms_ref(xx, ss, 1e-6).astype(jnp.float32).sum()

        with api.plan_context(mesh=mesh):
            gx, gs = jax.grad(fused, argnums=(0, 1))(x, s)
        rx, rs = jax.grad(ref, argnums=(0, 1))(x, s)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(rs),
                                   rtol=2e-5, atol=2e-5)

    def test_lm_loss_fused_spmd_forward_and_grad(self):
        mesh = env_mesh()
        cfg = ModelConfig(**self.CFG)
        logits = rnd((4, 8, 128), 0) * 2
        labels = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 128)

        with api.plan_context(mesh=mesh):
            assert blocks.use_fused_kernels()
            loss = lm_loss(logits, labels, cfg)
            grad = jax.grad(lambda l: lm_loss(l, labels, cfg))(logits)
        # same mesh, SPMD off: the pure-jnp vocab-parallel reference
        with api.plan_context(mesh=mesh, spmd=False):
            assert not blocks.use_fused_kernels()
            ref_loss = lm_loss(logits, labels, cfg)
            ref_grad = jax.grad(lambda l: lm_loss(l, labels, cfg))(logits)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad),
                                   rtol=2e-5, atol=2e-6)

    def test_lm_loss_pure_model_mesh_keeps_megatron_layout(self):
        """The acceptance cell: an 8-way model-parallel mesh, fused lm_loss
        forward + grad vs jnp, with logits vocab-sharded in the shard body
        (the local plan cache proves no full-vocab local launch exists)."""
        mesh = make_mesh(1, 8)
        cfg = ModelConfig(**self.CFG)
        logits = rnd((4, 8, 128), 0) * 2
        labels = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 128)
        clear_plan_cache()
        with api.plan_context(mesh=mesh):
            loss = lm_loss(logits, labels, cfg)
            grad = jax.grad(lambda l: lm_loss(l, labels, cfg))(logits)
        with api.plan_context(mesh=mesh, spmd=False):
            ref_loss = lm_loss(logits, labels, cfg)
            ref_grad = jax.grad(lambda l: lm_loss(l, labels, cfg))(logits)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad),
                                   rtol=2e-5, atol=2e-6)
        keys = local_keys("xent")
        assert any(k[1] == (32, 16) for k in keys), keys      # 128/8 vocab
        assert not any(k[1] == (32, 128) for k in keys), keys

    def test_model_loss_end_to_end_jit(self):
        """Tiny dense LM: apply_norm + lm_loss both route through shard_map
        inside jit, value and every parameter gradient match the jnp path."""
        from repro.models import build_model

        mesh = env_mesh()
        model = build_model(ModelConfig(**self.CFG))
        params = model.init(jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                         128),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                                         128),
        }
        vg = jax.value_and_grad(model.loss)
        with api.plan_context(mesh=mesh):
            loss, grads = jax.jit(vg)(params, batch)
        with api.plan_context(mesh=mesh, spmd=False):
            ref_loss, ref_grads = jax.jit(vg)(params, batch)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)

        flat, _ = jax.tree_util.tree_flatten(grads)
        rflat, _ = jax.tree_util.tree_flatten(ref_grads)
        for g, r in zip(flat, rflat):
            if g.dtype == jax.dtypes.float0:
                continue
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(r, np.float32),
                                       rtol=5e-5, atol=5e-6)

    def test_trainer_hot_plans_under_spmd_mesh(self):
        """plan_hot_kernels still pins the global-shape plans (launch-time
        re-derivation inside shard_map uses the local ones)."""
        from repro.data.pipeline import DataConfig
        from repro.optim import adamw
        from repro.optim.schedules import make_schedule
        from repro.runtime.trainer import Trainer, TrainerConfig
        from repro.models import build_model

        mesh = env_mesh()
        tr = Trainer(
            build_model(ModelConfig(**self.CFG)),
            DataConfig(vocab_size=128, seq_len=8, global_batch=4, d_model=64),
            adamw.AdamWConfig(master=False),
            make_schedule("cosine", peak=3e-3, warmup=2, total=8),
            TrainerConfig(n_steps=2, ckpt_every=2, ckpt_dir="/tmp/t_spmd"),
            mesh=mesh,
        )
        plans = tr.plan_hot_kernels()
        assert plans["xent"].mesh == mesh_key(mesh)
