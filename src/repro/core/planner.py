"""Layout planner: close the loop from stream analysis to Pallas execution.

The paper's headline claim (SS2.3) is that optimal padding/skew parameters
"can be obtained by analyzing the data access properties of the loop kernel,
together with some knowledge about the mapping between addresses and memory
controllers.  No trial and error is required."  This module is that claim
made executable for the TPU port: each kernel family declares its
``StreamSignature`` (how many read/write streams of what element size), and
the planner derives -- in closed form, no search --

  * the padded *physical* shape (lane/sublane tileable, optionally widened
    for a tensor-parallel mesh axis),
  * the Pallas block shape (``choose_block_shape``: whole-line DMAs that fit
    the VMEM budget with one buffer per resident stream),
  * the per-stream skews and segment shift (``plan_streams``), scored under
    the interleaved-memory conflict model.

``predicted_balance`` evaluates the *whole* plan: stream k skewed by
k x channel-step AND concurrent segments shifted by one channel step, which
is what guarantees full channel coverage for any stream count (the paper's
Jacobi case: 2 streams alone cover only 2 of 4 controllers; the segment
shift supplies the rest).  ``naive_balance`` scores the same streams with no
skew and period-aliased segments -- the paper's 4x collapse -- so
``explain()`` reports the analytically-predicted gain.

Plans are memoized in a process-level cache keyed on
``(kernel, shape, dtype, mesh, model)`` so repeated wrapper calls (and
re-traces under jit) reuse the same ``KernelPlan`` object.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Mapping

import numpy as np

from repro.core.aliasing import InterleavedMemoryModel, Stream
from repro.core.autotune import LayoutPlan, StreamSignature, plan_streams
from repro.core.layout import (
    LANES,
    PIPELINE_DEPTH,
    SUBLANES,
    VMEM_BYTES,
    cdiv,
    choose_block_shape,
    round_down,
    round_up,
)

# Widest 1-D reshape width the planner will choose: long enough that every
# DMA moves whole VREG tiles with low per-transfer overhead, small enough
# that n_streams blocks of any planned kernel fit VMEM comfortably.
MAX_WIDTH = 4096

# Sublane tile height per element size: fp32 packs (8, 128) VREG tiles,
# 2-byte dtypes (bf16/fp16) pack (16, 128), fp8/int8 pack (32, 128).  Using
# the dtype's native tile keeps the physical footprint equal to what XLA
# would materialize anyway -- and at 2 (or 1) bytes per element the padding
# the plan *pays* shrinks accordingly.
SUBLANES_BY_ITEMSIZE: dict[int, int] = {1: 32, 2: 16}


def sublanes_for_dtype(dtype) -> int:
    """Native sublane tile height for ``dtype`` (8 fp32 / 16 bf16 / 32 fp8)."""
    return SUBLANES_BY_ITEMSIZE.get(np.dtype(dtype).itemsize, SUBLANES)


# The paper's per-kernel "data access properties" table: how many read and
# write streams each kernel family drives against HBM.  Element size is
# rebound to the actual dtype at planning time.
FAMILIES: dict[str, StreamSignature] = {
    "stream.copy": StreamSignature(n_read=1, n_write=1),
    "stream.scale": StreamSignature(n_read=1, n_write=1),
    "stream.add": StreamSignature(n_read=2, n_write=1),
    "stream.triad": StreamSignature(n_read=2, n_write=1),
    "triad": StreamSignature(n_read=3, n_write=1),          # Schoenauer B+C*D
    "jacobi": StreamSignature(n_read=1, n_write=1),         # rows stream once
    "lbm.soa": StreamSignature(n_read=19, n_write=19),      # D3Q19 collide
    "lbm.ivjk": StreamSignature(n_read=19, n_write=19),
    "rmsnorm": StreamSignature(n_read=2, n_write=1),        # x, scale -> y
    "rmsnorm.gated": StreamSignature(n_read=3, n_write=1),  # x, z, scale -> y
    "xent": StreamSignature(n_read=2, n_write=1),           # logits, labels
}

# D3Q19 direction count, needed for the LBM block geometry.  Kept local so
# core never imports the kernels package.
_LBM_Q = 19

# VMEM-resident buffer count per family when it differs from the HBM stream
# count + 1: jacobi's three shifted row views are distinct Pallas operands
# even though they stream each source row from HBM only once.
VMEM_BUFFERS: dict[str, int] = {"jacobi": 4}

# Families whose kernels tile the minor dim too (blocked columns).  All
# other 2-D kernels stream full-width row blocks, so their row budget must
# be charged against the whole padded width.
COL_TILED = {"xent"}

# ---------------------------------------------------------------------------
# Traffic accounting (measured-vs-predicted validation, paper Fig. 4)
# ---------------------------------------------------------------------------
# How many of a family's streams move a *full planned array* each launch.
# The balance model above treats every stream as equal-weight when scoring
# channel conflicts; traffic prediction must not -- jacobi's three shifted
# row views stream each source row from HBM once, the LBM lattice already
# contains all 19 direction rows, and rmsnorm/xent carry small side operands.
# Families absent here move one full array per signature stream.
MAJOR_STREAMS: dict[str, int] = {
    "jacobi": 2,         # grid in + grid out; shifted views hit cached rows
    "lbm.soa": 2,        # lattice read + written once (19+19 direction rows)
    "lbm.ivjk": 2,
    "rmsnorm": 2,        # x in + y out; scale is a width-sized minor stream
    "rmsnorm.gated": 3,  # x, z in + y out
    "xent": 1,           # logits; labels and per-token nll are row-sized
}

# Minor side-operand bytes per launch: (rows, width, elem_bytes) -> bytes.
# labels are int32 and nll is fp32 regardless of the logits dtype.
MINOR_STREAM_BYTES: dict[str, Callable[[int, int, int], int]] = {
    "rmsnorm": lambda rows, width, eb: width * eb,
    "rmsnorm.gated": lambda rows, width, eb: width * eb,
    "xent": lambda rows, width, eb: rows * 4 + rows * 4,
}

# ---------------------------------------------------------------------------
# Predicted interconnect traffic (communication-minimal SPMD launches)
# ---------------------------------------------------------------------------
# Per-device wire bytes one SPMD launch of a *local* (per-shard) plan moves,
# under the standard ring cost model (the same formulas
# ``launch.lowering.collective_census`` applies to measured HLO):
#
#     all-reduce           2 (N-1)/N x payload
#     collective-permute               payload
#
# The mesh-axis names are the ``parallel.rules.DEFAULT_RULES`` targets the
# kernels' partitioning declarations resolve to ("batch" -> data, "vocab" ->
# model); a launcher that renames its axes should keep the rule table in
# sync.  The model assumes the declared partitioning engaged -- a
# divisibility fallback to replication moves fewer bytes than predicted,
# which the validation envelope absorbs.  Families absent here communicate
# nothing (batch-parallel shards are independent).


def _ring_all_reduce_bytes(payload: int, n: int) -> int:
    return int(2 * (n - 1) / n * payload) if n > 1 else 0


def _comm_jacobi(plan: "KernelPlan", sizes: Mapping[str, int]) -> int:
    # One (1, cols) halo row ppermuted up and one down per sweep; the halo
    # is exchanged at the logical column count (padding happens after the
    # exchange, inside the shard).
    d = sizes.get("data", 1)
    if d <= 1:
        return 0
    return 2 * int(plan.logical_shape[-1]) * plan.elem_bytes


def _comm_xent(plan: "KernelPlan", sizes: Mapping[str, int]) -> int:
    # Vocab-parallel lse combine: pmax(m) + psum(l) + psum(label_logit),
    # three fp32 vectors over the local token rows, all-reduced across the
    # model axis; plus the 4-byte scalar pmean of the per-shard NLL over the
    # batch axes.
    mv = sizes.get("model", 1)
    d = sizes.get("data", 1)
    rows = int(plan.logical_shape[0])
    total = _ring_all_reduce_bytes(3 * rows * 4, mv)
    total += _ring_all_reduce_bytes(4, d)
    return total


# Of D3Q19's 19 directions, 5 have c_x = +1 and 5 have c_x = -1 (one face
# + four edges each way); the other 9 never cross an X cut.  Hardcoded so
# core never imports the kernels package (same rule as _LBM_Q above).
_LBM_X_DIRS = 5


def _comm_lbm(plan: "KernelPlan", sizes: Mapping[str, int]) -> int:
    # X-sharded lattice (Q, X, Y, Z): per streaming step each shard
    # ppermutes one (5, 1, Y, Z) slab of +x-moving populations down-ring
    # and one slab of -x-moving populations up-ring -- only the 10
    # directions with nonzero c_x cross the cut, at depth |c_x| = 1.
    d = sizes.get("data", 1)
    if d <= 1:
        return 0
    y, z = (int(s) for s in plan.logical_shape[2:4])
    return 2 * _LBM_X_DIRS * y * z * plan.elem_bytes


COMM_MODEL: dict[str, Callable[["KernelPlan", Mapping[str, int]], int]] = {
    "jacobi": _comm_jacobi,
    "xent": _comm_xent,
    "lbm.soa": _comm_lbm,
    "lbm.ivjk": _comm_lbm,
}

# ---------------------------------------------------------------------------
# Exposed communication (the overlap term)
# ---------------------------------------------------------------------------
# Halo-exchange geometry per family: (sharded logical dim, halo depth).
# These are the families whose SPMD bodies are *overlapped* -- the halo
# ppermute is issued before interior-stripe compute, so the wire time can
# hide behind the interior memory stream.  The hideable fraction is the
# classic overlap bound: while the interior stripe streams
# ``MAJOR_STREAMS x interior_elems x elem_bytes`` through HBM, the link can
# move that window scaled by ICI_BW / HBM_BW; anything beyond that stays
# exposed on the critical path.  Families with a COMM_MODEL entry but no
# halo spec (xent's lse combine) block on their collective -- the compute
# that could hide it depends on the collective's result -- so their comm is
# fully exposed.  Bandwidths are the v5e roofline constants (also in
# benchmarks/roofline.py and launch/lowering.py, which core cannot import).
HALO_MODEL: dict[str, tuple[int, int]] = {
    "jacobi": (0, 1),     # one row up + one row down over the data axis
    "lbm.soa": (1, 1),    # X planes; 2 x 5 direction-slabs of depth 1
    "lbm.ivjk": (1, 1),
}
_HBM_BW = 819e9
_ICI_BW = 50e9


def register_family(
    name: str,
    signature: StreamSignature,
    *,
    vmem_buffers: int | None = None,
    col_tiled: bool = False,
) -> None:
    """Declare (or re-assert) a kernel family's stream signature.

    The registry (``repro.api.registry``) calls this when a kernel registers,
    so the planner's table and the registered kernels can never drift: a
    second declaration with a *different* signature or VMEM-buffer count is
    a shadowed name and raises instead of silently replacing the analysis.
    A declaration that introduces new block geometry (first ``vmem_buffers``
    or newly ``col_tiled``) drops the family's cached plans, so earlier
    plans made under the defaults cannot linger alongside new ones.
    """
    cur = FAMILIES.get(name)
    if cur is not None and (cur.n_read, cur.n_write) != (
            signature.n_read, signature.n_write):
        raise ValueError(
            f"kernel family {name!r} already declared with "
            f"{cur.n_read}R+{cur.n_write}W; refusing shadow declaration "
            f"{signature.n_read}R+{signature.n_write}W"
        )
    geometry_changed = False
    if vmem_buffers is not None:
        prev = VMEM_BUFFERS.get(name)
        if prev is not None and prev != vmem_buffers:
            raise ValueError(
                f"kernel family {name!r} already declared with "
                f"{prev} VMEM buffers; refusing shadow declaration "
                f"{vmem_buffers}"
            )
        geometry_changed = prev is None
    FAMILIES[name] = signature
    if vmem_buffers is not None:
        VMEM_BUFFERS[name] = vmem_buffers
    if col_tiled and name not in COL_TILED:
        COL_TILED.add(name)
        geometry_changed = True
    if geometry_changed:
        with _LOCK:
            for key in [k for k in _CACHE if k[0] == name]:
                del _CACHE[key]


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Everything a kernel wrapper needs to lay its arrays out.

    Frozen and hashable so wrappers can pass it as a jit-static argument;
    identical logical problems therefore share both the plan *and* the
    compiled executable.
    """

    kernel: str
    logical_shape: tuple[int, ...]
    dtype: str
    padded_shape: tuple[int, ...]
    block_shape: tuple[int, ...]
    signature: StreamSignature
    layout: LayoutPlan
    naive_balance: float
    mesh: tuple[tuple[str, int], ...] = ()
    sublanes: int = SUBLANES
    # True for a per-shard plan made by the SPMD launch path
    # (``plan_for(..., local=True)``): the shape is one device's slice, the
    # minor dim was not TP-re-widened, and ``predicted_comm_bytes`` below
    # describes the shard's collectives.
    local: bool = False
    # Where this plan came from: "analytic" (the planner's closed form) or a
    # measured source such as "sweep" / "profile:<path>" (see repro.measure).
    # Excluded from eq/hash: plans are jit-static arguments, and a
    # profile-loaded plan with analytic-identical geometry must share the
    # compiled executable, not force a recompile over a label.
    provenance: str = dataclasses.field(default="analytic", compare=False)

    # ---- geometry --------------------------------------------------------
    @property
    def rows(self) -> int:
        return self.padded_shape[0]

    @property
    def width(self) -> int:
        return self.padded_shape[-1]

    @property
    def block_rows(self) -> int:
        return self.block_shape[0]

    @property
    def block_cols(self) -> int:
        return self.block_shape[-1]

    @property
    def grid(self) -> tuple[int, ...]:
        return tuple(cdiv(p, b) for p, b in zip(self.padded_shape, self.block_shape))

    # ---- accounting ------------------------------------------------------
    @property
    def logical_elems(self) -> int:
        n = 1
        for s in self.logical_shape:
            n *= s
        return n

    @property
    def padded_elems(self) -> int:
        n = 1
        for s in self.padded_shape:
            n *= s
        return n

    @property
    def waste(self) -> float:
        """Fraction of the physical footprint that is padding."""
        p = self.padded_elems
        return (p - self.logical_elems) / p if p else 0.0

    @property
    def elem_bytes(self) -> int:
        return np.dtype(self.dtype).itemsize

    @property
    def padded_bytes(self) -> int:
        """Physical HBM footprint of one planned stream."""
        return self.padded_elems * self.elem_bytes

    @property
    def waste_bytes(self) -> int:
        """Padding overhead in bytes -- the hardware-meaningful waste metric
        (a bf16 plan with wider sublane tiles can pad more *elements* than
        the fp32 plan of the same logical shape yet cost fewer bytes)."""
        return (self.padded_elems - self.logical_elems) * self.elem_bytes

    @property
    def predicted_balance(self) -> float:
        return self.layout.predicted_balance

    @property
    def leading_stride_bytes(self) -> int:
        """Bytes between consecutive leading-dim slices of the padded array
        -- the row stride whose residue class modulo the interleave period
        decides which controllers a strided walk can reach (paper SS2.2)."""
        n = self.elem_bytes
        for s in self.padded_shape[1:]:
            n *= s
        return n

    # ---- predicted traffic ----------------------------------------------
    def _traffic_bytes(self, elems: int, shape: tuple[int, ...]) -> int:
        major = MAJOR_STREAMS.get(self.kernel, self.signature.n_streams)
        total = major * elems * self.elem_bytes
        minor = MINOR_STREAM_BYTES.get(self.kernel)
        if minor is not None:
            total += minor(int(shape[0]), int(shape[-1]), self.elem_bytes)
        return total

    @property
    def predicted_hbm_bytes(self) -> int:
        """Analytic HBM traffic per launch at the planned *physical*
        footprint: every major stream moves one padded array, plus the
        family's minor side operands.  This is the number the conflict model
        scores -- what ``repro.measure.validate`` checks against compiled
        HLO bytes-accessed (the paper's measured-vs-predicted envelope)."""
        return self._traffic_bytes(self.padded_elems, self.padded_shape)

    @property
    def predicted_logical_bytes(self) -> int:
        """Lower bound on the same traffic: the streams at their *logical*
        footprint (what a perfect compiler with no padding would move).
        ``predicted_hbm_bytes - predicted_logical_bytes`` is the traffic the
        plan pays for whole-tile DMAs -- the per-launch cost of
        ``waste_bytes``."""
        return self._traffic_bytes(self.logical_elems, self.logical_shape)

    @property
    def predicted_comm_bytes(self) -> int:
        """Analytic per-device interconnect traffic one SPMD launch of this
        plan moves (ring cost model; see ``COMM_MODEL``).  Nonzero only for
        *local* plans under a multi-axis mesh: a global plan describes the
        single-device direct path, which communicates nothing.  This is the
        number ``repro.measure.validate --comm`` checks against the
        collective census of the lowered shard_map program."""
        if not self.local or not self.mesh:
            return 0
        fn = COMM_MODEL.get(self.kernel)
        if fn is None:
            return 0
        return fn(self, dict(self.mesh))

    @property
    def predicted_exposed_comm_bytes(self) -> int:
        """The part of ``predicted_comm_bytes`` left on the critical path
        after overlap: total wire bytes minus what the interior-stripe
        compute window can hide (``HALO_MODEL``).  The overlapped shard
        bodies issue the halo ppermute before interior compute, so the link
        moves halo bytes while ``MAJOR_STREAMS x interior_elems`` stream
        through HBM; the hideable window is that HBM time converted to wire
        bytes at ICI_BW / HBM_BW.  Families without a halo spec (xent's
        blocking lse combine) expose everything.  This is the number
        ``repro.measure.validate --comm --exposed`` checks against the
        overlap structure of the lowered program."""
        total = self.predicted_comm_bytes
        if total == 0:
            return 0
        spec = HALO_MODEL.get(self.kernel)
        if spec is None:
            return total
        dim, depth = spec
        interior = [int(s) for s in self.logical_shape]
        interior[dim] = max(interior[dim] - 2 * depth, 0)
        elems = 1
        for s in interior:
            elems *= s
        major = MAJOR_STREAMS.get(self.kernel, self.signature.n_streams)
        window = major * elems * self.elem_bytes
        hidden = min(total, int(window * _ICI_BW / _HBM_BW))
        return total - hidden

    def explain(self) -> str:
        """Human-readable report: predicted balance, waste, block geometry."""
        sig = self.signature
        grid = "x".join(str(g) for g in self.grid)
        block = "x".join(str(b) for b in self.block_shape)
        return (
            f"plan[{self.kernel}] logical={self.logical_shape} {self.dtype}"
            f" -> physical {self.padded_shape}, block {block}, grid {grid},"
            f" sublanes {self.sublanes}\n"
            f"  streams: {sig.n_read}R+{sig.n_write}W x {sig.elem_bytes}B"
            f"  align={self.layout.align_bytes}B"
            f" offsets={self.layout.offsets_bytes}B"
            f" segment-shift={self.layout.segment_shift_bytes}B\n"
            f"  predicted balance {self.predicted_balance:.2f}"
            f" (naive {self.naive_balance:.2f}),"
            f" waste {self.waste:.1%}"
            f" ({self.padded_elems - self.logical_elems} pad elems)\n"
            f"  predicted traffic {self.predicted_hbm_bytes}B"
            f" (logical {self.predicted_logical_bytes}B,"
            f" comm {self.predicted_comm_bytes}B,"
            f" exposed {self.predicted_exposed_comm_bytes}B)"
            + ("" if not self.local
               else f"\n  local shard plan for mesh "
                    f"{dict(self.mesh) or '(none)'}")
            + ("" if self.provenance == "analytic"
               else f"\n  source: {self.provenance}")
        )


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------

_CACHE: dict[tuple, KernelPlan] = {}
_STATS = {"hits": 0, "misses": 0}
_LOCK = threading.RLock()
_DEFAULT_MODEL = InterleavedMemoryModel()


def _mesh_key(mesh) -> tuple[tuple[str, int], ...]:
    if mesh is None:
        return ()
    if hasattr(mesh, "axis_names") and hasattr(mesh, "devices"):
        return tuple(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))
    if isinstance(mesh, Mapping):
        return tuple(sorted((str(k), int(v)) for k, v in mesh.items()))
    return tuple((str(k), int(v)) for k, v in mesh)


def plan_kernel(
    kernel: str,
    shape,
    dtype,
    *,
    mesh=None,
    model: InterleavedMemoryModel | None = None,
    sublanes: int | None = None,
    vmem_budget: int | None = None,
    local: bool = False,
) -> KernelPlan:
    """Memoized analytic plan for ``kernel`` on a logical ``shape``/``dtype``.

    ``mesh`` (a jax Mesh, a mapping, or ``(axis, size)`` pairs) widens the
    minor-dim padding so every model-axis shard stays lane-aligned.
    ``sublanes`` overrides the dtype-derived sublane tile (8 fp32 / 16 bf16 /
    32 fp8); ``vmem_budget`` caps the per-core VMEM bytes the block chooser
    may assume.  Both default from the dtype / hardware and are normally
    supplied by the ambient ``repro.api.PlanContext``.

    ``local=True`` plans one *shard's* launch under the SPMD path
    (``repro.api.spmd``): ``shape`` is already a per-device slice, so the
    minor dim is padded only to the lane tile, not widened again by the
    mesh's tensor-parallel width -- the global array was split there, the
    local array was not.  The mesh still participates in the memo key, so
    per-shard plans are cached as ``(kernel, local_shape, dtype, mesh)``
    without colliding with global plans of the same shape.
    """
    if kernel not in FAMILIES:
        raise KeyError(
            f"unknown kernel family {kernel!r}; known: {sorted(FAMILIES)}"
        )
    dt = np.dtype(dtype)
    mesh_key = _mesh_key(mesh)
    model = model or _DEFAULT_MODEL
    sub = sublanes_for_dtype(dt) if sublanes is None else int(sublanes)
    budget = VMEM_BYTES if vmem_budget is None else int(vmem_budget)
    if sub <= 0:
        raise ValueError(f"sublanes must be positive, got {sublanes}")
    if budget <= 0:
        raise ValueError(f"vmem_budget must be positive, got {vmem_budget}")
    key = (kernel, tuple(int(s) for s in shape), dt.name, mesh_key, model,
           sub, budget, bool(local))
    with _LOCK:
        plan = _CACHE.get(key)
        if plan is not None:
            _STATS["hits"] += 1
            return plan
        _STATS["misses"] += 1
        plan = _plan_uncached(kernel, key[1], dt, mesh_key, model, sub,
                              budget, local=bool(local))
        _CACHE[key] = plan
        return plan


def plan_cache_info() -> dict[str, int]:
    with _LOCK:
        return {"hits": _STATS["hits"], "misses": _STATS["misses"],
                "size": len(_CACHE)}


def plan_cache_keys() -> list[tuple]:
    """Snapshot of the memo keys ``(kernel, shape, dtype, mesh, model,
    sublanes, vmem_budget)`` -- lets tests and audits assert *which* mesh and
    sublane policy actually reached the planner at a call site."""
    with _LOCK:
        return list(_CACHE)


def clear_plan_cache() -> None:
    with _LOCK:
        _CACHE.clear()
        _STATS["hits"] = _STATS["misses"] = 0


def invalidate_mesh_plans(mesh) -> int:
    """Drop every memoized plan keyed to ``mesh``; returns the count.

    The elastic runtime calls this on a topology change: plans derived
    under the old mesh (global shard-aligned padding *and* per-shard
    ``local=True`` cells) describe a machine that no longer exists, and a
    stale cell silently re-used after a re-mesh is exactly the "fixed
    layout on an asymmetric machine" hazard the paper warns about.  Plans
    for other meshes (and the mesh-free single-device cells) survive.
    """
    if mesh is None:
        return 0
    mesh_key = _mesh_key(mesh)
    with _LOCK:
        stale = [k for k in _CACHE if k[3] == mesh_key]
        for k in stale:
            del _CACHE[k]
        return len(stale)


def stream_stride_facts(
    plan: KernelPlan,
    model: InterleavedMemoryModel | None = None,
) -> dict:
    """Static layout facts ``repro.analyze`` scores without executing anything.

    Everything here is closed-form arithmetic on the plan's padded geometry
    and its ``LayoutPlan`` under ``model``'s address->controller map:

    * ``leading_stride_bytes`` / ``stride_gcd_period`` -- the row stride and
      its gcd with the interleave period.  A stride whose gcd *is* the period
      (every power of two >= period qualifies) pins a strided walk to one
      channel: the paper's thrashing condition.
    * ``start_channels`` -- the controller each planned stream's base address
      hits at tick zero.  Skewed streams land on distinct channels; a
      degenerate layout (no skews, no segment shift) piles every stream onto
      channel 0.
    * the plan's own balance scores, so rules can report predicted impact.
    """
    model = model or _DEFAULT_MODEL
    stride = plan.leading_stride_bytes
    period = model.period_bytes
    gcd = int(np.gcd(stride, period)) if stride else period
    offsets = plan.layout.offsets_bytes
    starts = tuple(model.channel(o) for o in offsets)
    return {
        "kernel": plan.kernel,
        "n_streams": plan.signature.n_streams,
        "leading_stride_bytes": stride,
        "stride_pow2": stride >= period and (stride & (stride - 1)) == 0,
        "stride_gcd_period": gcd,
        "period_bytes": period,
        "offsets_bytes": offsets,
        "start_channels": starts,
        "distinct_start_channels": len(set(starts)),
        "segment_shift_bytes": plan.layout.segment_shift_bytes,
        "predicted_balance": plan.predicted_balance,
        "naive_balance": plan.naive_balance,
    }


def explain(kernel: str, shape, dtype, *, mesh=None,
            model: InterleavedMemoryModel | None = None,
            sublanes: int | None = None,
            vmem_budget: int | None = None) -> str:
    """Convenience: plan and render the report in one call."""
    return plan_kernel(kernel, shape, dtype, mesh=mesh, model=model,
                       sublanes=sublanes, vmem_budget=vmem_budget).explain()


# ---------------------------------------------------------------------------
# Closed-form planning rules
# ---------------------------------------------------------------------------

def _plan_uncached(kernel: str, shape: tuple[int, ...], dt: np.dtype,
                   mesh_key, model: InterleavedMemoryModel,
                   sublanes: int, budget: int, *,
                   local: bool = False) -> KernelPlan:
    sig = dataclasses.replace(FAMILIES[kernel], elem_bytes=dt.itemsize)
    n_buffers = VMEM_BUFFERS.get(kernel, sig.n_streams + 1)
    if kernel.startswith("lbm."):
        padded, block = _plan_lbm(kernel, shape, sig, sublanes, budget)
    elif len(shape) == 1:
        padded, block = _plan_1d(shape[0], sig, n_buffers, sublanes, budget)
    elif len(shape) == 2:
        # A shard-local plan pads the minor dim to the plain lane tile: the
        # tensor-parallel widening aligns *global* arrays to their shard
        # boundaries, and a per-device slice has no shard boundary in it.
        tp = 1 if local else dict(mesh_key).get("model", 1)
        padded, block = _plan_2d(shape, sig, tp, n_buffers, sublanes, budget,
                                 col_tiled=kernel in COL_TILED)
    else:
        raise ValueError(
            f"{kernel}: cannot plan rank-{len(shape)} shape {shape}"
        )
    layout = _plan_layout(sig, model)
    naive = _naive_balance(sig, model)
    plan = KernelPlan(
        kernel=kernel,
        logical_shape=shape,
        dtype=dt.name,
        padded_shape=padded,
        block_shape=block,
        signature=sig,
        layout=layout,
        naive_balance=naive,
        mesh=mesh_key,
        sublanes=sublanes,
        local=local,
    )
    # Narrow-dtype waste guarantee: a bf16/fp8 plan must never pay more
    # padding *bytes* than the fp32 plan of the same logical shape.  The
    # native wide-sublane tile usually pads fewer bytes (more pad elements
    # at half/quarter price), but its taller row tile can lose badly when
    # `_fit_block` rounds the row count up a whole block.  The fp32 plan's
    # geometry is always legal at a narrower dtype (rows stay
    # 8-sublane-tileable, blocks shrink under the same VMEM budget), and
    # costs exactly itemsize/4 of the fp32 padding bytes -- so take the
    # cheaper of the two, still in closed form.  Explicit sublane overrides
    # (context sublane_policy) are honored untouched.
    if dt.itemsize < 4 and sublanes == sublanes_for_dtype(dt):
        f32 = plan_kernel(kernel, shape, np.float32, mesh=mesh_key,
                          model=model, vmem_budget=budget, local=local)
        if plan.waste_bytes * 4 > f32.waste_bytes * dt.itemsize:
            plan = dataclasses.replace(
                plan, padded_shape=f32.padded_shape,
                block_shape=f32.block_shape, sublanes=f32.sublanes,
            )
    return plan


def _plan_layout(sig: StreamSignature, model: InterleavedMemoryModel) -> LayoutPlan:
    """The analytic skew plan, scored as deployed: n_channels concurrent
    segments whose chunk stride is congruent to one channel step, so skewed
    streams + shifted segments jointly cover every channel each tick."""
    step = 1 << model.channel_shift
    return plan_streams(
        sig, model,
        n_threads=model.n_channels,
        chunk_bytes=model.period_bytes + step,
    )


def _naive_balance(sig: StreamSignature, model: InterleavedMemoryModel) -> float:
    """Score of the *unplanned* layout: page-aligned streams, period-aliased
    segments -- every request lands on one controller (paper Fig. 2, offset
    zero)."""
    streams = [
        Stream(base=0, kind="write" if k < sig.n_write else "read")
        for k in range(sig.n_streams)
    ]
    return model.balance(streams, n_threads=model.n_channels,
                         chunk_bytes=model.period_bytes)


def _fit_block(rows: int, width: int, sig: StreamSignature, n_buffers: int,
               sublanes: int, budget: int,
               *, col_tiled: bool = False) -> tuple[int, int, int]:
    """VMEM block for (rows, width): ``n_buffers`` resident blocks, whole
    lines per DMA, sublane-multiple rows.  Full-width kernels charge the row
    budget against the whole width (their blocks are (brows, width));
    col-tiled kernels (online-softmax style) also tile the minor dim.

    A divisor of the row count within half the budgeted block is preferred
    (zero extra padding at a small block-size cost); failing that, rows are
    padded *up* to a block multiple (returned as the first element) rather
    than the block shrunk further: an awkward row count (e.g. a large prime
    x 8) costs at most one extra block of padding instead of collapsing
    every DMA to one sublane tile."""
    brows, bcols = choose_block_shape(
        rows, width,
        bytes_per_el=sig.elem_bytes,
        n_buffers=n_buffers,
        vmem_budget=budget,
        max_block_cols=MAX_WIDTH if col_tiled else width,
        sublane_tile=sublanes,
    )
    bcols = min(bcols, width)
    while width % bcols:
        bcols -= LANES
    bcols = max(bcols, LANES)
    brows = max(min(brows, rows), sublanes)
    for cand in range(brows, max(brows // 2, sublanes) - 1, -sublanes):
        if rows % cand == 0:
            return rows, cand, bcols
    return round_up(rows, brows), brows, bcols


def _plan_1d(n: int, sig: StreamSignature, n_buffers: int, sublanes: int,
             budget: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """1-D stream of n elements -> (rows, width) whole-tile 2-D layout.

    The width is the smallest lane multiple that keeps the sublane-padded
    row count minimal (waste shrinks toward one tile), capped at MAX_WIDTH
    so blocks stay within the VMEM budget for any stream count.
    """
    n = max(int(n), 1)
    width = round_up(min(max(cdiv(n, sublanes), LANES), MAX_WIDTH), LANES)
    rows = round_up(cdiv(n, width), sublanes)
    rows, brows, bcols = _fit_block(rows, width, sig, n_buffers, sublanes,
                                    budget)
    return (rows, width), (brows, bcols)


def _plan_2d(shape: tuple[int, ...], sig: StreamSignature, tp: int,
             n_buffers: int, sublanes: int, budget: int, *,
             col_tiled: bool) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(rows, cols) kernel: sublane-pad rows, lane-pad cols (x tp when the
    minor dim is sharded over a model axis)."""
    r, c = shape
    rows = round_up(max(int(r), 1), sublanes)
    width = round_up(max(int(c), 1), LANES * max(int(tp), 1))
    rows, brows, bcols = _fit_block(rows, width, sig, n_buffers, sublanes,
                                    budget, col_tiled=col_tiled)
    return (rows, width), (brows, bcols)


def _plan_lbm(kernel: str, shape: tuple[int, ...], sig: StreamSignature,
              sublanes: int,
              budget: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """D3Q19 collision layouts.  ``shape`` is the lattice (Q, X, Y, Z).

    soa : f stored (Q, S)        -- block (Q, bs), bs sized so the in and
                                    out blocks of all Q direction rows fit
                                    VMEM, PIPELINE_DEPTH copies each.  A
                                    block narrower than the lattice holds
                                    whole (sublanes, 128) tiles of sites,
                                    because the kernel views each direction
                                    row as (bs/128, 128).
    ivjk: f stored (S/128, Q, L) -- directions interleaved at lane
                                    granularity; block is bsb super-rows.
    """
    q = int(shape[0])
    if q != _LBM_Q:
        raise ValueError(f"{kernel}: leading dim must be Q={_LBM_Q}, got {q}")
    s = 1
    for d in shape[1:]:
        s *= int(d)
    s = max(s, 1)
    elem = sig.elem_bytes
    buffers = 2 * PIPELINE_DEPTH          # in + out, each pipelined
    if kernel == "lbm.soa":
        tile = LANES * sublanes
        cap = max(round_down(
            min(budget // max(q * elem * buffers, 1), MAX_WIDTH), tile
        ), tile)
        bs = round_up(s, LANES)
        if bs > cap:
            bs = cap
        spad = round_up(s, bs)
        return (q, spad), (q, bs)
    # ivjk: super-block rows of (Q, 128) slabs
    cap = round_down(
        min(budget // max(q * LANES * elem * buffers, 1), 64), sublanes
    )
    bsb = max(min(cap, round_up(cdiv(s, LANES), sublanes)), sublanes)
    spad = round_up(s, bsb * LANES)
    return (spad // LANES, q, LANES), (bsb, q, LANES)
