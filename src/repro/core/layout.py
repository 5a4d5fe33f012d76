"""Layout policy: tile- and mesh-aware padding, alignment math, waste accounting.

This is the TPU port of the paper's central remedy: *analytic* padding and
alignment derived from the hardware's address->resource map, not trial and
error.  On the UltraSPARC T2 the map was ``controller = phys_addr bits 8:7``
(512 B interleave period); on TPU the controllable analogues are

  * the (8, 128) sublane x lane VREG tile: trailing-two-dim shapes that are
    not multiples of (8, 128) are implicitly padded by XLA -- implicit pad is
    wasted bandwidth *and* wasted MXU occupancy,
  * the mesh: a dimension sharded N-ways that is not divisible by N forces
    GSPMD to materialize ragged shards (internally padded, with extra
    collective traffic),
  * VMEM blocks: Pallas BlockSpec shapes must tile the (padded) array.

``LayoutPolicy`` turns a *logical* model dimension into a *padded physical*
dimension and accounts for the waste so the roofline analysis can report the
"useful compute" ratio.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping

# TPU v5e hardware tiling constants (the "address map" of this machine).
LANES = 128          # minor-most dim of a VREG tile / MXU systolic edge
SUBLANES = 8         # second-minor dim of a VREG tile (fp32); bf16 packs 16
MXU_EDGE = 128       # MXU matmul tile edge
# The scoped VMEM limit every pallas_call compiles under (v5e has 128 MiB of
# VMEM per core; Mosaic's own default limit is 16 MiB).  Half of it is the
# block chooser's budget for pipelined buffers -- Pallas keeps
# PIPELINE_DEPTH copies of every blocked operand -- and the other half holds
# the temporaries a kernel body materialises (fp32 upcasts, rolled views).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
VMEM_BYTES = VMEM_LIMIT_BYTES // 2
PIPELINE_DEPTH = 2


def round_up(n: int, multiple: int) -> int:
    """Smallest m >= n with m % multiple == 0 (multiple >= 1)."""
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    if n < 0:
        raise ValueError(f"n must be non-negative, got {n}")
    return ((n + multiple - 1) // multiple) * multiple


def round_down(n: int, multiple: int) -> int:
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    return (n // multiple) * multiple


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class PaddedDim:
    """A logical dimension and the physical size the policy chose for it."""

    logical: int
    physical: int
    reason: str = ""

    @property
    def pad(self) -> int:
        return self.physical - self.logical

    @property
    def waste(self) -> float:
        """Fraction of the physical extent that is padding."""
        return self.pad / self.physical if self.physical else 0.0


@dataclasses.dataclass(frozen=True)
class LayoutPolicy:
    """Analytic padding policy for model dimensions.

    Parameters
    ----------
    lane_tile:
        minor-most hardware tile (128 on all current TPUs).
    sublane_tile:
        second-minor tile (8 for fp32; callers may pass 16 for bf16-major
        layouts).
    tp:
        tensor-parallel degree of the target mesh ("model" axis size).  A
        dimension sharded over the model axis must be divisible by ``tp`` and
        each *shard* must be lane-aligned, i.e. divisible by ``tp * lane_tile``
        when it is a minor dim.
    pad_to_mesh:
        if False, produce the *paper-naive* layout (logical sizes untouched)
        so the baseline/optimized comparison in EXPERIMENTS.md SSPerf has a
        faithful "plain malloc()" analogue.
    """

    lane_tile: int = LANES
    sublane_tile: int = SUBLANES
    tp: int = 1
    pad_to_mesh: bool = True

    # ---- dimension rules -------------------------------------------------
    def pad_minor(self, n: int, *, sharded: bool = False) -> PaddedDim:
        """Pad a minor (lane) dimension: multiples of 128, and of tp*128 when
        sharded over the model axis so every shard stays lane-aligned."""
        if not self.pad_to_mesh:
            return PaddedDim(n, n, "plain")
        m = self.lane_tile * (self.tp if sharded else 1)
        return PaddedDim(n, round_up(n, m), f"lane{'xTP' if sharded else ''}={m}")

    def pad_sublane(self, n: int, *, sharded: bool = False) -> PaddedDim:
        """Pad a second-minor (sublane) dimension."""
        if not self.pad_to_mesh:
            return PaddedDim(n, n, "plain")
        m = self.sublane_tile * (self.tp if sharded else 1)
        return PaddedDim(n, round_up(n, m), f"sublane{'xTP' if sharded else ''}={m}")

    def pad_count(self, n: int, *, sharded: bool = False) -> PaddedDim:
        """Pad a 'count' dimension (heads, experts): only mesh divisibility
        matters, there is no lane constraint (each unit is itself tiled)."""
        if not self.pad_to_mesh or not sharded or self.tp <= 1:
            return PaddedDim(n, n, "plain")
        return PaddedDim(n, round_up(n, self.tp), f"count%TP={self.tp}")

    def pad_vocab(self, n: int) -> PaddedDim:
        """Vocab is sharded minor-most over TP for the output projection."""
        return self.pad_minor(n, sharded=True)

    # ---- model-level convenience ----------------------------------------
    def plan(self, dims: Mapping[str, tuple[int, str]]) -> dict[str, PaddedDim]:
        """Plan a set of named dims.  ``dims[name] = (logical, kind)`` where
        kind in {minor, minor_sharded, sublane, count, count_sharded, vocab}.
        """
        out: dict[str, PaddedDim] = {}
        for name, (n, kind) in dims.items():
            if kind == "minor":
                out[name] = self.pad_minor(n)
            elif kind == "minor_sharded":
                out[name] = self.pad_minor(n, sharded=True)
            elif kind == "sublane":
                out[name] = self.pad_sublane(n)
            elif kind == "count":
                out[name] = self.pad_count(n)
            elif kind == "count_sharded":
                out[name] = self.pad_count(n, sharded=True)
            elif kind == "vocab":
                out[name] = self.pad_vocab(n)
            else:
                raise ValueError(f"unknown dim kind {kind!r} for {name!r}")
        return out

    @staticmethod
    def total_waste(plan: Mapping[str, PaddedDim]) -> float:
        """Aggregate padding fraction over a plan (unweighted mean)."""
        if not plan:
            return 0.0
        return sum(d.waste for d in plan.values()) / len(plan)


# ---- Pallas block-shape chooser ------------------------------------------

def choose_block_shape(
    rows: int,
    cols: int,
    *,
    bytes_per_el: int = 4,
    n_buffers: int = 3,
    vmem_budget: int = VMEM_BYTES,
    max_block_rows: int = 1024,
    max_block_cols: int = 4096,
    sublane_tile: int = SUBLANES,
) -> tuple[int, int]:
    """Pick an (rows, cols) VMEM block for a streaming 2-D kernel.

    The paper's rule "align each segment to the controller period" becomes:
    the block minor dim is a multiple of 128 lanes (full lines per DMA), the
    block major dim a multiple of ``sublane_tile`` sublanes (8 for fp32, 16
    for 2-byte dtypes, 32 for fp8), and ``n_buffers`` blocked operands, each
    held ``PIPELINE_DEPTH`` times by the Pallas pipeline, must fit the VMEM
    budget.  Kernels that stream full-width row blocks pass
    ``max_block_cols=cols`` so the row budget is charged against the columns
    they actually keep resident.
    """
    bcols = round_up(min(cols, max_block_cols), LANES)
    # rows: as many sublane-multiples as fit the budget
    per_row = bcols * bytes_per_el * n_buffers * PIPELINE_DEPTH
    brows = max(sublane_tile, round_down(
        min(vmem_budget // max(per_row, 1), max_block_rows, rows),
        sublane_tile,
    ))
    brows = max(brows, min(rows, sublane_tile))
    return int(brows), int(bcols)
