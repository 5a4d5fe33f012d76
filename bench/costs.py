"""Operations and bytes of each measured kernel, from shapes.

These are the least the algorithm needs, not what the program happens to
move: a roofline share built on them is the fraction of the chip's best
that a call reaches.  A kernel is bounded by the larger of its bytes over
the HBM bandwidth and its operations over the peak rate.  A model's
operations per step are its configuration's to count (its reference,
``bench/configs/<config>.py``), since they depend on the architecture.
"""
from __future__ import annotations

LBM_Q = 19


def lbm_site_bytes(elem_bytes: int = 4) -> int:
    """One D3Q19 update reads 19 and writes 19 populations: 152 B in
    fp32.  The collision kernel and a whole sweep have the same minimum."""
    return 2 * LBM_Q * elem_bytes


def min_seconds(peak: dict, *, bytes_: float = 0.0, flops: float = 0.0
                ) -> float:
    """The roofline time of ``bytes_`` and ``flops`` on one chip."""
    return max(bytes_ / peak["hbm_bytes_per_s"],
               flops / peak["bf16_flops_per_s"])


def xent_bytes(rows: int, vocab: int, logit_bytes: int = 4) -> int:
    """Fused cross-entropy over (rows, vocab) logits: read every logit and
    the label, write one loss per row."""
    return rows * vocab * logit_bytes + 2 * rows * 4


def xent_flops(rows: int, vocab: int) -> int:
    """max, subtract, exp and sum per logit."""
    return 4 * rows * vocab

