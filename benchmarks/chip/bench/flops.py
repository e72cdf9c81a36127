"""Operations and bytes the algorithms need, computed from their shapes.

These are the numerators of every roofline share and utilization the
benchmark reports.  They count what the mathematics requires, not what a
kernel happens to do: recomputation, padding and copies are not counted,
so a share can only fall when work is wasted.
"""
from __future__ import annotations

from typing import Dict, Tuple


def matmul(M: int, N: int, K: int, itemsize: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of C[M,N] = A[M,K] @ B[K,N]: each operand read once
    and C written once."""
    return 2.0 * M * N * K, float(itemsize) * (M * K + K * N + M * N)


def flash_causal(B: int, H: int, KH: int, S: int, D: int, itemsize: int
                 ) -> Tuple[float, float]:
    """(FLOPs, bytes) of causal self-attention forward: q·k and p·v over
    the lower triangle, 2·B·H·S²·D in all; q, k, v read and o written
    once (k and v have KH heads)."""
    flops = 2.0 * B * H * S * S * D
    nbytes = float(itemsize) * (2 * B * H * S * D + 2 * B * KH * S * D)
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the measured time, in percent, and which bound."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound

