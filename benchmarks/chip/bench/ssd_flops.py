"""Operations and bytes the Mamba-2 SSD scan needs, computed from its
shapes, as ``bench/flops.py`` counts the transformer kernels': what the
mathematics requires, not what a kernel happens to do.
"""
from __future__ import annotations

from typing import Tuple


def ssd(B: int, L: int, H: int, P: int, G: int, N: int, chunk: int,
        itemsize: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the chunked SSD scan of x (B,H,L,P) with B/C
    (B,G,L,N) shared by the H/G heads of a group.

    Per chunk of ``cl`` steps: C·B^T over the lower triangle once per
    group, G·cl(cl+1)·N; per head the masked matrix applied to x over the
    lower triangle, cl(cl+1)·P, the incoming state read by C and the
    chunk's update of the state, 2·cl·N·P each.  x, B, C read and y written
    once in ``itemsize`` bytes (B and C once, though a kernel may read them
    once per head block); dt (B,H,L), A and D (H,) read and the final state
    (B,H,P,N) written once in float32.
    """
    cl = min(chunk, L)
    per_chunk = G * cl * (cl + 1) * N + H * (cl * (cl + 1) * P
                                             + 4 * cl * N * P)
    flops = float(B * (L // cl) * per_chunk)
    nbytes = (float(itemsize) * (2 * B * H * L * P + 2 * B * G * L * N)
              + 4.0 * (B * H * L + 2 * H + B * H * P * N))
    return flops, nbytes
