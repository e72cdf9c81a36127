"""Jit wrapper for the SSD scan kernel with backend dispatch, plus the
static per-tile DMA burst list implied by its BlockSpec grid (the §IV
"schedule is the burst list" contract; consumed by the FireBridge memory
bridge and the online congestion link, Fig. 8)."""
from __future__ import annotations

from typing import List, Tuple

import jax

from repro.kernels.mamba2_scan.kernel import ssd_scan as _ssd_scan

F32 = 4                                   # dt and the state are float32


def ssd_scan(x, dt, B_, C_, A, D, *, chunk=128, hb=8):
    return _ssd_scan(x, dt, B_, C_, A, D, chunk=chunk, hb=hb,
                     interpret=jax.default_backend() != "tpu")


def transactions(B: int, L: int, H: int, P: int, N: int, *, G: int = 1,
                 chunk: int = 128, hb: int = 8,
                 dtype_bytes: int = 4) -> List[Tuple[str, str, int, int]]:
    """Per-tile HBM bursts of the SSD scan grid (B, H/hb, L/chunk), in the
    kernel's head-major layout: x/y (B,H,L,P) and B/C (B,G,L,N) in
    ``dtype_bytes``, dt (B,H,L) and the state (B,H,P,N) in float32.

    Per grid cell: the x and dt tiles (one strip per head of the block),
    the chunk of B and of C of the block's group, and the y tile.  A head
    block lies in one group, so it reads only that group's B/C, and B/C
    are read once per head block.  Per (batch, head block) one final-state
    writeback: the VMEM-resident state never round-trips, the kernel's
    locality win, visible as the absence of dma_state traffic inside the
    chunk sweep.
    """
    chunk = min(chunk, L)
    hb = min(hb, H // G)
    per_group = (H // G) // hb
    x_base = 0
    dt_base = x_base + B * H * L * P * dtype_bytes
    b_base = dt_base + B * H * L * F32
    c_base = b_base + B * G * L * N * dtype_bytes
    y_base = c_base + B * G * L * N * dtype_bytes
    s_base = y_base + B * H * L * P * dtype_bytes
    x_strip = chunk * P * dtype_bytes
    dt_strip = chunk * F32
    bc_tile = chunk * N * dtype_bytes
    state = hb * P * N * F32
    txs: List[Tuple[str, str, int, int]] = []
    for b in range(B):
        for blk in range(H // hb):
            heads = range(blk * hb, (blk + 1) * hb)
            g = blk // per_group
            for c in range(L // chunk):
                for h in heads:
                    txs.append(("dma_x", "read", x_base + ((b * H + h) * L
                                + c * chunk) * P * dtype_bytes, x_strip))
                for h in heads:
                    txs.append(("dma_dt", "read", dt_base + ((b * H + h) * L
                                + c * chunk) * F32, dt_strip))
                bc_off = ((b * G + g) * L + c * chunk) * N * dtype_bytes
                txs.append(("dma_bc", "read", b_base + bc_off, bc_tile))
                txs.append(("dma_bc", "read", c_base + bc_off, bc_tile))
                for h in heads:
                    txs.append(("dma_y", "write", y_base + ((b * H + h) * L
                                + c * chunk) * P * dtype_bytes, x_strip))
            txs.append(("dma_state", "write",
                        s_base + (b * H + blk * hb) * P * N * F32, state))
    return txs
