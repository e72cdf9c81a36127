"""Jit-facing wrapper: custom-VJP flash attention backed by the Pallas
kernels, with model-layout (B, S, H, D) in/out and backend dispatch
(interpret=True off-TPU, compiled kernel on TPU).

Also derives the kernel's static per-tile DMA burst list from its
BlockSpec grid (``transactions``) — the FireBridge §IV data-movement
contract: the schedule IS the burst list, fed to core/transactions.py for
Fig. 8/9 profiling and to the online congestion link (§IV-C).
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as K


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, bq, bk):
    out, _ = K.flash_fwd(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                         interpret=_interpret_default())
    return out


def _fwd(q, k, v, causal, window, bq, bk):
    out, lse = K.flash_fwd(q, k, v, causal=causal, window=window, bq=bq,
                           bk=bk, interpret=_interpret_default())
    return out, (q, k, v, out, lse)


def _bwd(causal, window, bq, bk, res, dout):
    q, k, v, out, lse = res
    interp = _interpret_default()
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                     # (B,H,Sq,1)
    dk, dv = K.flash_dkdv(q, k, v, dout, lse, delta, causal=causal,
                          window=window, bq=bq, bk=bk, interpret=interp)
    dq = K.flash_dq(q, k, v, dout, lse, delta, causal=causal, window=window,
                    bq=bq, bk=bk, interpret=interp)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, *, q_pos=None, kv_pos=None, causal=True,
                    window=0, bq=512, bk=512):
    """Model-layout entry point: q (B,S,H,D), k/v (B,S,KH,D).

    Positions are assumed to be arange (self-attention); q_pos/kv_pos are
    accepted for interface parity with repro.models.attention and ignored.
    """
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, causal, window, bq, bk)
    return out.transpose(0, 2, 1, 3)


def transactions(B: int, H: int, Sq: int, Sk: int, D: int, *,
                 bq: int = 512, bk: int = 512, causal: bool = True,
                 dtype_bytes: int = 2) -> List[Tuple[str, str, int, int]]:
    """Static per-tile HBM<->VMEM burst list implied by the fwd BlockSpecs.

    Returns [(engine, direction, address, nbytes)] in grid order — per q
    block one q-tile fetch, a k/v-tile fetch per live KV block (causally
    masked tiles are skipped, matching the kernel's pl.when predication),
    and one output-tile write.  This is the §IV "schedule is the burst
    list" contract used by MemoryBridge.log_burst_list and the congestion
    link (Fig. 8).
    """
    bq, bk = min(bq, Sq), min(bk, Sk)
    q_base = 0
    k_base = q_base + B * H * Sq * D * dtype_bytes
    v_base = k_base + B * H * Sk * D * dtype_bytes
    o_base = v_base + B * H * Sk * D * dtype_bytes
    q_tile = bq * D * dtype_bytes
    kv_tile = bk * D * dtype_bytes
    txs: List[Tuple[str, str, int, int]] = []
    for b in range(B):
        for h in range(H):
            bh_q = (b * H + h) * Sq * D * dtype_bytes
            bh_k = (b * H + h) * Sk * D * dtype_bytes
            for i in range(Sq // bq):
                txs.append(("dma_q", "read",
                            q_base + bh_q + i * q_tile, q_tile))
                for j in range(Sk // bk):
                    if causal and j * bk > (i + 1) * bq - 1:
                        continue                   # fully-masked tile skipped
                    txs.append(("dma_k", "read",
                                k_base + bh_k + j * kv_tile, kv_tile))
                    txs.append(("dma_v", "read",
                                v_base + bh_k + j * kv_tile, kv_tile))
                txs.append(("dma_o", "write",
                            o_base + bh_q + i * q_tile, q_tile))
    return txs
