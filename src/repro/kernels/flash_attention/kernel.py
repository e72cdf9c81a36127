"""Pallas TPU flash-attention kernels (fwd + dkdv/dq bwd).

Layout: (B, H, S, D) with D = head_dim on the 128-lane minor dim and S tiled
in MXU-friendly multiples of 8/128.  Grid iteration on TPU is row-major
(minor-most fastest), so for grid (b, h, i, j) the VMEM scratch carries the
online-softmax state across the j (KV-block) sweep of a fixed q block — the
exact schedule of the lax work-list twin in repro/models/attention.py.

GQA is handled in the index maps (k/v block index h // G); no KV repeat is
ever materialised.  Causal/window tiles that are fully masked are skipped
via predication (pl.when), the kernel-side equivalent of the work-list
``skip_masked_tiles`` flag.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import contract_precision

NEG = -1.0e30


def _tile_mask(i, j, bq, bk, causal: bool, window: int):
    """(bq, bk) bool mask for q block i, kv block j (positions are arange)."""
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        m = m & (kpos <= qpos)
    if window:
        m = m & (kpos > qpos - window)
    return m


def _tile_live(i, j, bq, bk, causal: bool, window: int):
    """Scalar predicate: does tile (i, j) contain any unmasked element?"""
    live = jnp.bool_(True)
    if causal:
        live = live & (j * bk <= i * bq + bq - 1)
    if window:
        live = live & ((j + 1) * bk - 1 > i * bq - window)
    return live


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                causal: bool, window: int, bq: int, bk: int, nk: int,
                scale: float):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(_tile_live(i, j, bq, bk, causal, window))
    def _compute():
        prec = contract_precision(q_ref.dtype)
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale
        mask = _tile_mask(i, j, bq, bk, causal, window)
        s = jnp.where(mask, s, NEG)
        m_prev = m_s[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec)
        m_s[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(l)


def flash_fwd(q, k, v, *, causal: bool, window: int = 0, bq: int = 512,
              bk: int = 512, interpret: bool = True):
    """q (B,H,Sq,D); k/v (B,KH,Skv,D) -> (out (B,H,Sq,D), lse (B,H,Sq,1)).

    ``lse`` keeps a trailing unit dim so its (1, 1, bq, 1) block meets the
    TPU (8, 128) tiling rule (bq rows, a full minor dim); a (1, 1, bq)
    block over (B, H, Sq) does not once H > 1.
    """
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, D = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    grid = (B, H, nq, nk)
    scale = 1.0 / np.sqrt(D)

    kernel = functools.partial(_fwd_kernel, causal=causal, window=window,
                               bq=bq, bk=bk, nk=nk, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward: dk/dv kernel (grid minor dim sweeps q blocks)
# ---------------------------------------------------------------------------


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, *, causal: bool, window: int, bq: int,
                 bk: int, nq: int, G: int, scale: float):
    h = pl.program_id(1)
    j = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when((h % G == 0) & (i == 0))
    def _init():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
        dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

    @pl.when(_tile_live(i, j, bq, bk, causal, window))
    def _compute():
        prec = contract_precision(q_ref.dtype)
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                               # (bq, 1)
        delta = delta_ref[0, 0]                           # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale
        mask = _tile_mask(i, j, bq, bk, causal, window)
        p = jnp.exp(jnp.where(mask, s, NEG) - lse)
        p = jnp.where(mask, p, 0.0)                       # (bq, bk)
        dv_ref[0, 0] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec)
        ds = p * (dp - delta) * scale
        dk_ref[0, 0] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec)


def flash_dkdv(q, k, v, dout, lse, delta, *, causal: bool, window: int = 0,
               bq: int = 512, bk: int = 512, interpret: bool = True):
    B, H, Sq, D = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    nq, nk = Sq // bq, Skv // bk
    grid = (B, H, nk, nq)
    kernel = functools.partial(_dkdv_kernel, causal=causal, window=window,
                               bq=bq, bk=bk, nq=nq, G=G,
                               scale=1.0 / np.sqrt(D))
    dk, dv = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h // G, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KH, Skv, D), jnp.float32),
            jax.ShapeDtypeStruct((B, KH, Skv, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dk, dv


# ---------------------------------------------------------------------------
# Backward: dq kernel (grid minor dim sweeps kv blocks)
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               causal: bool, window: int, bq: int, bk: int, nk: int,
               scale: float):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

    @pl.when(_tile_live(i, j, bq, bk, causal, window))
    def _compute():
        prec = contract_precision(q_ref.dtype)
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale
        mask = _tile_mask(i, j, bq, bk, causal, window)
        p = jnp.exp(jnp.where(mask, s, NEG) - lse)
        p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec)
        ds = p * (dp - delta) * scale
        dq_ref[0, 0] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec)


def flash_dq(q, k, v, dout, lse, delta, *, causal: bool, window: int = 0,
             bq: int = 512, bk: int = 512, interpret: bool = True):
    B, H, Sq, D = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    nq, nk = Sq // bq, Skv // bk
    grid = (B, H, nq, nk)
    kernel = functools.partial(_dq_kernel, causal=causal, window=window,
                               bq=bq, bk=bk, nk=nk, scale=1.0 / np.sqrt(D))
    dq = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), jnp.float32),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dq
