"""Blocked matmul kernel — the TPU adaptation of the paper's representative
systolic-array accelerator (paper §V-B, Fig. 4).

The paper's SoC streams A/B tiles through AXI DMAs into a weight-stationary
systolic array.  On TPU the MXU *is* the systolic array; the analogue of the
DMA burst schedule is the BlockSpec index map, and the analogue of the AXI
transaction stream is the (statically derivable) sequence of HBM->VMEM tile
fetches.  ops.py exposes that transaction stream to the FireBridge memory
bridge so the same firmware-profiling flow as the paper's Fig. 8/9 runs
against this kernel.

Grid (nm, nn, nk), k minor-most: the f32 VMEM accumulator persists across
the k sweep; C is written once per (m, n) tile — max data reuse, one C
writeback, exactly like an output-stationary systolic schedule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import contract_precision


def _mm_kernel(a_ref, b_ref, o_ref, acc_s, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    acc_s[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=contract_precision(a_ref.dtype))

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_s[...].astype(o_ref.dtype)


def matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
           interpret: bool = True, out_dtype=None):
    """a (M,K) @ b (K,N) -> (M,N) with explicit VMEM tiling."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape)
    grid = (M // bm, N // bn, K // bk)
    out_dtype = out_dtype or a.dtype
    return pl.pallas_call(
        functools.partial(_mm_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="systolic_matmul",
    )(a, b)
