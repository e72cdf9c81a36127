"""Pallas TPU kernel for the Mamba-2 SSD chunked scan, grouped B/C.

Layout is head-major, as flash attention's (B, H, S, D): x and y
(B, H, L, P), dt (B, H, L) f32, B and C (B, G, L, N) with G groups each
shared by H/G heads, A and D (H,) f32.  Grid (B, H/hb, L/cl) with the
chunk index minor-most: the (hb, P, N) f32 state lives in VMEM scratch and
is carried across chunks, so x/dt are read and y written once, and the
state is written once per head block; the lax twin
(``ref.ssd_chunked_ref``) carries it from chunk to chunk as a loop value.
The head block ``hb`` divides H/G, so one block reads one group's B/C
chunk; B/C are read once per head block.

Every construct lowers with Mosaic: blocks keep their last two dims
(8, 128)-aligned or whole; the in-chunk prefix sum of dt·A is a matmul with
a triangular ones matrix at HIGHEST precision (exact to f32 rounding: a
one-pass bf16 prefix sum moves exp(cum_i - cum_j) far off); the chunk's
total is a row sum; the causal segment matrices are built head by head as
2-D (cl, cl) arrays.  All exponent arguments are <= 0 (SSD property), so
the kernel is overflow-safe in f32 without rescaling tricks.  The
exponentials are computed to f32 rounding (``_exp``): a v5e's native exp
is within about 100 units in the last place (6e-6), and the state carries
a product of per-chunk decays through the whole context.

Scope, as deployed (``mamba_chunk_scan_combined``): dt arrives with its
softplus and bias applied; z gating, the causal conv and the gated RMSNorm
are separate ops.  y is written in x's dtype, the final state in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import contract_precision

HI = jax.lax.Precision.HIGHEST
NN = (((1,), (0,)), ((), ()))          # a @ b
NT = (((1,), (1,)), ((), ()))          # a @ b.T
TN = (((0,), (0,)), ((), ()))          # a.T @ b


def _exp(x):
    """exp(x) for x <= 0 to within a few f32 units in the last place:
    x = n ln2 + r with |r| <= ln2/2 (ln2 split in two, so n ln2 is
    exact), a degree-7 Taylor polynomial for exp(r) (truncation 5e-9),
    and 2**n built in the exponent bits.  Arguments below -87 read
    exp(-87) ~ 1.6e-38, where the result would leave the normal range."""
    x = jnp.maximum(x, -87.0)
    n = jnp.floor(x * 1.4426950408889634 + 0.5)
    r = x - n * 0.693145751953125 - n * 1.4286068202862268e-06
    p = jnp.full_like(r, 1.0 / 5040.0)
    for c in (1.0 / 720.0, 1.0 / 120.0, 1.0 / 24.0, 1.0 / 6.0, 0.5, 1.0,
              1.0):
        p = p * r + c
    two_n = jax.lax.bitcast_convert_type(
        (n.astype(jnp.int32) + 127) << 23, jnp.float32)
    return p * two_n


def _dot(a, b, dims, precision=HI):
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dt_ref, B_ref, C_ref, A_ref, D_ref, y_ref, st_ref,
                state_s, *, nc: int, cl: int, hb: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_s[...] = jnp.zeros_like(state_s)

    dt = dt_ref[0]                            # (hb, cl) f32
    A = A_ref[...]                            # (hb, 1) f32
    B_ = B_ref[0, 0]                          # (cl, N), the group's
    C_ = C_ref[0, 0]                          # (cl, N)
    N = B_.shape[1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    causal = jj <= ii                         # [i, j]: j <= i

    def ones(mask):
        return jnp.where(mask, 1.0, 0.0).astype(jnp.float32)

    # prefix and suffix sums and totals as matmuls, each in the orientation
    # its use broadcasts along (Mosaic broadcasts along sublanes or lanes,
    # not both); the state's weights take the suffix sums directly, not
    # the total less a prefix sum, which cancels where |cum| is large
    dA = dt * A                               # (hb, cl) <= 0
    cum = _dot(dA, ones(ii <= jj), NN)        # (hb, cl): cum_j
    cum_t = _dot(ones(causal), dA, NT)        # (cl, hb): cum_i
    suf_t = _dot(ones(ii < jj), dA, NT)       # (cl, hb): sum over k > j
    dt_t = _dot(ones(ii == jj), dt, NT)       # (cl, hb): dt transposed
    tot_n = _dot(dA, jnp.ones((cl, N), jnp.float32), NN)    # (hb, N)
    CB = _dot(C_, B_, NT, precision=contract_precision(C_.dtype))
    Cf = C_.astype(jnp.float32)
    Bf = B_.astype(jnp.float32)
    h0 = pl.program_id(1) * hb

    for h in range(hb):
        x = x_ref[0, h].astype(jnp.float32)   # (cl, P)
        row = cum[h:h + 1, :]                 # (1, cl): cum_j
        col = cum_t[:, h:h + 1]               # (cl, 1): cum_i
        seg = jnp.where(causal, _exp(jnp.where(causal, col - row, 0.0)),
                        0.0)
        M = CB * seg * dt[h:h + 1, :]         # weight by dt_j
        state = state_s[h]                    # (P, N)
        y = _dot(M, x, NN)
        y = y + _dot(Cf, state, NT) * _exp(col)
        y = y + D_ref[h0 + h] * x
        y_ref[0, h] = y.astype(y_ref.dtype)
        w = dt_t[:, h:h + 1] * _exp(suf_t[:, h:h + 1])        # (cl, 1)
        state_s[h] = (state * _exp(tot_n[h:h + 1, :])
                      + _dot(x * w, Bf, TN))

    @pl.when(c == nc - 1)
    def _done():
        st_ref[0] = state_s[...]


def ssd_scan(x, dt, B_, C_, A, D, *, chunk: int = 128, hb: int = 8,
             interpret: bool = True):
    """x (B,H,L,P); dt (B,H,L) f32; B_/C_ (B,G,L,N); A/D (H,) f32.
    Returns (y (B,H,L,P) in x's dtype, final_state (B,H,P,N) f32)."""
    B, H, L, P = x.shape
    G, N = B_.shape[1], B_.shape[3]
    cl = min(chunk, L)
    hb = min(hb, H // G)
    assert H % G == 0 and L % cl == 0 and (H // G) % hb == 0, \
        (H, G, L, cl, hb)
    per_group = (H // G) // hb                # head blocks per group
    grid = (B, H // hb, L // cl)
    y, st = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=grid[2], cl=cl, hb=hb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, cl, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hb, cl), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, cl, N),
                         lambda b, h, c: (b, h // per_group, c, 0)),
            pl.BlockSpec((1, 1, cl, N),
                         lambda b, h, c: (b, h // per_group, c, 0)),
            pl.BlockSpec((hb, 1), lambda b, h, c: (h, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, cl, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hb, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, dt.astype(jnp.float32), B_, C_,
      A.astype(jnp.float32).reshape(H, 1), D.astype(jnp.float32))
    return y, st
