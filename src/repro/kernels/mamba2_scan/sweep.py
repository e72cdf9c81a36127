"""Backend tables of the SSD scan for co-verification (kernel layout
x (B,H,L,P), dt (B,H,L), B/C (B,G,L,N), A/D (H,)), mirroring
kernels/flash_attention/sweep.py.  Every tier takes the six inputs and
returns (y in x's dtype, final state in float32) as host arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mamba2_scan import kernel as K
from repro.kernels.mamba2_scan import ref as R


def _host(fn):
    """``fn`` on device copies of host inputs, its outputs back on the
    host."""
    def call(*args):
        y, st = fn(*(jnp.asarray(a) for a in args))
        return np.asarray(y), np.asarray(st)
    return call


def _chunked(chunk: int):
    def ssd_oracle(x, dt, B_, C_, A, D):
        y, st = R.ssd_chunked_ref(x, dt, B_, C_, A, D, chunk=chunk)
        return y.astype(x.dtype), st
    return ssd_oracle


def ssd_backends(chunk: int = 128, hb: int = 8) -> dict:
    """oracle/interpret/compiled backend table for register_op.

    oracle = the chunked float32 SSD (``ref.ssd_chunked_ref``), interpret
    = the Pallas kernel in interpret mode ("RTL sim"), compiled = the
    jitted chunked SSD (XLA deployment tier).
    """
    ref = _host(jax.jit(_chunked(chunk)))

    def interp(x, dt, B_, C_, A, D):
        return K.ssd_scan(x, dt, B_, C_, A, D, chunk=chunk, hb=hb,
                          interpret=True)
    return dict(oracle=ref, interpret=_host(jax.jit(interp)), compiled=ref)


def ssd_chip_backends(chunk: int, hb: int) -> dict:
    """``ssd_backends(chunk, hb)`` for a TPU.

    compiled = the Pallas kernel compiled for the chip
    (``interpret=False``), jitted under its own name, so the device trace
    and the lowered program name it ``ssd_scan``; the ``kernel`` attribute
    is that jitted function, so a caller can read the lowered program.
    The oracle is the chunked float32 SSD jitted as ``ssd_oracle`` and run
    at float32 matmul precision: a TPU's default precision for an f32 dot
    is one bf16 pass.  Needs a TPU.
    """
    @jax.jit
    def ssd_scan(x, dt, B_, C_, A, D):
        return K.ssd_scan(x, dt, B_, C_, A, D, chunk=chunk, hb=hb,
                          interpret=False)

    chunked = _host(jax.jit(_chunked(chunk)))

    def oracle(*args):
        with jax.default_matmul_precision("float32"):
            return chunked(*args)

    compiled = _host(ssd_scan)
    compiled.kernel = ssd_scan
    return dict(ssd_backends(chunk, hb), oracle=oracle, compiled=compiled)
