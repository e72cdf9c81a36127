"""Reusable flash-attention co-verification sweep pieces (kernel layout
B,H,S,D), mirroring kernels/systolic_matmul/sweep.py: one firmware + one
backend table shared by the scheduler tests, the fabric scaling benchmark,
and the cluster example, plus the head-sharded fabric firmware.

Heads are independent in attention, so the fabric layout
(sharding/specs.py "flash_attention": shard q/k/v/o on H) gathers to a
bit-identical result vs the single-device launch whenever the device
count divides both H and KH.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as R


def _inputs(batch: int, heads: int, seq: int, dim: int):
    """Seeded kernel-layout q/k/v (MHA: KH == H, so any device count that
    divides H shards exactly)."""
    rng = np.random.default_rng(batch * 7919 + heads * 101 + seq + dim)
    q = rng.normal(size=(batch, heads, seq, dim)).astype(np.float32)
    k = rng.normal(size=(batch, heads, seq, dim)).astype(np.float32)
    v = rng.normal(size=(batch, heads, seq, dim)).astype(np.float32)
    return q, k, v


def flash_backends(bq: int = 32, bk: int = 32, causal: bool = True,
                   jit: bool = True) -> dict:
    """oracle/interpret/compiled backend table for register_op.

    oracle = jnp reference, interpret = Pallas kernel in interpret mode
    ("RTL sim"), compiled = jitted reference (XLA deployment tier).
    """
    def oracle(q, k, v):
        return np.asarray(R.attention_ref(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))

    def interp_raw(q, k, v):
        out, _ = K.flash_fwd(q, k, v, causal=causal, window=0, bq=bq, bk=bk,
                             interpret=True)
        return out

    if not jit:
        return dict(
            oracle=oracle,
            interpret=lambda q, k, v: np.asarray(
                interp_raw(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))),
            compiled=oracle)
    jit_interp = jax.jit(interp_raw)
    jit_ref = jax.jit(lambda q, k, v: R.attention_ref(q, k, v,
                                                      causal=causal))
    return dict(
        oracle=oracle,
        interpret=lambda q, k, v: np.asarray(jit_interp(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))),
        compiled=lambda q, k, v: np.asarray(jit_ref(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))))


def flash_chip_backends(bq: int, bk: int, causal: bool = True) -> dict:
    """``flash_backends(bq, bk, causal)`` for a TPU.

    compiled = the Pallas forward kernel compiled for the chip
    (``interpret=False``), jitted once under its own name, so the device
    trace and the lowered program name it ``flash_attention_fwd``; the
    ``kernel`` attribute is that jitted function, so a caller can read the
    lowered program.  The oracle runs at float32 matmul precision: a TPU's
    default precision for an f32 dot is one bf16 pass.  Needs a TPU.
    """
    table = flash_backends(bq, bk, causal)
    ref = table["oracle"]

    @jax.jit
    def flash_attention_fwd(q, k, v):
        return K.flash_fwd(q, k, v, causal=causal, window=0, bq=bq, bk=bk,
                           interpret=False)[0]

    def oracle(q, k, v):
        with jax.default_matmul_precision("float32"):
            return ref(q, k, v)

    def compiled(q, k, v):
        return np.asarray(flash_attention_fwd(jnp.asarray(q), jnp.asarray(k),
                                              jnp.asarray(v)))
    compiled.kernel = flash_attention_fwd
    return dict(table, oracle=oracle, compiled=compiled)


def flash_firmware(fb, op, backend, *, batch=1, heads=8, seq=64, dim=16,
                   bq: int = 32, bk: int = 32):
    """Single-device host program: alloc/seed q/k/v/o DDR buffers, launch
    with the BlockSpec-derived per-tile burst list (§IV contract)."""
    q, k, v = _inputs(batch, heads, seq, dim)
    for name, arr in (("q", q), ("k", k), ("v", v)):
        fb.mem.alloc(name, arr.shape, np.float32)
        fb.mem.host_write(name, arr)
    fb.mem.alloc("o", q.shape, np.float32)
    fb.launch(op, backend, ["q", "k", "v"], ["o"],
              burst_list=lambda: fa_ops.transactions(
                  batch, heads, seq, seq, dim, bq=bq, bk=bk, causal=True,
                  dtype_bytes=4))


def flash_fabric_firmware(fab, op, backend, *, batch=1, heads=8, seq=64,
                          dim=16, bq: int = 32, bk: int = 32):
    """Head-sharded fabric counterpart of ``flash_firmware`` (same seeded
    data, same host buffer names): scatter q/k/v on H, device-local
    launches with shard-sized burst lists, gather o on H."""
    from repro.core.fabric import sharded_launch
    from repro.sharding.specs import FABRIC_OP_SPECS

    if heads % fab.n:
        raise ValueError(f"device count {fab.n} must divide heads {heads}")
    q, k, v = _inputs(batch, heads, seq, dim)
    sharded_launch(
        fab, op, backend,
        inputs={"q": q, "k": k, "v": v},
        output=("o", q.shape, np.float32),
        specs=FABRIC_OP_SPECS["flash_attention"],
        burst_list=lambda dev, shapes: fa_ops.transactions(
            batch, shapes["q"][1], seq, seq, dim, bq=bq, bk=bk, causal=True,
            dtype_bytes=4))
