"""Reusable matmul co-verification sweep pieces (paper Fig. 5 cells).

One firmware + one backend table for the systolic matmul, shared by the
quickstart preflight, the Fig. 5 sweep benchmark, and the scheduler tests
so the three stay in lockstep.  The firmware signature matches
core/scheduler.CoVerifySession: ``firmware(fb, op, backend, **config)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.systolic_matmul import ops as mm_ops, ref as mm_ref
from repro.kernels.systolic_matmul.kernel import matmul as mm_kernel


def matmul_firmware(fb, op, backend, *, size, tile: int = 32):
    """Host-side program for one sweep cell: alloc/seed DDR, launch the
    matmul with its per-tile burst list (§IV data-movement contract)."""
    rng = np.random.default_rng(size)
    a = rng.normal(size=(size, size)).astype(np.float32)
    b = rng.normal(size=(size, size)).astype(np.float32)
    fb.mem.alloc("a", a.shape, np.float32)
    fb.mem.alloc("b", b.shape, np.float32)
    fb.mem.alloc("c", (size, size), np.float32)
    fb.mem.host_write("a", a)
    fb.mem.host_write("b", b)
    fb.launch(op, backend, ["a", "b"], ["c"],
              burst_list=lambda: mm_ops.transactions(
                  size, size, size, bm=tile, bn=tile, bk=tile,
                  dtype_bytes=4))


def matmul_fabric_firmware(fab, op, backend, *, size, tile: int = 32):
    """Sharded fabric counterpart of ``matmul_firmware`` (same seeded data,
    same host buffer names): row-shard A/C across the cluster, broadcast B
    — the ``sharding/specs.py`` "systolic_matmul" fabric layout — then
    gather C.  K is never split, so the gathered C is bit-identical to the
    single-device launch of the same backend.
    """
    from repro.core.fabric import sharded_launch
    from repro.sharding.specs import FABRIC_OP_SPECS

    rng = np.random.default_rng(size)
    a = rng.normal(size=(size, size)).astype(np.float32)
    b = rng.normal(size=(size, size)).astype(np.float32)
    sharded_launch(
        fab, op, backend,
        inputs={"a": a, "b": b},
        output=("c", (size, size), np.float32),
        specs=FABRIC_OP_SPECS["systolic_matmul"],
        burst_list=lambda dev, shapes: mm_ops.transactions(
            shapes["c"][0], size, size,
            bm=min(tile, shapes["c"][0]), bn=tile, bk=tile, dtype_bytes=4))


def matmul_backends(tile: int = 32, jit: bool = True) -> dict:
    """oracle/interpret/compiled backend table for register_op.

    With ``jit`` the interpret and compiled backends are jitted ONCE at
    table-creation time — registering one table per CoVerifySession is
    what makes traces/executables cache across sweep cells; re-creating
    the table per cell (the sequential baseline) re-pays tracing.
    """
    oracle = lambda x, y: np.asarray(mm_ref.matmul_ref(jnp.asarray(x),
                                                       jnp.asarray(y)))
    if not jit:
        return dict(
            oracle=oracle,
            interpret=lambda x, y: np.asarray(mm_kernel(
                jnp.asarray(x), jnp.asarray(y), bm=tile, bn=tile, bk=tile,
                interpret=True)),
            compiled=oracle)
    jit_interp = jax.jit(lambda x, y: mm_kernel(
        x, y, bm=tile, bn=tile, bk=tile, interpret=True))
    jit_mm = jax.jit(lambda x, y: mm_ref.matmul_ref(x, y))
    return dict(
        oracle=oracle,
        interpret=lambda x, y: np.asarray(jit_interp(jnp.asarray(x),
                                                     jnp.asarray(y))),
        compiled=lambda x, y: np.asarray(jit_mm(jnp.asarray(x),
                                                jnp.asarray(y))))


def matmul_chip_backends(tile: int) -> dict:
    """``matmul_backends(tile)`` for a TPU.

    compiled = the Pallas kernel compiled for the chip (``interpret=False``),
    jitted once under its own name, so the device trace and the lowered
    program name it ``systolic_matmul``; the ``kernel`` attribute is that
    jitted function, so a caller can read the lowered program.  The
    oracle runs at float32 matmul precision: a TPU's default precision for
    an f32 dot is one bf16 pass.  Needs a TPU and (8, 128)-aligned tiles.
    """
    table = matmul_backends(tile)
    ref = table["oracle"]

    @jax.jit
    def systolic_matmul(x, y):
        return mm_kernel(x, y, bm=tile, bn=tile, bk=tile, interpret=False)

    def oracle(x, y):
        with jax.default_matmul_precision("float32"):
            return ref(x, y)

    def compiled(x, y):
        return np.asarray(systolic_matmul(jnp.asarray(x), jnp.asarray(y)))
    compiled.kernel = systolic_matmul
    return dict(table, oracle=oracle, compiled=compiled)
