"""Co-verification sweeps of one elementwise kernel, z = alpha·x + y in
bfloat16, back to back through ``CoVerifySession.run``: the stand-in
second configuration that ``tests/test_bench_standin.py`` lays over a copy
of the benchmark as new files.

The firmware writes x and y (made once from the seed) into DDR and
launches ``axpy`` with its per-block burst list over the shared congestion
link; each sweep runs {oracle, compiled} and diffs their DDR state.
Correctness: the compiled tier's output of every sweep in the window is
compared, after the window, with the configuration's plain reference.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from bench import stats

OPS = ("axpy",)


def axpy_kernel(x, y, *, alpha: float, block: int, interpret: bool):
    """alpha·x + y over row blocks of ``block`` rows, in float32, written
    in x's type."""
    import jax
    from jax.experimental import pallas as pl

    def body(x_ref, y_ref, o_ref):
        o_ref[...] = (alpha * x_ref[...].astype(np.float32)
                      + y_ref[...].astype(np.float32)).astype(o_ref.dtype)

    spec = pl.BlockSpec((block, x.shape[1]), lambda i: (i, 0))
    return pl.pallas_call(
        body, grid=(x.shape[0] // block,), in_specs=[spec, spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret)(x, y)


def tables(block: int, interpret: bool = False) -> Dict[str, dict]:
    """oracle = NumPy in float32; compiled = the Pallas kernel (compiled
    for the chip, or in interpret mode)."""
    import jax
    import jax.numpy as jnp

    def oracle(x, y, *, alpha):
        z = alpha * np.asarray(x, np.float32) + np.asarray(y, np.float32)
        return z.astype(x.dtype)

    kern = jax.jit(axpy_kernel, static_argnames=("alpha", "block",
                                                 "interpret"))

    def compiled(x, y, *, alpha):
        return np.asarray(kern(jnp.asarray(x), jnp.asarray(y), alpha=alpha,
                               block=block, interpret=interpret))
    return {"axpy": {"oracle": oracle, "compiled": compiled}}


def control_tables(tables: Dict[str, dict], ref, cast) -> Dict[str, dict]:
    """The plain reference in the compiled kernel's place, from inputs
    rounded to ``cast``, written back in the inputs' type."""
    def axpy(x, y, *, alpha):
        return np.asarray(ref.axpy(x, y, alpha, cast=cast).astype(x.dtype))
    return {"axpy": dict(tables["axpy"], compiled=axpy)}


def make_inputs(kern: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    import jax
    import jax.numpy as jnp

    shape = (kern["R"], kern["C"])
    key = jax.random.key(int(np.random.default_rng(seed).integers(2**31)))

    @jax.jit
    def gen(key):
        kx, ky = jax.random.split(key)
        return {"x": jax.random.normal(kx, shape, jnp.bfloat16),
                "y": jax.random.normal(ky, shape, jnp.bfloat16)}

    return {n: np.asarray(v) for n, v in gen(key).items()}


def build_session(kern: Dict[str, Any], traffic: dict,
                  inputs: Dict[str, np.ndarray], tables: Dict[str, dict],
                  spans):
    from repro.core import CoVerifySession
    from repro.core.congestion import CongestionConfig

    x = inputs["x"]
    row = x.shape[1] * x.dtype.itemsize

    def bursts(block):
        txs = []
        for i in range(x.shape[0] // block):
            for eng, d, base in (("dma_x", "read", 0),
                                 ("dma_y", "read", x.nbytes),
                                 ("dma_z", "write", 2 * x.nbytes)):
                txs.append((eng, d, base + i * block * row, block * row))
        return txs

    def firmware(fb, op, backend, *, block):
        for n in ("x", "y"):
            fb.mem.alloc(n, x.shape, x.dtype)
            fb.mem.host_write(n, inputs[n])
        fb.mem.alloc("z", x.shape, x.dtype)
        with spans.span("launch"):
            fb.launch(op, backend, ["x", "y"], ["z"],
                      burst_list=lambda: bursts(block), alpha=kern["alpha"])

    def spanned(fn):
        def call(*args, **kw):
            with spans.span("backend"):
                return fn(*args, **kw)
        return call

    sess = CoVerifySession(firmware, congestion=CongestionConfig())
    sess.register_op("axpy", **{b: spanned(tables["axpy"][b])
                                for b in traffic["backends"]})
    for b in traffic["backends"]:
        sess.add_cell("axpy", b, {"block": traffic["block"]})
    return sess


def run(ctx) -> Dict[str, Any]:
    cfg, tr = ctx.config, ctx.traffic
    kern = cfg["kernels"]["axpy"]
    inputs = make_inputs(kern, ctx.seed)
    t = ctx.tables(tr["block"]) if ctx.tables else tables(tr["block"])
    if ctx.control is not None:
        t = control_tables(t, ctx.reference, ctx.control)
    sess = build_session(kern, tr, inputs, t, ctx.spans)

    def sweep():
        rep = sess.run(tol=cfg["session"]["tol"])
        out = [r.outputs["z"] for r in rep.cells
               if r.cell.backend == "compiled"][0]
        return rep, out

    rep, _ = sweep()                                  # warm-up: compiles
    if not rep.passed:
        ctx.notes.append(f"warm-up sweep failed: {rep.summary()}")
    ctx.setup_done()

    kept, took, failed = [], [], 0

    def one():
        nonlocal failed
        t0 = time.perf_counter()
        with ctx.spans.span("sweep"):
            rep, out = sweep()
        took.append(time.perf_counter() - t0)
        failed += not rep.passed
        kept.append(out)

    t0 = time.perf_counter()
    with ctx.traced_window() as tw:
        one()
        while tw.elapsed() < min(tr["trace_seconds"], ctx.seconds):
            one()
    traced = {"sweeps": len(kept), "spans": ctx.spans.snapshot()}
    while time.perf_counter() - t0 < ctx.seconds:
        one()
    window = time.perf_counter() - t0
    ctx.window_done()
    del sess

    ref = ctx.reference
    want = ref.axpy(inputs["x"], inputs["y"], kern["alpha"])
    err = max(ref.rel_err(o, want) for o in kept)
    checks = {"axpy_err": {"value": err,
                           "limit": cfg["checks"]["axpy_err"]["limit"]},
              "sweeps_failed": {"value": failed, "limit": 0}}
    return {"attempted": len(kept), "failed": failed,
            "e2e": {"sweep_s": stats.sweep_s(window, len(kept))},
            "checks": checks,
            "info": {"sweep_seconds": took,
                     "traced_sweeps": traced["sweeps"],
                     "traced_spans": traced["spans"],
                     "axpy": {"R": kern["R"], "C": kern["C"]},
                     "itemsize": inputs["x"].dtype.itemsize}}
