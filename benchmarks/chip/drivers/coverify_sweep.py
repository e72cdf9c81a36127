"""Co-verification sweeps back to back through ``CoVerifySession.run``.

The user's program here is the firmware: it allocates the kernels' DDR
buffers, writes the inputs made once in set-up from the seed, and launches
each op through ``FireBridge.launch`` with the kernel's burst list, over
the shared congestion link.  Each sweep runs every (op, backend) cell of
the traffic file and diffs the backends' DDR state (the session's own
check).  The window runs whole sweeps until ``--seconds`` have passed.

Correctness: the compiled tier's DDR output of every sweep in the window
is compared, after the window, with the configuration's plain reference
(``rel_err``), and every sweep's session report has to pass.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from bench import stats

OPS = ("matmul", "flash")


def make_inputs(kern: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """The kernels' inputs in bfloat16, standard normal, from the seed (made
    on the device in one call, then held on the host as the firmware's
    source data)."""
    import jax
    import jax.numpy as jnp

    mm, fl = kern["matmul"], kern["flash"]
    shapes = {"a": (mm["M"], mm["K"]), "b": (mm["K"], mm["N"]),
              "q": (fl["B"], fl["H"], fl["S"], fl["D"]),
              "k": (fl["B"], fl["KH"], fl["S"], fl["D"]),
              "v": (fl["B"], fl["KH"], fl["S"], fl["D"])}
    key = jax.random.key(int(np.random.default_rng(seed).integers(2**31)))

    @jax.jit
    def gen(key):
        ks = jax.random.split(key, len(shapes))
        return {n: jax.random.normal(k, s, jnp.bfloat16)
                for k, (n, s) in zip(ks, sorted(shapes.items()))}

    return {n: np.asarray(x) for n, x in gen(key).items()}


def chip_tables(tile: int) -> Dict[str, dict]:
    from repro.kernels.flash_attention.sweep import flash_chip_backends
    from repro.kernels.systolic_matmul.sweep import matmul_chip_backends
    return {"matmul": matmul_chip_backends(tile),
            "flash": flash_chip_backends(tile, tile)}


def control_tables(tables: Dict[str, dict], ref, cast) -> Dict[str, dict]:
    """The control: the plain reference in the compiled kernel's place,
    computed from inputs rounded to ``cast`` and written back in the
    inputs' type, so that it goes through the same session, writeback and
    comparison as the kernel."""
    def matmul(a, b):
        return np.asarray(ref.matmul(a, b, cast=cast).astype(a.dtype))

    def flash(q, k, v):
        return np.asarray(ref.causal_attention(q, k, v, cast=cast)
                          .astype(q.dtype))
    return {"matmul": dict(tables["matmul"], compiled=matmul),
            "flash": dict(tables["flash"], compiled=flash)}


def build_session(config: dict, traffic: dict, inputs: Dict[str, np.ndarray],
                  tables: Dict[str, dict], spans):
    """The session the window drives: one firmware, one registered table per
    op (backend callables wrapped in a ``backend`` span), one cell per
    (op, backend)."""
    from repro.core import CoVerifySession
    from repro.core.congestion import CongestionConfig
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.systolic_matmul import ops as mm_ops

    mm, fl = config["kernels"]["matmul"], config["kernels"]["flash"]
    dt = inputs["a"].dtype
    item = dt.itemsize

    def firmware(fb, op, backend, *, tile):
        if op == "matmul":
            for n in ("a", "b"):
                fb.mem.alloc(n, inputs[n].shape, dt)
                fb.mem.host_write(n, inputs[n])
            fb.mem.alloc("c", (mm["M"], mm["N"]), dt)
            with spans.span("launch"):
                fb.launch(op, backend, ["a", "b"], ["c"],
                          burst_list=lambda: mm_ops.transactions(
                              mm["M"], mm["N"], mm["K"], bm=tile, bn=tile,
                              bk=tile, dtype_bytes=item))
        else:
            for n in ("q", "k", "v"):
                fb.mem.alloc(n, inputs[n].shape, dt)
                fb.mem.host_write(n, inputs[n])
            fb.mem.alloc("o", inputs["q"].shape, dt)
            with spans.span("launch"):
                fb.launch(op, backend, ["q", "k", "v"], ["o"],
                          burst_list=lambda: fa_ops.transactions(
                              fl["B"], fl["H"], fl["S"], fl["S"], fl["D"],
                              bq=tile, bk=tile, causal=True,
                              dtype_bytes=item))

    def spanned(fn):
        def call(*args):
            with spans.span("backend"):
                return fn(*args)
        return call

    sess = CoVerifySession(firmware, congestion=CongestionConfig())
    for op in OPS:
        t = tables[op]
        sess.register_op(op, **{b: spanned(t[b]) for b in traffic["backends"]})
    for op in OPS:
        for b in traffic["backends"]:
            sess.add_cell(op, b, {"tile": traffic["tile"]})
    return sess


def run(ctx) -> Dict[str, Any]:
    cfg, tr = ctx.config, ctx.traffic
    tol = cfg["session"]["tol"]
    inputs = make_inputs(cfg["kernels"], ctx.seed)
    tables = ctx.tables(tr["tile"]) if ctx.tables else chip_tables(tr["tile"])
    if ctx.control is not None:
        tables = control_tables(tables, ctx.reference, ctx.control)
    sess = build_session(cfg, tr, inputs, tables, ctx.spans)
    out_of = {"matmul": "c", "flash": "o"}

    def sweep():
        rep = sess.run(tol=tol)
        outs = {op: r.outputs[out_of[op]] for r in rep.cells
                for op in OPS if r.cell.op == op
                and r.cell.backend == "compiled"}
        bursts = sum(r.counters["totals"].get("transactions", 0)
                     for r in rep.cells if r.counters)
        return rep.passed, outs, bursts, rep

    ok, _, bursts, rep = sweep()                  # warm-up: compiles
    if not ok:
        ctx.notes.append(f"warm-up sweep failed: {rep.summary()}")
    ctx.setup_done()

    kept = []                       # compiled outputs of every sweep
    took = []                       # host seconds of every sweep
    failed = 0

    def one():
        nonlocal failed
        t = time.perf_counter()
        with ctx.spans.span("sweep"):
            ok, outs, _, rep = sweep()
        took.append(time.perf_counter() - t)
        if not ok:
            failed += 1
            ctx.notes.append(f"sweep {len(kept) + 1} failed: "
                             f"{rep.summary()}")
        kept.append(outs)

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
    sweeps = len(kept)
    del sess

    ref = ctx.reference
    want = {"matmul": ref.matmul(inputs["a"], inputs["b"]),
            "flash": ref.causal_attention(inputs["q"], inputs["k"],
                                          inputs["v"])}
    errs = {op: max(ref.rel_err(o[op], want[op]) for o in kept)
            for op in OPS}
    lim = cfg["checks"]
    checks = {"matmul_err": {"value": errs["matmul"],
                             "limit": lim["matmul_err"]["limit"]},
              "flash_err": {"value": errs["flash"],
                            "limit": lim["flash_err"]["limit"]},
              "sweeps_failed": {"value": failed, "limit": 0}}
    ctx.notes.append(f"bursts per sweep (counters): {bursts}")
    ctx.notes.append("seconds of each sweep in the window: "
                     + " ".join(f"{t:.3f}" for t in took))
    mm, fl = cfg["kernels"]["matmul"], cfg["kernels"]["flash"]
    return {"attempted": sweeps, "failed": failed,
            "e2e": {"sweep_s": stats.sweep_s(window, sweeps)},
            "checks": checks,
            "info": {"sweep_seconds": took,
                     "traced_sweeps": traced["sweeps"],
                     "traced_spans": traced["spans"],
                     "matmul": mm, "flash": fl,
                     "itemsize": inputs["a"].dtype.itemsize,
                     "tile": tr["tile"]}}
