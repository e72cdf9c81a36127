"""Co-verification sweeps of the Mamba-2 SSD scan back to back through
``CoVerifySession.run``.

The user's program here is the firmware: it allocates the scan's DDR
buffers, writes the inputs made once in set-up from the seed (x, dt, B,
C, A, D), and launches the ``ssd`` op through ``FireBridge.launch`` into
``y`` and ``state`` with the kernel's per-tile burst list, over the shared
congestion link.  Each sweep runs the op on every backend of the traffic
file and diffs the backends' DDR state (the session's own check).  The
window runs whole sweeps until ``--seconds`` have passed.

The backend tables take the form of ``by_output``: ``scan`` runs a tier,
and each output (``y``, ``state``) has an entry that takes that output
from the tier's answer, so that a planted fault in the tests can break an
answer one array at a time.

Correctness: the compiled tier's ``y`` and ``state`` of every sweep in
the window are compared, after the window, with the configuration's plain
reference (``rel_err``), and every sweep's session report has to pass.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from bench import stats

OP = "ssd"
INS = ("x", "dt", "B", "C", "A", "D")
OUTS = ("y", "state")


def make_inputs(cfg: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """The scan's inputs from the seed, made on the device in one call and
    then held on the host as the firmware's source data: x, B and C
    standard normal in bfloat16; per step and head dt = softplus(N(0,1) +
    dt_bias), with dt_bias the inverse softplus of a per-head draw
    log-uniform in [time_step_min, time_step_max], floored at
    time_step_floor; A = -U[1, 16] and D standard normal per head."""
    import jax
    import jax.numpy as jnp

    s = cfg["kernels"]["ssd"]
    b, H, L, P, G, N = s["B"], s["H"], s["L"], s["P"], s["G"], s["N"]
    lo, hi = np.log(cfg["time_step_min"]), np.log(cfg["time_step_max"])
    floor = cfg["time_step_floor"]
    key = jax.random.key(int(np.random.default_rng(seed).integers(2**31)))

    @jax.jit
    def gen(key):
        k = jax.random.split(key, 7)
        dt0 = jnp.maximum(jnp.exp(jax.random.uniform(k[0], (H,), minval=lo,
                                                     maxval=hi)), floor)
        dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
        dt = jax.nn.softplus(jax.random.normal(k[1], (b, H, L))
                             + dt_bias[:, None])
        return {"x": jax.random.normal(k[2], (b, H, L, P), jnp.bfloat16),
                "dt": dt,
                "B": jax.random.normal(k[3], (b, G, L, N), jnp.bfloat16),
                "C": jax.random.normal(k[4], (b, G, L, N), jnp.bfloat16),
                "A": -jax.random.uniform(k[5], (H,), minval=1.0,
                                         maxval=16.0),
                "D": jax.random.normal(k[6], (H,))}

    return {n: np.asarray(v) for n, v in gen(key).items()}


def by_output(table: Dict[str, Any]) -> Dict[str, dict]:
    """One backend table of the scan (tier -> callable returning
    (y, state)) as the driver's tables: ``scan`` holds the tiers, and each
    output's entry takes that output from a tier's answer."""
    tables = {"scan": dict(table)}
    for i, name in enumerate(OUTS):
        tables[name] = {t: (lambda answer, i=i: answer[i]) for t in table}
    return tables


def chip_tables(chunk: int, hb: int) -> Dict[str, dict]:
    from repro.kernels.mamba2_scan.sweep import ssd_chip_backends
    return by_output(ssd_chip_backends(chunk, hb))


def control_tables(tables: Dict[str, dict], ref, cast) -> Dict[str, dict]:
    """The control: the plain reference in the compiled kernel's place,
    computed from x, B and C rounded to ``cast`` and with y written back in
    x's type, so that it goes through the same session, writeback and
    comparison as the kernel."""
    def scan(x, dt, B, C, A, D):
        y, state = ref.ssd(x, dt, B, C, A, D, cast=cast)
        return np.asarray(y.astype(x.dtype)), np.asarray(state)
    return dict(tables, scan=dict(tables["scan"], compiled=scan))


def build_session(config: dict, traffic: dict, inputs: Dict[str, np.ndarray],
                  tables: Dict[str, dict], spans):
    """The session the window drives: one firmware, the ``ssd`` op
    registered with one callable per backend (wrapped in a ``backend``
    span), one cell per backend."""
    from repro.core import CoVerifySession
    from repro.core.congestion import CongestionConfig
    from repro.kernels.mamba2_scan import ops as ssd_ops

    s = config["kernels"]["ssd"]
    x = inputs["x"]

    def firmware(fb, op, backend):
        for n in INS:
            fb.mem.alloc(n, inputs[n].shape, inputs[n].dtype)
            fb.mem.host_write(n, inputs[n])
        fb.mem.alloc("y", x.shape, x.dtype)
        fb.mem.alloc("state", (s["B"], s["H"], s["P"], s["N"]), np.float32)
        with spans.span("launch"):
            fb.launch(op, backend, list(INS), list(OUTS),
                      burst_list=lambda: ssd_ops.transactions(
                          s["B"], s["L"], s["H"], s["P"], s["N"], G=s["G"],
                          chunk=s["chunk"], hb=s["hb"],
                          dtype_bytes=x.dtype.itemsize))

    def backend(tier):
        def call(*args):
            with spans.span("backend"):
                answer = tables["scan"][tier](*args)
                return tuple(tables[n][tier](answer) for n in OUTS)
        return call

    sess = CoVerifySession(firmware, congestion=CongestionConfig())
    sess.register_op(OP, **{b: backend(b) for b in traffic["backends"]})
    for b in traffic["backends"]:
        sess.add_cell(OP, b, {})
    return sess


def run(ctx) -> Dict[str, Any]:
    cfg, tr = ctx.config, ctx.traffic
    s = cfg["kernels"]["ssd"]
    tol = cfg["session"]["tol"]
    tables = (ctx.tables(s["chunk"], s["hb"]) if ctx.tables
              else chip_tables(s["chunk"], s["hb"]))
    inputs = make_inputs(cfg, ctx.seed)
    if ctx.control is not None:
        tables = control_tables(tables, ctx.reference, ctx.control)
    sess = build_session(cfg, tr, inputs, tables, ctx.spans)

    def sweep():
        rep = sess.run(tol=tol)
        outs = {n: r.outputs[n] for r in rep.cells
                if r.cell.backend == "compiled" for n in OUTS}
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
    want = dict(zip(OUTS, ref.ssd(*(inputs[n] for n in INS))))
    errs = {n: max(ref.rel_err(o[n], want[n]) for o in kept) for n in OUTS}
    lim = cfg["checks"]
    checks = {"ssd_y_err": {"value": errs["y"],
                            "limit": lim["ssd_y_err"]["limit"]},
              "ssd_state_err": {"value": errs["state"],
                                "limit": lim["ssd_state_err"]["limit"]},
              "sweeps_failed": {"value": failed, "limit": 0}}
    ctx.notes.append(f"bursts per sweep (counters): {bursts}")
    ctx.notes.append("seconds of each sweep in the window: "
                     + " ".join(f"{t:.3f}" for t in took))
    return {"attempted": sweeps, "failed": failed,
            "e2e": {"sweep_s": stats.sweep_s(window, sweeps)},
            "checks": checks,
            "info": {"sweep_seconds": took,
                     "traced_sweeps": traced["sweeps"],
                     "traced_spans": traced["spans"],
                     "ssd": {k: s[k] for k in ("B", "L", "H", "P", "G", "N",
                                               "chunk", "hb")},
                     "itemsize": inputs["x"].dtype.itemsize,
                     "bursts_per_sweep": bursts}}
