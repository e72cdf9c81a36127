"""The CPU test case of ``drivers/ssd_sweep.py``'s cells, with the names of
``cases/coverify_sweep.py``: the cell cut to a CPU size, the backend
tables, the answers a planted fault breaks and the checks it must fail,
the control, and a hand-made traced run for the readers of these cells.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench import common, trace as tr

# every width but the context and the heads, which a CPU run cannot hold:
# two groups of 8 heads, one head block each
SSD = {"B": 1, "L": 256, "H": 16, "P": 64, "G": 2, "N": 128, "chunk": 128,
       "hb": 8}

# the entries of the driver's tables whose compiled answer a planted fault
# breaks: each output of the scan (``by_output``)
OPS = ("y", "state")

# the control: the plain reference in the kernel's place, computed from x,
# B and C rounded to float8, the next type below the configuration's
# bfloat16; it must fail each of these checks
CONTROL = jnp.float8_e4m3fn
CONTROL_FAILS = ("ssd_y_err", "ssd_state_err")

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def driver():
    return common.load_module(common.BENCH_DIR / "drivers" / "ssd_sweep.py")


def cpu_found(found):
    """The cell as run.py finds it, cut to a CPU size."""
    found["config"]["kernels"]["ssd"].update(SSD)
    return found


def cpu_tables(chunk: int, hb: int):
    """The tiers of ``ssd_backends``: on the CPU the compiled tier is the
    jitted chunked SSD (the kernel's agreement with it is a kernel test)."""
    from repro.kernels.mamba2_scan.sweep import ssd_backends
    return driver().by_output(ssd_backends(chunk, hb))


def fault_checks(ops, fault: str):
    """(checks that must fail, checks that must pass) with ``fault``
    planted in the compiled answers of ``ops``: with both outputs broken,
    both errors and the session's diff fail, whichever the fault."""
    if tuple(ops) != OPS:
        raise ValueError(f"no expectation for a fault in {ops!r}")
    return {"ssd_y_err", "ssd_state_err", "sweeps_failed"}, set()


def sample_run():
    """A hand-made traced run of one sweep at the CPU size: the kernel's
    custom call, the oracle's fusion, the window, the driver's counts."""
    E = tr.Event
    s = SSD
    ops = [E("ssd_scan.1", 1_000, 31_000, "jit_ssd_scan", "d0",
             f"ssd_scan.1 (bf16[{s['B']},{s['H']},{s['L']},{s['P']}], "
             f"f32[{s['B']},{s['H']},{s['P']},{s['N']}]) custom-call(...)"),
           E("fusion", 40_000, 90_000, "jit_ssd_oracle", "d0", "fusion")]
    spans = [E(tr.WINDOW_SPAN, 0, 1_000_000)]
    info = {"traced_sweeps": 1, "sweep_seconds": [0.9, 0.8, 4.0],
            "ssd": dict(SSD), "itemsize": 2,
            "traced_spans": {"sweep": [0.9, 1], "launch": [0.5, 2],
                             "backend": [0.2, 2]}}
    return {"trace": tr.Trace(ops, spans, ["d0"]), "info": info,
            "peak": PEAK}


def empty_run():
    """``sample_run`` with nothing in its traced window."""
    run = sample_run()
    return dict(run, trace=tr.Trace([], [tr.Event(tr.WINDOW_SPAN, 0, 10)],
                                    []),
                info=dict(run["info"], traced_spans={}, traced_sweeps=0,
                          sweep_seconds=[]))
