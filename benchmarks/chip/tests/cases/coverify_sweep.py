"""The CPU test case of ``drivers/coverify_sweep.py``'s cells: the cell cut
to a CPU size, the interpret-mode backend tables, the ops a planted fault
breaks and the checks it must fail, the control, and a hand-made traced
run for the readers of these cells.

``tests/cells.py`` finds this file by the driver's name; a cell with
another driver brings ``tests/cases/<driver>.py`` with the same names.
"""
from __future__ import annotations

import copy

import jax.numpy as jnp

from bench import trace as tr

KERNELS = {"matmul": {"M": 256, "K": 384, "N": 512},
           "flash": {"B": 1, "H": 4, "KH": 2, "S": 256, "D": 128,
                     "causal": True}}
TILE = 128

# the ops whose compiled answer a planted fault breaks (each op's
# ``compiled`` callable in the session's backend tables)
OPS = ("matmul", "flash")

# the control: the plain reference in the kernel's place, computed from
# inputs rounded to float8, the next type below the configuration's
# bfloat16; it must fail each of these checks
CONTROL = jnp.float8_e4m3fn
CONTROL_FAILS = ("matmul_err", "flash_err")

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cpu_found(found):
    """The cell as run.py finds it, cut to a CPU size."""
    found["config"]["kernels"] = copy.deepcopy(KERNELS)
    found["traffic"]["tile"] = TILE
    return found


def cpu_tables(tile: int):
    from repro.kernels.flash_attention.sweep import flash_backends
    from repro.kernels.systolic_matmul.sweep import matmul_backends
    return {"matmul": matmul_backends(tile),
            "flash": flash_backends(tile, tile)}


def fault_checks(ops, fault: str):
    """(checks that must fail, checks that must pass) with ``fault``
    planted in the compiled answers of ``ops``.

    Every op broken: the matmul's error and the session's diff fail.
    Flash alone: an output left as allocated, or with half its heads left
    out, reads under 1 by ``flash_err`` (each element's error is at most
    its own |reference|), under that number's limit; the session's diff
    against the oracle catches it (``sweeps_failed``).  One element moved
    by 4 rms fails both, and the matmul stays correct."""
    if tuple(ops) == OPS:
        return {"matmul_err", "sweeps_failed"}, set()
    if tuple(ops) == ("flash",):
        fail = {"sweeps_failed"} | ({"flash_err"} if fault == "altered"
                                    else set())
        return fail, {"matmul_err"}
    raise ValueError(f"no expectation for a fault in {ops!r}")


def sample_run():
    """A hand-made traced run of one sweep at the CPU size: both kernels'
    custom calls, one XLA fusion, the window, the driver's counts."""
    E = tr.Event
    mm, fl = KERNELS["matmul"], KERNELS["flash"]
    ops = [E("custom-call.1", 1_000, 31_000, "jit__lambda", "d0",
             "custom-call.1 %x = bf16[256,512]{1,0} custom-call(...)"),
           E("custom-call.2", 40_000, 60_000, "jit__lambda", "d0",
             "custom-call.2 (bf16[1,4,256,128]{3,2,1,0}, f32[1,4,256,1]) "
             "custom-call(...)"),
           E("fusion", 70_000, 90_000, "jit_dot_general", "d0", "fusion")]
    spans = [E(tr.WINDOW_SPAN, 0, 1_000_000)]
    info = {"traced_sweeps": 1, "sweep_seconds": [0.9, 0.8, 4.0],
            "matmul": {k: mm[k] for k in ("M", "K", "N")},
            "flash": {k: fl[k] for k in ("B", "H", "KH", "S", "D")},
            "itemsize": 2,
            "traced_spans": {"sweep": [0.9, 1], "launch": [0.5, 4],
                             "backend": [0.2, 4]}}
    return {"trace": tr.Trace(ops, spans, ["d0"]), "info": info,
            "peak": PEAK}


def empty_run():
    """``sample_run`` with nothing in its traced window."""
    run = sample_run()
    return dict(run, trace=tr.Trace([], [tr.Event(tr.WINDOW_SPAN, 0, 10)],
                                    []),
                info=dict(run["info"], traced_spans={}, traced_sweeps=0,
                          sweep_seconds=[]))
