"""The CPU test case of the stand-in ``drivers/axpy_sweep.py``: the same
names as ``cases/coverify_sweep.py``."""
from __future__ import annotations

import jax.numpy as jnp

from bench import common, trace as tr

R, C, BLOCK = 256, 512, 64
OPS = ("axpy",)
CONTROL = jnp.float8_e4m3fn
CONTROL_FAILS = ("axpy_err",)


def cpu_found(found):
    found["config"]["kernels"]["axpy"].update(R=R, C=C)
    found["traffic"]["block"] = BLOCK
    return found


def cpu_tables(block: int):
    driver = common.load_module(common.BENCH_DIR / "drivers" /
                                "axpy_sweep.py")
    return driver.tables(block, interpret=True)


def fault_checks(ops, fault: str):
    if tuple(ops) != OPS:
        raise ValueError(f"no expectation for a fault in {ops!r}")
    return {"axpy_err", "sweeps_failed"}, set()


def sample_run():
    E = tr.Event
    ops = [E("axpy.1", 1_000, 3_000, "jit_axpy_kernel", "d0",
             f"axpy.1 bf16[{R},{C}]{{1,0}} custom-call(...)"),
           E("fusion", 4_000, 5_000, "jit_gen", "d0", "fusion")]
    info = {"traced_sweeps": 1, "sweep_seconds": [0.01],
            "traced_spans": {"sweep": [0.01, 1]},
            "axpy": {"R": R, "C": C}, "itemsize": 2}
    return {"trace": tr.Trace(ops, [E(tr.WINDOW_SPAN, 0, 100_000)], ["d0"]),
            "info": info,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def empty_run():
    run = sample_run()
    return dict(run, trace=tr.Trace([], [tr.Event(tr.WINDOW_SPAN, 0, 10)],
                                    []),
                info=dict(run["info"], traced_spans={}, traced_sweeps=0,
                          sweep_seconds=[]))
