"""Fault-injection throughput: randomized co-verification scenarios/sec
per fuzz layer (core/fuzz.py).

The metric that matters for the "thousands of hostile scenarios" goal is
how many seeded fault scenarios the harness retires per second — bridge
scenarios pay for three backend runs + differential check, register
scenarios are pure protocol, serving scenarios drive the full engine.

Quick mode (the default, used by benchmarks/run.py and safe for the smoke
lane) sizes the scenario counts to finish in seconds and skips the
model-building serving layer; ``--full`` measures all three layers at
10x the scenario count.

    PYTHONPATH=src:. python benchmarks/bench_fuzz.py [--full]
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core import ProtocolFuzzer

QUICK_N = {"bridge": 8, "registers": 60}
FULL_N = {"bridge": 80, "registers": 600, "serving": 40}


def run(quick: bool = True) -> list[str]:
    counts = QUICK_N if quick else FULL_N
    rows = ["case,layer,scenarios,seconds,scenarios_per_s,faults,passed"]
    for layer, n in counts.items():
        fz = ProtocolFuzzer(seed=0, layers=(layer,))
        if layer == "serving":          # build + jit outside the timing
            fz.run(1)
        t0 = time.perf_counter()
        report = fz.run(n)
        dt = time.perf_counter() - t0
        nfaults = sum(report.fault_counts().values())
        rows.append(f"fuzz,{layer},{n},{dt:.2f},{n / dt:.1f},"
                    f"{nfaults},{report.passed}")
    return rows


def run_full() -> list[str]:
    return run(quick=False)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("\n".join(run(quick="--full" not in sys.argv[1:])))
