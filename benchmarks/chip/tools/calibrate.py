"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/tools/calibrate.py --workload <name> \
        --seconds <s> --seeds 1,2,... --control-seeds 7,8,9

For every seed, one run of the cell's timed path (the same driver and
comparison as run.py, with a window of ``--seconds``) prints the numbers
compared.  For each control seed the run puts the control in the compiled
kernel's place: the configuration's reference computed from inputs in
float8 (e4m3), the next type below bfloat16; it has to come out not
correct.  One JSON line per run, all in one process; the limits in the
configuration file are set between the largest program reading and the
smallest control reading (PERF.md gives both).  run.py never runs this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from bench import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(common.CACHE_DIR)
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = common.load_module(HERE / "run.py")
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    found = common.find_cell(bench, args.workload)
    peak = common.peak_for(jax.devices()[0].device_kind)
    runs = [(int(s), None) for s in args.seeds.split(",") if s]
    runs += [(int(s), jnp.float8_e4m3fn)
             for s in args.control_seeds.split(",") if s]
    for seed, control in runs:
        t0 = time.perf_counter()
        ctx = run.Ctx(found, seed, args.seconds, False, t0)
        ctx.devices = jax.devices()[:1]
        ctx.control = control
        res = run.run_cell(found, ctx, peak)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": control is not None,
                          "failed": res["failed"],
                          "attempted": res["attempted"],
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"],
                          "wall_s": time.perf_counter() - t0}), flush=True)
        print("\n".join(ctx.notes), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
