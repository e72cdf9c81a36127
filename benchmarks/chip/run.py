"""Run one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by the names in BENCHMARK.json: the workload's
configuration file (``configs/<config>.json``, with its plain reference
beside it), its traffic file (``traffic/<traffic>.json``, whose ``driver``
names the loop in ``drivers/``), and each per-layer metric's reader
(``metrics/<metric>.py``).  A new cell, configuration, mix or metric is new
files and entries; this file does not change.  A new cell whose driver
reports an end-to-end metric that lists its cells (``sweep_s``) appends its
name to that metric's ``workloads`` list; the metric's name, unit and bound
stay.  Its driver's CPU test case is ``tests/cases/<driver>.py``.

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the first part of the window.  The run refuses to start without a TPU, or
with fewer chips than the cell asks for, and then prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                          # noqa: E402
import contextlib                                        # noqa: E402
import os                                                # noqa: E402
import sys                                               # noqa: E402
from pathlib import Path                                 # noqa: E402
from typing import Any, Callable, Dict, List, Optional   # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from bench import common                                 # noqa: E402


class Ctx:
    """What a driver gets: the cell's files, the seed and window, and the
    hooks that mark the end of set-up and the traced window."""

    def __init__(self, found: Dict[str, Any], seed: int, seconds: float,
                 trace: bool, t_start: float,
                 tables: Optional[Callable] = None) -> None:
        self.config, self.traffic = found["config"], found["traffic"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = t_start
        self.tables = tables                 # co-verify backend tables
        self.control = None      # a lower type: the control in the kernel's place
        self.reference = common.load_module(
            common.BENCH_DIR / "configs" / self.config["reference"])
        self.spans = common.Spans()
        self.compiles = common.CompileCounter()
        self.notes: List[str] = []
        self.setup_s: Optional[float] = None
        self.device: Dict[str, Any] = {}
        self.trace_dir: Optional[Path] = None
        self.devices = None

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self.compiles.active = True

    def window_done(self) -> None:
        """The window is over: count its compilations and read the peak
        memory before anything else runs on the device."""
        self.compiles.active = False
        self.notes.append(f"compilations inside the window: "
                          f"{self.compiles.count}")
        if self.devices is not None:
            self.device = common.device_info(self.devices)
            self.notes.append(f"peak_bytes_in_use: "
                              f"{self.device['memory_peak_bytes']}")

    @contextlib.contextmanager
    def traced_window(self):
        """The traced part of the window: with ``--trace 1`` the profiler
        runs and a ``bench.window`` span marks it; spans restart here."""
        import jax
        from jax.profiler import TraceAnnotation

        class Clock:
            def __init__(self):
                self.t0 = time.perf_counter()

            def elapsed(self):
                return time.perf_counter() - self.t0

        self.spans.reset()
        if self.trace:
            self.trace_dir = common.OUT_DIR / "trace" / "current"
            if self.trace_dir.exists():
                import shutil
                shutil.rmtree(self.trace_dir)
            # the Python tracer would time every Python call of the
            # host-bound sweep (about three times slower); the benchmark's
            # own spans are host TraceMe events and stay in the trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        try:
            with TraceAnnotation("bench.window"):
                yield Clock()
        finally:
            if self.trace:
                jax.profiler.stop_trace()


def read_per_layer(found: Dict[str, Any], ctx: Ctx, out: Dict[str, Any],
                   peak: dict) -> Dict[str, Any]:
    """Reduce the trace and call each per-layer metric's reader."""
    from bench import trace as tr
    t = tr.load(tr.find_xplane(str(ctx.trace_dir)))
    run = {"trace": t, "info": out["info"], "peak": peak,
           "config": ctx.config, "traffic": ctx.traffic}
    metrics: Dict[str, Any] = {}
    for m in found["per_layer"]:
        mod = common.load_module(common.BENCH_DIR / "metrics" /
                                 f"{m['name']}.py")
        v = mod.read(run)
        if v is None:
            # BENCHMARK.json lists this cell for the metric: a reader that
            # finds nothing here is matching the wrong names
            raise RuntimeError(f"per-layer metric {m['name']!r} found "
                               f"nothing to read in the trace of "
                               f"{found['cell']['name']!r}")
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    ctx.device["busy_s"] = t.busy_s()
    ctx.device["window_s"] = t.window_s()
    return {"metrics": metrics, "breakdown": t.breakdown()}


def run_cell(found: Dict[str, Any], ctx: Ctx, peak: dict) -> Dict[str, Any]:
    """Drive the cell and build its result line (less the device check)."""
    driver = common.load_module(common.BENCH_DIR / "drivers" /
                                f"{ctx.traffic['driver']}.py")
    out = driver.run(ctx)
    result: Dict[str, Any] = {"correct": common.checks_ok(out["checks"]),
                              "attempted": out["attempted"],
                              "failed": out["failed"]}
    if ctx.trace:
        per = read_per_layer(found, ctx, out, peak)
        result["metrics"] = per["metrics"]
        result["device"] = ctx.device
        result["breakdown"] = per["breakdown"]
    else:
        metrics = {"setup_s": {"value": ctx.setup_s, "unit": "s"}}
        units = {m["name"]: m["unit"] for m in found["end_to_end"]}
        for name, v in out["e2e"].items():
            if name not in units:
                raise RuntimeError(
                    f"driver {ctx.traffic['driver']!r} reports the "
                    f"end-to-end metric {name!r}, which BENCHMARK.json does "
                    f"not apply to cell {found['cell']['name']!r}: append "
                    f"{found['cell']['name']!r} to the \"workloads\" list "
                    f"of {name!r} in BENCHMARK.json's end_to_end")
            metrics[name] = {"value": v, "unit": units[name]}
        result["metrics"] = metrics
        result["device"] = ctx.device
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    found = common.find_cell(bench, args.workload)
    # the compile cache sits at a fixed path inside the checkout; the
    # program's use_compile_cache() takes it from the environment
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(common.CACHE_DIR)
    common.CACHE_DIR.mkdir(parents=True, exist_ok=True)
    import jax
    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    want = found["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"run.py: needs {want} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    devices = devices[:want]
    peak = common.peak_for(devices[0].device_kind)
    use_compile_cache()
    ctx = Ctx(found, args.seed, args.seconds, bool(args.trace), T_START)
    ctx.devices = devices
    result = run_cell(found, ctx, peak)
    common.emit(result, ctx.notes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
