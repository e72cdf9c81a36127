"""What every cell shares: finding files by name, the table of peaks, host
spans, the compile counter and the result line.

Nothing here touches a device or loads a backend when it is imported.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
import threading
import time
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                              # the checkout
OUT_DIR = BENCH_DIR / "out"                              # gitignored
CACHE_DIR = BENCH_DIR / "out" / "jax_cache"              # fixed path


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file by its path (file names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str) -> Dict[str, Any]:
    """Everything one cell needs, found by the names in BENCHMARK.json: the
    workload entry, its configuration file, its traffic file, and the
    metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def peak_for(device_kind: str, table: Optional[dict] = None) -> dict:
    """The peaks of one device kind; a kind not in peaks.json is an error,
    never a default."""
    table = table if table is not None else load_json(BENCH_DIR /
                                                      "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table)}); add its published peaks first")
    return table[device_kind]


class Spans:
    """Host spans from the benchmark's own files: each is timed on the host
    clock (thread-safe totals) and written into the profiler's trace as a
    ``TraceAnnotation`` named ``bench.<name>``, so idle gaps on the device
    can be named by the span open around them."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.total: Dict[str, float] = defaultdict(float)
        self.count: Dict[str, int] = defaultdict(int)

    @contextlib.contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        with TraceAnnotation("bench." + name):
            yield
        dt = time.perf_counter() - t0
        with self._lock:
            self.total[name] += dt
            self.count[name] += 1

    def snapshot(self) -> Dict[str, list]:
        with self._lock:
            return {k: [self.total[k], self.count[k]] for k in self.total}

    def reset(self) -> None:
        with self._lock:
            self.total.clear()
            self.count.clear()


class CompileCounter:
    """Counts programs made ready (compiled, or read from the persistent
    cache) while ``active``; a warmed-up window should count none."""

    def __init__(self) -> None:
        self.count = 0
        self.active = False
        import jax.monitoring as mon

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if self.active and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        def on_event(event: str, **_kw) -> None:
            if self.active and event == "/jax/compilation_cache/cache_hits":
                self.count += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


def device_info(devices) -> Dict[str, Any]:
    """Platform, kind, count, and the peak bytes in use on the fullest
    chip, as JAX reports them."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def checks_ok(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def emit(result: Dict[str, Any], notes: List[str]) -> None:
    """Print the run's notes on standard error, the compared numbers as the
    last lines there, and the result as the last line of standard
    output."""
    for n in notes:
        print(n, file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
