"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV summary lines (plus each figure's
detailed CSV) and writes artifacts under benchmarks/artifacts/.

    PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

ART = Path(__file__).resolve().parent / "artifacts"


def _run(name: str, fn) -> list[str]:
    t0 = time.perf_counter()
    rows = fn()
    us = (time.perf_counter() - t0) * 1e6
    ART.mkdir(parents=True, exist_ok=True)
    (ART / f"{name}.csv").write_text("\n".join(rows))
    derived = rows[-1].replace(",", ";") if rows else ""
    print(f"{name},{us:.0f},{derived}")
    for r in rows:
        print(f"  {r}")
    return rows


def main() -> None:
    from benchmarks import (bench_access_patterns, bench_bandwidth_profile,
                            bench_counters, bench_debug_iteration,
                            bench_fabric_scaling, bench_fuzz,
                            bench_hls4ml_scaling, bench_profiler,
                            bench_replay, bench_runfarm, bench_serving,
                            bench_simspeed)
    from benchmarks import roofline as roofline_mod

    print("name,us_per_call,derived")
    _run("fig5_debug_iteration", bench_debug_iteration.run)
    _run("fig5_batched_sweep", bench_debug_iteration.run_sweep)
    _run("fig7_hls4ml_scaling", bench_hls4ml_scaling.run)
    _run("fig8_bandwidth_profile", bench_bandwidth_profile.run)
    _run("fig9_access_patterns", bench_access_patterns.run)
    _run("fuzz_throughput", bench_fuzz.run)         # quick mode
    _run("fabric_scaling", bench_fabric_scaling.run)  # quick mode
    _run("replay_debug_iteration", bench_replay.run)  # quick mode
    _run("profiler_overhead", bench_profiler.run)   # quick mode
    _run("counters_overhead", bench_counters.run)   # quick mode
    _run("simspeed", bench_simspeed.run)            # quick mode
    _run("runfarm_scaling", bench_runfarm.run)      # quick mode
    _run("serving_slo", bench_serving.run)          # quick mode

    def _roofline():
        recs = roofline_mod.load("baseline")
        (ART / "dryrun_table.md").write_text(
            roofline_mod.render_dryrun_table(recs))
        (ART / "roofline_table.md").write_text(
            roofline_mod.render_roofline_table(recs))
        return [f"roofline,baseline_cells,{len(recs)}",
                "roofline,tables,dryrun_table.md;roofline_table.md"]

    _run("roofline_tables", _roofline)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
