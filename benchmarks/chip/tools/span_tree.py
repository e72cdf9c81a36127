"""Put a traced run's device idle time down to the program's host work.

    python3 benchmarks/chip/tools/span_tree.py <trace dir or .xplane.pb>

For each traced sweep (each ``fb.sweep`` span inside ``bench.window``) and
each span name, prints: count, wall ms (union of its intervals), thread-ms
(summed over threads), self ms (less what spans nested in it on the same
thread cover), its summed counts, and the ms of device idle during which a
span of that name is open on some thread (overlaps count toward each
name).  Then how far the sweep's phases, the sweep, and the leaves of the
longest cell cover their parents, the per-sweep quantities of
``bench.spans.per_sweep`` over the window, and the window's longest device
idle gaps named by the innermost program span open at their midpoint.
"""
from __future__ import annotations

import os
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import spans as sp                                # noqa: E402
from bench import trace as tr                                # noqa: E402

PHASES = ("fb.sweep.cells", "fb.sweep.precheck", "fb.sweep.compare")


def device_gaps(path: str, lo: int, hi: int):
    """The idle stretches of [lo, hi) on the first device plane (all of
    it where the trace has no device)."""
    t = tr.load(path)
    plane = t.planes[0] if t.planes else None
    return tr.idle_gaps([(e.start, e.end) for e in t.ops
                         if e.plane == plane], lo, hi)


def sweep_table(spans, sweep, gaps) -> list:
    inside = sp.of_sweep(spans, sweep)
    own = sp.self_ns(inside)
    rows = defaultdict(lambda: {"n": 0, "iv": [], "thread": 0, "self": 0,
                                "counts": defaultdict(int)})
    for s, self_ns in zip(inside, own):
        r = rows[s.name]
        r["n"] += 1
        r["iv"].append((s.start, s.end))
        r["thread"] += s.dur
        r["self"] += self_ns
        for k, v in s.args.items():
            if isinstance(v, int) and k != "sweep":
                r["counts"][k] += v
    lines = [f"  {'span':20s} {'count':>5s} {'wall_ms':>10s} "
             f"{'thread_ms':>10s} {'self_ms':>10s} {'idle_ms':>10s}  counts"]
    for name in sorted(rows, key=lambda n: -rows[n]["thread"]):
        r = rows[name]
        wall = tr.union_ns(r["iv"], sweep.start, sweep.end)
        idle = sp.idle_while_open(inside, name, gaps)
        counts = " ".join(f"{k}={v}" for k, v in sorted(r["counts"].items()))
        lines.append(f"  {name:20s} {r['n']:5d} {wall / 1e6:10.2f} "
                     f"{r['thread'] / 1e6:10.2f} {r['self'] / 1e6:10.2f} "
                     f"{idle / 1e6:10.2f}  {counts}")
    return lines


def main(path: str) -> None:
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    spans = sp.load(path)
    sweeps = sp.sweeps(spans)
    if not sweeps:
        sys.exit(f"no fb.sweep span in {path}")
    w = sp.window(spans) or (sweeps[0].start, sweeps[-1].end)
    gaps = device_gaps(path, *w)
    print(f"trace {path}: window {(w[1] - w[0]) / 1e6:.1f} ms, "
          f"{len(sweeps)} sweep(s), device idle "
          f"{sum(b - a for a, b in gaps) / 1e6:.1f} ms")
    bench_sweeps = [s for s in spans if s.name == "bench.sweep"]
    for k, sweep in enumerate(sweeps, 1):
        inside = sp.of_sweep(spans, sweep)
        phases = sp.cover([s for s in inside if s.name in PHASES], sweep)
        cells = [s for s in inside if s.name == "fb.cell"]
        print(f"\nsweep {k} (run {sweep.args.get('sweep', '?')}): fb.sweep "
              f"{sweep.dur / 1e6:.1f} ms; cells+precheck+compare cover "
              f"{100 * phases:.2f}% of it")
        outer = [b for b in bench_sweeps
                 if b.start <= sweep.start and sweep.end <= b.end]
        if outer:
            print(f"  fb.sweep covers {100 * sp.cover([sweep], outer[0]):.2f}"
                  f"% of bench.sweep ({outer[0].dur / 1e6:.1f} ms)")
        if cells:
            longest = max(cells, key=lambda s: s.dur)
            print(f"  leaves cover {100 * sp.leaf_cover(inside, longest):.2f}"
                  f"% of the longest fb.cell ({longest.args.get('cell')}, "
                  f"{longest.dur / 1e6:.1f} ms)")
        print("\n".join(sweep_table(spans, sweep, gaps)))
    print("\nper sweep over the window:")
    for name, v in sp.per_sweep(spans).items():
        print(f"  {name:24s} {v if v is None else f'{v:.6g}'}")
    print("\nlongest device idle gaps, named by the innermost program span"
          " open at their midpoint:")
    gaps.sort(key=lambda g: g[0] - g[1])
    named = tr.attribute_gaps(gaps[:10], [s for s in spans
                                          if s.name.startswith("fb.")])
    for name, a, b in named:
        print(f"  {(b - a) / 1e6:10.2f} ms  {name}")


if __name__ == "__main__":
    main(sys.argv[1])
