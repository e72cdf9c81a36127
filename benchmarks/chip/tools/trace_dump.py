"""Print what a profiler trace holds, to look at one by hand before
writing a reader against it.

    python3 benchmarks/chip/tools/trace_dump.py <trace dir or .xplane.pb>

Lists every plane and line with its event count, then for each device
plane's lines the events that took most time, with their stats.
"""
from __future__ import annotations

import os
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(path: str) -> None:
    from jax.profiler import ProfileData

    from bench.trace import find_xplane

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            if not plane.name.startswith("/device") or not evs:
                continue
            tot, sample = defaultdict(int), {}
            for e in evs:
                tot[e.name] += int(e.duration_ns)
                sample.setdefault(e.name, e)
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:12]:
                e = sample[name]
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in e.stats}
                print(f"    {ns / 1e6:10.3f} ms  {name[:100]!r}  {stats}")


if __name__ == "__main__":
    main(sys.argv[1])
