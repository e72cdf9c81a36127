"""Reduction of a JAX profiler trace to device busy time, idle gaps and
per-operation device time.

A traced run writes one ``.xplane.pb``.  ``load`` keeps two things from
it, both on the profiler's one clock (nanoseconds):

* the device operations: the events of the op line of every device plane
  (on a TPU, plane ``/device:TPU:<n>``, line ``XLA Ops``), each with the
  name of the program module it ran in;
* the benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
  starting with ``bench.``), one of which, ``bench.window``, marks the
  traced window.

Everything after ``load`` is plain interval arithmetic, checked on small
hand-made traces in tests/test_bench_trace.py.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]                   # [start_ns, end_ns)

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int
    module: str = ""                          # program module (device ops)
    plane: str = ""
    text: str = ""                            # name and string stats

    def has(self, word: str) -> bool:
        return word in self.text

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: List[Event]                          # device operations
    spans: List[Event]                        # benchmark host spans
    planes: List[str]                         # device planes seen

    @property
    def window(self) -> Interval:
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                             f"{len(w)}")
        return (w[0].start, w[0].end)

    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran on the device, averaged over
        the device planes, inside the window."""
        lo, hi = self.window
        per_plane = defaultdict(list)
        for e in self.ops:
            per_plane[e.plane].append((e.start, e.end))
        if not per_plane:
            return 0.0
        return (sum(union_ns(iv, lo, hi) for iv in per_plane.values())
                / len(per_plane) * 1e-9)

    def idle_share(self) -> Optional[float]:
        w = self.window_s()
        if w <= 0 or not self.ops:
            return None
        return 1.0 - self.busy_s() / w

    def in_window(self, events: Iterable[Event]) -> List[Event]:
        lo, hi = self.window
        return [e for e in events if e.start >= lo and e.end <= hi]

    def op_time(self, match) -> Tuple[float, int]:
        """(device seconds, count) of the window's ops whose name or module
        satisfies ``match(event)``."""
        hits = [e for e in self.in_window(self.ops) if match(e)]
        return sum(e.dur for e in hits) * 1e-9, len(hits)

    def breakdown(self, n: int = 10) -> Dict[str, list]:
        """The device operations that took most time, and the longest idle
        gaps named by the benchmark span open around them."""
        lo, hi = self.window
        per_op: Dict[str, int] = defaultdict(int)
        for e in self.in_window(self.ops):
            per_op[e.name] += e.dur
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:n]
        plane = self.planes[0] if self.planes else ""
        gaps = idle_gaps([(e.start, e.end) for e in self.ops
                          if e.plane == plane], lo, hi)
        gaps.sort(key=lambda g: g[0] - g[1])
        named = attribute_gaps(gaps[:n], [s for s in self.spans
                                          if s.name != WINDOW_SPAN])
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[name, (b - a) * 1e-9]
                              for name, a, b in named]}


def union_ns(intervals: Sequence[Interval], lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` clipped to [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Sequence[Interval], lo: int, hi: int
              ) -> List[Interval]:
    """The stretches of [lo, hi) that no interval covers."""
    gaps, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(a, b) for a, b in gaps if b > a]


def attribute_gaps(gaps: Sequence[Interval], spans: Sequence[Event]
                   ) -> List[Tuple[str, int, int]]:
    """Name each gap by the innermost span open at its midpoint (the one
    that started last), or ``(no span)``."""
    out = []
    for a, b in gaps:
        mid = (a + b) // 2
        open_ = [s for s in spans if s.start <= mid < s.end]
        name = (max(open_, key=lambda s: s.start).name if open_
                else "(no span)")
        out.append((name, a, b))
    return out


def _text(ev) -> str:
    return " ".join([ev.name] + [str(v) for _, v in ev.stats
                                 if isinstance(v, str)])


def _stat(ev, key: str):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str, *, device_prefix: str = DEVICE_PLANE_PREFIX,
         op_line: str = OP_LINE, span_prefix: str = SPAN_PREFIX) -> Trace:
    """Read the device ops and benchmark spans of one
    ``.xplane.pb`` (see the module docstring for what is kept)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[Event] = []
    spans: List[Event] = []
    planes: List[str] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            planes.append(plane.name)
            lines = list(plane.lines)
            names = {line.name for line in lines}
            if op_line not in names:
                raise ValueError(f"device plane {plane.name!r} has no "
                                 f"{op_line!r} line (lines: "
                                 f"{sorted(names)})")
            for line in lines:
                if line.name == op_line:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        ops.append(Event(ev.name, s, s + int(ev.duration_ns),
                                         str(_stat(ev, "hlo_module") or ""),
                                         plane.name, _text(ev)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        s = int(ev.start_ns)
                        spans.append(Event(ev.name, s,
                                           s + int(ev.duration_ns)))
    return Trace(ops, spans, sorted(planes))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]
