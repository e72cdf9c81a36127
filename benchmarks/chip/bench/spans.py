"""The program's own host spans in a profiler trace, and what they add up to.

The co-verification layers open ``fb.*`` spans (``repro.core.spans``):
``fb.sweep`` around each ``CoVerifySession.run``, with ``fb.sweep.cells``,
``fb.sweep.precheck``, ``fb.sweep.compare`` and ``fb.sweep.bisect`` on the
caller's thread, and on each pool thread an ``fb.cell`` holding
``fb.firmware`` (allocs, host writes, launches, their bursts, the link
model and the backend call) and ``fb.cell.collect``.  ``load`` keeps these
and the benchmark's ``bench.*`` spans, each with its thread and its
arguments; everything after it is interval arithmetic on the profiler's
clock, checked on hand-made spans in tests/test_bench_spans.py.

Thread-ms sums a span's durations over every thread; wall-ms is the union
of its intervals; self-ms is a span's duration less what the spans nested
in it on the same thread cover.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.trace import Interval, WINDOW_SPAN, union_ns

PREFIXES = ("fb.", "bench.")


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int
    thread: Tuple[str, int] = ("", 0)     # (host plane, line index)
    args: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> int:
        return self.end - self.start

    def within(self, iv: Interval) -> bool:
        return iv[0] <= self.start and self.end <= iv[1]


def load(path: str) -> List[Span]:
    """The ``fb.*`` and ``bench.*`` host spans of one ``.xplane.pb``,
    ordered by start."""
    from jax.profiler import ProfileData

    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = int(ev.start_ns)
                    out.append(Span(ev.name, s, s + int(ev.duration_ns),
                                    (plane.name, i), dict(ev.stats)))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def window(spans: Sequence[Span]) -> Optional[Interval]:
    w = [s for s in spans if s.name == WINDOW_SPAN]
    return (w[0].start, w[0].end) if len(w) == 1 else None


def sweeps(spans: Sequence[Span]) -> List[Span]:
    """The ``fb.sweep`` spans inside the window (all of them where the
    trace has no ``bench.window``)."""
    w = window(spans)
    return [s for s in spans if s.name == "fb.sweep"
            and (w is None or s.within(w))]


def of_sweep(spans: Sequence[Span], sweep: Span) -> List[Span]:
    """The ``fb.*`` spans inside one sweep's interval, on any thread."""
    iv = (sweep.start, sweep.end)
    return [s for s in spans if s.name.startswith("fb.") and s.within(iv)]


def children(spans: Sequence[Span]) -> Dict[int, List[int]]:
    """Index of each span -> indices of the spans directly nested in it on
    the same thread."""
    kids: Dict[int, List[int]] = defaultdict(list)
    per_thread: Dict[Tuple[str, int], List[int]] = defaultdict(list)
    for i, s in enumerate(spans):
        per_thread[s.thread].append(i)
    for idx in per_thread.values():
        stack: List[int] = []
        for i in sorted(idx, key=lambda i: (spans[i].start, -spans[i].end)):
            while stack and spans[stack[-1]].end <= spans[i].start:
                stack.pop()
            if stack:
                kids[stack[-1]].append(i)
            stack.append(i)
    return kids


def self_ns(spans: Sequence[Span]) -> List[int]:
    kids = children(spans)
    return [s.dur - union_ns([(spans[k].start, spans[k].end)
                              for k in kids.get(i, [])], s.start, s.end)
            for i, s in enumerate(spans)]


def leaf_cover(spans: Sequence[Span], outer: Span) -> float:
    """Share of ``outer`` that spans with no span nested in them cover, on
    its thread."""
    kids = children(spans)
    leaves = [(s.start, s.end) for i, s in enumerate(spans)
              if s.thread == outer.thread and not kids.get(i)
              and s.within((outer.start, outer.end)) and s is not outer]
    return union_ns(leaves, outer.start, outer.end) / max(outer.dur, 1)


def cover(inner: Iterable[Span], outer: Span) -> float:
    """Share of ``outer`` that the ``inner`` spans cover (any thread)."""
    return union_ns([(s.start, s.end) for s in inner],
                    outer.start, outer.end) / max(outer.dur, 1)


def thread_ns(spans: Iterable[Span], *names: str) -> int:
    return sum(s.dur for s in spans if s.name in names)


def arg_sum(spans: Iterable[Span], name: str, key: str) -> int:
    return sum(int(s.args.get(key, 0)) for s in spans if s.name == name)


def idle_while_open(spans: Sequence[Span], name: str,
                    gaps: Sequence[Interval]) -> int:
    """Nanoseconds of the device's idle ``gaps`` during which a span named
    ``name`` is open on some thread."""
    opened = [(s.start, s.end) for s in spans if s.name == name]
    return sum(union_ns(opened, a, b) for a, b in gaps)


# ------------------------------------------------ per-sweep quantities
def per_sweep(spans: Sequence[Span]) -> Dict[str, Optional[float]]:
    """What the program's spans say of the window's sweeps, each None where
    the trace holds none of the spans it reads:

    * ``compare_ms_per_sweep``: wall ms of ``fb.sweep.precheck`` and
      ``fb.sweep.compare`` (the caller's thread);
    * ``collect_ms_per_sweep``: thread-ms of ``fb.cell.collect``;
    * ``host_write_ms_per_sweep``: thread-ms of ``fb.mem.alloc`` and
      ``fb.mem.host_write``;
    * ``link_us_per_burst``: thread-us of ``fb.link`` over its ``bursts``;
    * ``cell_concurrency``: thread-ms of ``fb.cell`` over the wall ms of
      ``fb.sweep.cells`` (4 would be four cells overlapping throughout).
    """
    sw = sweeps(spans)
    inside = [s for sweep in sw for s in of_sweep(spans, sweep)]
    n = len(sw)

    def ms(*names: str) -> Optional[float]:
        t = thread_ns(inside, *names)
        return t / 1e6 / n if n and t else None

    bursts = arg_sum(inside, "fb.link", "bursts")
    link = thread_ns(inside, "fb.link")
    cells, pool = thread_ns(inside, "fb.cell"), thread_ns(inside,
                                                          "fb.sweep.cells")
    return {
        "compare_ms_per_sweep": ms("fb.sweep.precheck", "fb.sweep.compare"),
        "collect_ms_per_sweep": ms("fb.cell.collect"),
        "host_write_ms_per_sweep": ms("fb.mem.alloc", "fb.mem.host_write"),
        "link_us_per_burst": link / 1e3 / bursts if bursts else None,
        "cell_concurrency": cells / pool if cells and pool else None,
    }
