"""Named host spans on the profiler's clock.

``span(name, **counts)`` marks one stretch of host work.  Inside a
``jax.profiler`` trace it is a TraceMe event on the host plane, on the
same clock as the device ops, with ``counts`` as its arguments; so a
stretch in which the device waits can be put down to the host work open
on some thread meanwhile.  Outside a trace it records nothing and costs
about a microsecond.  Either way it times itself: ``.seconds`` is its
duration once it has closed.

Spans mark transfers, launches and phases, never single bursts; a burst
count travels as an argument (``bursts=``).  The co-verification layers
open them under ``fb.*`` names (README, "Host time of a sweep").
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class span:
    """``with span("fb.link", bursts=n) as s: ...``; then ``s.seconds``.
    ``s.set(**counts)`` adds arguments known only inside the span."""

    __slots__ = ("_ann", "_t0", "seconds")

    def __init__(self, name: str, **counts) -> None:
        self._ann = TraceAnnotation(name, **counts)
        self.seconds = 0.0

    def set(self, **counts) -> None:
        self._ann.set_metadata(**counts)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        return False
