"""Reductions from a window's records to end-to-end metrics, and the
spread the bounds in BENCHMARK.json are set from.

A time per sweep is taken over all the sweeps and all the time of the
window.
"""
from __future__ import annotations

import statistics
from typing import Sequence


def sweep_s(window_s: float, sweeps: int) -> float:
    """Seconds per co-verification sweep: the window runs whole sweeps back
    to back and ends with the last one, so every second of it belongs to a
    counted sweep."""
    if sweeps <= 0:
        raise ValueError("no sweep completed in the window")
    return window_s / sweeps


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median (the rule the bounds
    in BENCHMARK.json were set from)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
