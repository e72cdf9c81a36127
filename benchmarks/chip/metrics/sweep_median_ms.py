"""Median host milliseconds of one sweep over every sweep of the window,
traced and untraced.  A sweep in which the chip's host stood still for
seconds moves ``sweep_s`` (all the work over all the time) but not this
median, so it shows a change of a few percent that ``sweep_s``'s bound
cannot.  Moves ``sweep_s``."""
import statistics


def read(run):
    took = run["info"].get("sweep_seconds") or []
    return 1e3 * statistics.median(took) if took else None
