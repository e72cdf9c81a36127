"""Share of the traced window in which no operation ran on the device
(1 - union of the device-op intervals / window)."""
from bench.readers import idle_pct


def read(run):
    return idle_pct(run)
