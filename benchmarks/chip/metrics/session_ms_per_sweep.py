"""Host milliseconds per sweep in the co-verification session outside the
launches (core/scheduler.py ``CoVerifySession.run``): the firmware's
buffer allocation and input writes, each cell's copy of its DDR state,
the comparison of every buffer across backends, and the report.  The
benchmark's ``sweep`` spans less the ``launch`` spans inside them.  Moves
``sweep_s``."""
from bench.readers import span_ms_per_sweep


def read(run):
    sweep = span_ms_per_sweep(run, "sweep")
    launch = span_ms_per_sweep(run, "launch")
    if sweep is None or launch is None:
        return None
    return sweep - launch
