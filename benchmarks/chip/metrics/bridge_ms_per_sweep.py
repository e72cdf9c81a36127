"""Host milliseconds per sweep in the bridge and its modeled time
(core/bridge.py, core/congestion.py): the benchmark's ``launch`` spans
around ``FireBridge.launch`` less the ``backend`` spans around the
registered backend callables inside them.  Moves ``sweep_s``."""
from bench.readers import span_ms_per_sweep


def read(run):
    launch = span_ms_per_sweep(run, "launch")
    backend = span_ms_per_sweep(run, "backend")
    if launch is None or backend is None:
        return None
    return launch - backend
