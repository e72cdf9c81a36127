"""Host milliseconds per sweep inside the registered backend callables of
``*_chip_backends`` (kernels/*/sweep.py): host-device copies, the
kernels and the oracle, and ``np.asarray`` of the results.  Moves
``sweep_s``."""
from bench.readers import span_ms_per_sweep


def read(run):
    return span_ms_per_sweep(run, "backend")
