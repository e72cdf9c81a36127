"""Plain reference for the stand-in elementwise kernel, z = alpha·x + y,
in float32 from the mathematics alone, importing nothing of the program.
``cast`` rounds the inputs to a lower type first (the control).
``rel_err`` is the number compared, as in ``kernels_ref.py``: the largest
error of an element relative to |reference| + rms/16."""
from __future__ import annotations

import numpy as np

FLOOR = 1.0 / 16


def _f32(x, cast):
    x = np.asarray(x)
    if cast is not None:
        x = x.astype(np.float32).astype(cast)
    return x.astype(np.float32)


def axpy(x, y, alpha, cast=None):
    return alpha * _f32(x, cast) + _f32(y, cast)


def rel_err(out, ref) -> float:
    ref = np.asarray(ref, np.float32)
    rms = np.sqrt(np.mean(ref * ref))
    err = np.abs(np.asarray(out).astype(np.float32) - ref)
    return float(np.max(err / (np.abs(ref) + FLOOR * rms)))
