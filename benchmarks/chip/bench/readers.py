"""Helpers the per-layer metric readers in ``metrics/`` share.

A reader gets one dict: ``trace`` (bench.trace.Trace of the traced
window), ``info`` (what the driver counted in that window), ``peak`` (the
device's row of peaks.json), ``config`` and ``traffic``.  It returns a
number, or None where the trace holds nothing for it to read; it never
returns 0 for a share of a roofline or of a peak.
"""
from __future__ import annotations

from typing import Optional

from bench import flops


def idle_pct(run) -> Optional[float]:
    share = run["trace"].idle_share()
    return None if share is None else 100.0 * share


def is_kernel(e, out_shape: str) -> bool:
    """A Pallas kernel's op: a custom call whose text (name and string
    stats, the HLO instruction among them) holds its output shape, such as
    ``bf16[2048,14336]``.  Both compiled kernels are ``jit(<lambda>)``
    custom calls today, so the shape is what tells them apart."""
    return (e.has("custom-call") or e.has("custom_call")) and e.has(out_shape)


def kernel_roofline(run, out_shape: str, flops_bytes) -> Optional[float]:
    """Roofline share of the kernel whose custom call writes ``out_shape``
    (one launch per sweep), from its summed device time."""
    secs, n = run["trace"].op_time(lambda e: is_kernel(e, out_shape))
    sweeps = run["info"]["traced_sweeps"]
    if not n or not secs:
        return None
    f, b = flops_bytes
    share, _ = flops.roofline_share(f * sweeps, b * sweeps, secs, run["peak"])
    return share


def matmul_fb(info):
    mm = info["matmul"]
    return flops.matmul(mm["M"], mm["N"], mm["K"], info["itemsize"])


def flash_fb(info):
    fl = info["flash"]
    return flops.flash_causal(fl["B"], fl["H"], fl["KH"], fl["S"], fl["D"],
                              info["itemsize"])


def span_ms_per_sweep(run, name: str) -> Optional[float]:
    total, _ = run["info"]["traced_spans"].get(name, (0.0, 0))
    sweeps = run["info"]["traced_sweeps"]
    return 1e3 * total / sweeps if sweeps and total else None
