"""Roofline share of the compiled tier's SSD scan: the chunked scan's
FLOPs and x + dt + B + C + y + state bytes (bench/ssd_flops.py) over the
device time of its Pallas custom call, the op the program names
``ssd_scan``.  Moves ``sweep_s``."""
from bench import flops, ssd_flops


def _is_scan(e):
    return (e.has("custom-call") or e.has("custom_call")) and \
        e.has("ssd_scan")


def read(run):
    info = run["info"]
    ssd = info.get("ssd")
    sweeps = info.get("traced_sweeps")
    secs, n = run["trace"].op_time(_is_scan)
    if not ssd or not sweeps or not n or not secs:
        return None
    f, b = ssd_flops.ssd(ssd["B"], ssd["L"], ssd["H"], ssd["P"], ssd["G"],
                         ssd["N"], ssd["chunk"], info["itemsize"])
    share, _ = flops.roofline_share(f * sweeps, b * sweeps, secs, run["peak"])
    return share
