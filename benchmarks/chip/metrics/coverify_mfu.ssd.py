"""The whole sweep's share of the chip's bf16 peak in the SSD cell: the
compiled tier's SSD FLOPs (bench/ssd_flops.py) of the sweeps in the traced
window, over that window's seconds.  Moves ``sweep_s``."""
from bench import ssd_flops


def read(run):
    info, t = run["info"], run["trace"]
    ssd = info.get("ssd")
    sweeps = info.get("traced_sweeps")
    w = t.window_s()
    if not ssd or not sweeps or w <= 0:
        return None
    f, _ = ssd_flops.ssd(ssd["B"], ssd["L"], ssd["H"], ssd["P"], ssd["G"],
                         ssd["N"], ssd["chunk"], info["itemsize"])
    return 100.0 * f * sweeps / w / run["peak"]["bf16_flops_per_s"]
