"""The whole sweep's share of the chip's bf16 peak: the compiled tier's
kernel FLOPs (matmul and flash forward) of the sweeps in the traced
window, over that window's seconds.  Moves ``sweep_s``."""
from bench.readers import flash_fb, matmul_fb


def read(run):
    info, t = run["info"], run["trace"]
    sweeps = info["traced_sweeps"]
    w = t.window_s()
    if not sweeps or w <= 0:
        return None
    work = (matmul_fb(info)[0] + flash_fb(info)[0]) * sweeps
    return 100.0 * work / w / run["peak"]["bf16_flops_per_s"]
