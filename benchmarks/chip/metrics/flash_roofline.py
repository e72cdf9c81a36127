"""Roofline share of the compiled tier's flash-attention forward: causal
2·B·H·S²·D FLOPs and q + k + v + o bytes over the device time of its
Pallas custom call (the op that writes the bf16 B x H x S x D output).
Moves ``sweep_s``."""
from bench.readers import flash_fb, kernel_roofline


def read(run):
    fl = run["info"]["flash"]
    return kernel_roofline(
        run, f"bf16[{fl['B']},{fl['H']},{fl['S']},{fl['D']}]",
        flash_fb(run["info"]))
