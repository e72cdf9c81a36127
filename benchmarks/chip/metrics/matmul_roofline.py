"""Roofline share of the compiled tier's systolic matmul: 2·M·N·K FLOPs
and A + B + C bytes over the device time of its Pallas custom call (the
op that writes the bf16 M x N output).  Moves ``sweep_s``."""
from bench.readers import kernel_roofline, matmul_fb


def read(run):
    mm = run["info"]["matmul"]
    return kernel_roofline(run, f"bf16[{mm['M']},{mm['N']}]",
                           matmul_fb(run["info"]))
