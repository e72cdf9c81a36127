"""Roofline share of the stand-in axpy kernel: 2·R·C FLOPs and x + y + z
bytes over the device time of its Pallas custom call (the op that writes
the bf16 R x C output).  Moves ``sweep_s``."""
from bench.readers import kernel_roofline


def read(run):
    info = run["info"]
    r, c = info["axpy"]["R"], info["axpy"]["C"]
    return kernel_roofline(run, f"bf16[{r},{c}]",
                           (2.0 * r * c, 3.0 * r * c * info["itemsize"]))
