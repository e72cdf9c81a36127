"""Fig. 7 reproduction: runtime and peak memory of FireBridge verification
vs FPGA prototyping for HLS4ML-style cascaded dense networks of growing
width, until the design no longer fits the ZCU102.

Measured side: wall time + tracemalloc peak of a full bridge verification
(oracle vs interpret backends) of an N-wide 4-layer 16-bit-quantized dense
cascade.  FPGA side modeled from the paper (Vivado HLS+synth minutes and
EDA peak memory), labeled accordingly.
"""
from __future__ import annotations

import time
import tracemalloc

import jax.numpy as jnp
import numpy as np

from repro.core import coverify
from repro.kernels.systolic_matmul import ref as mm_ref
from repro.kernels.systolic_matmul.kernel import matmul as mm_kernel

WIDTHS = [32, 64, 128, 256, 512]
ZCU102_DSP = 2520
# paper-modeled Vivado flow: minutes and GB vs width (fails past the DSPs)
FPGA_MIN = {32: 22, 64: 31, 128: 55, 256: 96, 512: None}
FPGA_GB = {32: 6.5, 64: 8.0, 128: 11.0, 256: 18.0, 512: None}


def verify_cascade(width: int) -> tuple[float, float]:
    rng = np.random.default_rng(width)
    layers = 4
    x = rng.normal(size=(8, width)).astype(np.float32)
    ws = [rng.normal(size=(width, width)).astype(np.float32) / np.sqrt(width)
          for _ in range(layers)]

    def quant16(v):     # hls4ml ap_fixed<16,6>-style quantization
        return np.round(v * 1024) / 1024

    def firmware(fb, backend):
        fb.mem.alloc("x", x.shape, np.float32)
        fb.mem.host_write("x", x)
        cur = "x"
        for i, w in enumerate(ws):
            fb.mem.alloc(f"w{i}", w.shape, np.float32)
            fb.mem.host_write(f"w{i}", quant16(w))
            fb.mem.alloc(f"y{i}", x.shape, np.float32)
            fb.launch("dense", backend, [cur, f"w{i}"], [f"y{i}"])
            cur = f"y{i}"

    tile = min(32, width)
    ops = {"dense": dict(
        oracle=lambda a, w: np.maximum(np.asarray(
            mm_ref.matmul_ref(jnp.asarray(a), jnp.asarray(w))), 0.0),
        interpret=lambda a, w: np.maximum(np.asarray(mm_kernel(
            jnp.asarray(np.pad(a, ((0, (-a.shape[0]) % tile), (0, 0)))),
            jnp.asarray(w), bm=tile, bn=tile, bk=tile,
            interpret=True))[:a.shape[0]], 0.0),
    )}
    tracemalloc.start()
    t0 = time.perf_counter()
    res = coverify(firmware, ops, backends=("oracle", "interpret"), tol=1e-3)
    dt = time.perf_counter() - t0
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert res.passed
    return dt, peak / 1e9


def run() -> list[str]:
    rows = ["case,width,dsp_estimate,fits_zcu102,firebridge_s,"
            "firebridge_peak_gb,fpga_s(modeled),fpga_peak_gb(modeled)"]
    for w in WIDTHS:
        dsp = w * 4          # ~1 DSP per MAC column per layer (16-bit)
        fits = dsp <= ZCU102_DSP
        dt, peak = verify_cascade(w)
        fpga_s = FPGA_MIN[w] * 60 if FPGA_MIN[w] else "DNF"
        fpga_g = FPGA_GB[w] if FPGA_GB[w] else "DNF"
        rows.append(f"fig7,{w},{dsp},{fits},{dt:.2f},{peak:.3f},"
                    f"{fpga_s},{fpga_g}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("\n".join(run()))
