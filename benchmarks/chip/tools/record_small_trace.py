"""Record a small TPU trace of known shape, for checking ``bench/trace.py``
against what a chip writes.

    python3 benchmarks/chip/tools/record_small_trace.py <out dir>

Inside one ``bench.window`` span: a jitted matmul, a 50 ms host sleep in a
``bench.sleep`` span (an idle gap of known length), and the matmul again.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main(out: str) -> None:
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with TraceAnnotation("bench.window"):
        f(x).block_until_ready()
        with TraceAnnotation("bench.sleep"):
            time.sleep(0.05)
        f(x).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
