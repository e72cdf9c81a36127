"""Plain reference for the co-verified kernels of a transformer layer.

Written from the mathematics alone, in float32 at the highest matmul
precision, and importing nothing of the program: a matmul, and causal
grouped-query attention with a softmax over each query's keys.  ``cast``
rounds the inputs to a lower type first (the control that the comparison
must reject).

``rel_err`` is the number compared: the largest error of any output
element relative to that element's reference value, with a floor of a
sixteenth of the reference's root mean square so that elements near zero
do not dominate.  An output rounded correctly to bfloat16 reads at most
2**-8 (half a unit in the last place).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FLOOR = 1.0 / 16


def _f32(x, cast):
    """``x`` in float32, rounded to ``cast`` first.  The rounding is made
    on the host: inside a jitted program the TPU compiler drops a round
    trip through float8 on a v5e, which then reads as the bfloat16 it
    came from."""
    if cast is not None:
        x = np.asarray(x).astype(np.float32).astype(cast)
    return jnp.asarray(x).astype(jnp.float32)


def matmul(a, b, cast=None):
    """C = A @ B in float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda a, b: jnp.dot(
            a, b, preferred_element_type=jnp.float32))(
                _f32(a, cast), _f32(b, cast))


def causal_attention(q, k, v, cast=None):
    """q (B,H,S,D), k/v (B,KH,S,D) -> o (B,H,S,D); query head h reads key
    head h // (H // KH); query i sees keys 0..i."""
    def f(q, k, v):
        B, H, S, D = q.shape
        KH = k.shape[1]
        g = H // KH
        kk = jnp.repeat(k, g, axis=1)
        vv = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / jnp.sqrt(jnp.float32(D))
        keep = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(keep, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv)
    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(_f32(q, cast), _f32(k, cast), _f32(v, cast))


def rel_err(out, ref) -> float:
    """max |out - ref| / (|ref| + FLOOR * rms(ref)) over all elements."""
    def f(out, ref):
        ref = ref.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(ref * ref))
        err = jnp.abs(out.astype(jnp.float32) - ref)
        return jnp.max(err / (jnp.abs(ref) + FLOOR * rms))
    return float(jax.jit(f)(jnp.asarray(out), ref))
