"""Plain reference for the co-verified Mamba-2 SSD scan.

Written from the mathematics alone, in float32 at the highest matmul
precision, and importing nothing of the program: the state-space
recurrence taken one step at a time over the whole sequence, each head
reading the B and C of its group.  ``cast`` rounds the bfloat16 operands
(x, B, C) to a lower type first (the control that the comparison must
reject); dt, A and D stay float32, as the configuration holds them.

The per-step decays exp(dt_t A_h) are computed on the host in float64 and
rounded once to float32.  A v5e's exp is within about 100 units in the
last place (6e-6), and the recurrence multiplies the state by thousands
of them: computed on the chip, they moved the final state by 0.0036 of
itself over 8192 steps, as far from both chunked tiers as a bfloat16
state would be.

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


def _f32(x, cast=None):
    """``x`` in float32, rounded to ``cast`` first.  The rounding is made
    on the host: inside a jitted program the TPU compiler drops a round
    trip through float8 on a v5e, which then reads as the bfloat16 it
    came from."""
    if cast is not None:
        x = np.asarray(x).astype(np.float32).astype(cast)
    return jnp.asarray(x).astype(jnp.float32)


def ssd(x, dt, B, C, A, D, cast=None):
    """x (b,H,L,P), dt (b,H,L), B/C (b,G,L,N), A/D (H,) -> y (b,H,L,P)
    and the final state (b,H,P,N).  For head h, with g = h // (H // G),
    starting from a zero state s (P x N):

        s_t = exp(dt_t A_h) s_{t-1} + dt_t x_t B_{g,t}^T
        y_t = s_t C_{g,t} + D_h x_t
    """
    def f(x, dt, decay, B, C, D):
        H, G = x.shape[1], B.shape[1]
        group = jnp.arange(H) // (H // G)

        def step(s, inp):
            x_t, dt_t, a_t, B_t, C_t = inp    # (b,H,P) (b,H) (b,H) (b,G,N)
            Bh, Ch = B_t[:, group], C_t[:, group]               # (b,H,N)
            s = (a_t[:, :, None, None] * s
                 + (dt_t[:, :, None] * x_t)[..., None] * Bh[:, :, None, :])
            y_t = jnp.einsum("bhpn,bhn->bhp", s, Ch) + D[:, None] * x_t
            return s, y_t

        s0 = jnp.zeros(x.shape[:2] + (x.shape[3], B.shape[3]), jnp.float32)
        s, ys = jax.lax.scan(step, s0, tuple(
            jnp.moveaxis(v, 2, 0) for v in (x, dt, decay, B, C)))
        return jnp.moveaxis(ys, 0, 2), s
    dt = np.asarray(dt, np.float32)
    decay = np.exp(dt.astype(np.float64)
                   * np.asarray(A, np.float64)[:, None]).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(_f32(x, cast), _f32(dt), jnp.asarray(decay),
                          _f32(B, cast), _f32(C, cast), _f32(D))


def rel_err(out, ref) -> float:
    """max |out - ref| / (|ref| + FLOOR * rms(ref)) over all elements."""
    def f(out, ref):
        ref = ref.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(ref * ref))
        err = jnp.abs(out.astype(jnp.float32) - ref)
        return jnp.max(err / (jnp.abs(ref) + FLOOR * rms))
    return float(jax.jit(f)(jnp.asarray(out), ref))
