"""Oracles for the SSD scan kernel, in its head-major grouped layout:
x (B,H,L,P), dt (B,H,L), B_/C_ (B,G,L,N), A/D (H,); head h reads group
h // (H // G).  Both return y and the final state in float32.

``ssd_scan_ref`` is the per-timestep recurrence (exact, slow): the oracle
of the CPU tests.  ``ssd_chunked_ref`` is the kernel's lax twin, the
chunked SSD in plain ``jnp`` with the state carried from chunk to chunk:
the golden model that fits a whole 8192-token context on a chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _grouped(x, dt, B_, C_):
    """float32 views with the heads split as (G, H // G)."""
    Bsz, H, L, P = x.shape
    G = B_.shape[1]
    f = jnp.float32
    return (x.astype(f).reshape(Bsz, G, H // G, L, P),
            dt.astype(f).reshape(Bsz, G, H // G, L),
            B_.astype(f), C_.astype(f))


def ssd_scan_ref(x, dt, B_, C_, A, D):
    """state_t = state * exp(dt_t A) + dt_t * x_t outer B_t;
    y_t = C_t . state_t + D * x_t, head by head with its group's B/C."""
    Bsz, H, L, P = x.shape
    G, N = B_.shape[1], B_.shape[3]
    xg, dtg, Bf, Cf = _grouped(x, dt, B_, C_)
    Ag = A.astype(jnp.float32).reshape(G, H // G)

    def step(state, t):                       # state (B,G,R,P,N)
        xt, dtt = xg[:, :, :, t], dtg[:, :, :, t]
        bt, ct = Bf[:, :, t], Cf[:, :, t]
        decay = jnp.exp(dtt * Ag)                               # (B,G,R)
        state = state * decay[..., None, None] + jnp.einsum(
            "bgn,bgrp->bgrpn", bt, xt * dtt[..., None])
        return state, jnp.einsum("bgn,bgrpn->bgrp", ct, state)

    state0 = jnp.zeros((Bsz, G, H // G, P, N), jnp.float32)
    state, ys = jax.lax.scan(step, state0, jnp.arange(L))
    y = ys.transpose(1, 2, 3, 0, 4).reshape(Bsz, H, L, P)
    y = y + D.astype(jnp.float32)[None, :, None, None] * x.astype(jnp.float32)
    return y, state.reshape(Bsz, H, P, N)


def ssd_chunked_ref(x, dt, B_, C_, A, D, *, chunk: int = 128):
    """The chunked SSD: within a chunk of ``chunk`` steps, y is the causal
    (C B^T ∘ decay ∘ dt) matrix applied to x plus the incoming state read
    by C; the state then decays over the chunk and takes the chunk's
    dt-weighted x outer B."""
    Bsz, H, L, P = x.shape
    G, N = B_.shape[1], B_.shape[3]
    R = H // G
    cl = min(chunk, L)
    nc = L // cl
    xg, dtg, Bf, Cf = _grouped(x, dt, B_, C_)
    # chunk-major, for the scan
    xs = xg.reshape(Bsz, G, R, nc, cl, P).transpose(3, 0, 1, 2, 4, 5)
    dts = dtg.reshape(Bsz, G, R, nc, cl).transpose(3, 0, 1, 2, 4)
    Bs = Bf.reshape(Bsz, G, nc, cl, N).transpose(2, 0, 1, 3, 4)
    Cs = Cf.reshape(Bsz, G, nc, cl, N).transpose(2, 0, 1, 3, 4)
    Ag = A.astype(jnp.float32).reshape(G, R)
    causal = jnp.tril(jnp.ones((cl, cl), bool))

    def chunk_step(state, inp):               # state (B,G,R,P,N)
        xc, dtc, bc, cc = inp
        dA = dtc * Ag[None, :, :, None]                        # (B,G,R,cl)
        cum = jnp.cumsum(dA, axis=-1)
        seg = cum[..., :, None] - cum[..., None, :]            # [i, j]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        m = (jnp.einsum("bgin,bgjn->bgij", cc, bc)[:, :, None]
             * decay * dtc[..., None, :])
        y = jnp.einsum("bgrij,bgrjp->bgrip", m, xc)
        y = y + (jnp.einsum("bgin,bgrpn->bgrip", cc, state)
                 * jnp.exp(cum)[..., None])
        # decay from each step to the chunk's end: the sum of the later
        # steps' dt·A, not the total less the prefix sum, which cancels
        later = jnp.cumsum(dA[..., :0:-1], axis=-1)[..., ::-1]
        w = dtc * jnp.exp(jnp.concatenate(
            [later, jnp.zeros_like(dA[..., :1])], axis=-1))
        state = (state * jnp.exp(cum[..., -1])[..., None, None]
                 + jnp.einsum("bgrjp,bgjn->bgrpn", xc * w[..., None], bc))
        return state, y

    state0 = jnp.zeros((Bsz, G, R, P, N), jnp.float32)
    state, ys = jax.lax.scan(chunk_step, state0, (xs, dts, Bs, Cs))
    y = ys.transpose(1, 2, 3, 0, 4, 5).reshape(Bsz, H, L, P)
    y = y + D.astype(jnp.float32)[None, :, None, None] * x.astype(jnp.float32)
    return y, state.reshape(Bsz, H, P, N)
