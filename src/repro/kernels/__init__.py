"""Pallas kernels: the "hardware" that FireBridge co-verifies."""
import jax
import jax.numpy as jnp


def contract_precision(dtype):
    """MXU precision for a kernel's dots, given its inputs' ``dtype``.

    f32 inputs get a full f32 contraction: a TPU's default for an f32 dot
    is one bf16 pass, which an f32 oracle sees as a divergence.  bf16
    inputs keep the default.  That pass is exact only for a dot of two
    inputs (matmul's a·b; flash's q·k and do·v): a dot with an f32
    intermediate (flash's p·v, p·do, ds·q and ds·k) rounds it to bf16.
    """
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
