"""Systolic matmul / Mamba2 SSD / RWKV6 WKV kernels vs oracles (interpret
mode), plus the static BlockSpec transaction stream."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.mamba2_scan import kernel as SSD, ref as SSDref
from repro.kernels.rwkv6_wkv import kernel as WKV, ref as WKVref
from repro.kernels.systolic_matmul import kernel as MM, ops as MMops, \
    ref as MMref

KEY = jax.random.PRNGKey(5)


@pytest.mark.parametrize("M,N,K,bm,dt", [
    (256, 128, 128, 64, jnp.float32),
    (128, 256, 512, 64, jnp.bfloat16),
    (128, 128, 128, 128, jnp.float32),
])
def test_matmul_kernel(M, N, K, bm, dt):
    a = jax.random.normal(jax.random.fold_in(KEY, 1), (M, K), dt)
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (K, N), dt)
    got = MM.matmul(a, b, bm=bm, bn=bm, bk=bm)
    ref = MMref.matmul_ref(a, b)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    assert err < (1e-4 if dt == jnp.float32 else 1.0) * max(1.0, float(
        jnp.max(jnp.abs(ref.astype(jnp.float32)))))


def test_matmul_transaction_stream():
    txs = MMops.transactions(256, 128, 128, bm=64, bn=64, bk=64,
                             dtype_bytes=2)
    reads = [t for t in txs if t[1] == "read"]
    writes = [t for t in txs if t[1] == "write"]
    # grid 4x2x2: 2 reads per k step, 1 write per (m,n)
    assert len(reads) == 4 * 2 * 2 * 2 and len(writes) == 4 * 2
    assert sum(t[3] for t in writes) == 256 * 128 * 2


def _rel_err(got, want):
    """max |got - want| over the largest |want|."""
    want = want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                 / jnp.max(jnp.abs(want)))


# f32: every path computes in float32 (the kernel's prefix sums are
# matmuls, the oracles' sequential sums); the most read at these sizes is
# 7.4e-6 of the largest |output|, and 2e-5 of it stays under the 1e-3
# absolute these cases were held to before (|y| <= 40, |state| <= 13)
F32_TOL = 2e-5
# a bf16 y is the f32 result rounded once: within 2**-9 of each element
BF16_TOL = 2.0 ** -8


@pytest.mark.parametrize("B,L,H,P,N,chunk,G,hb,dt", [
    pytest.param(2, 64, 8, 16, 8, 16, 1, 4, jnp.float32,
                 id="2-64-8-16-8-16"),
    pytest.param(1, 128, 4, 8, 16, 32, 1, 4, jnp.float32,
                 id="1-128-4-8-16-32"),
    pytest.param(1, 128, 8, 16, 16, 32, 2, 4, jnp.float32,
                 id="G2-1-128-8-16-16-32"),
    pytest.param(2, 64, 8, 16, 8, 16, 4, 2, jnp.float32,
                 id="G4-2-64-8-16-8-16"),
    pytest.param(1, 256, 16, 64, 128, 128, 2, 8, jnp.bfloat16,
                 id="G2-bf16-1-256-16-64-128-128"),
])
def test_ssd_kernel(B, L, H, P, N, chunk, G, hb, dt):
    """The grouped kernel (interpret mode), the chunked SSD and the
    per-timestep recurrence agree on y and on the final state."""
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (B, H, L, P), dt)
    dt_ = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 4),
                                            (B, H, L)))
    B_ = jax.random.normal(jax.random.fold_in(KEY, 5), (B, G, L, N), dt)
    C_ = jax.random.normal(jax.random.fold_in(KEY, 6), (B, G, L, N), dt)
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 7), (H,)) * 0.5)
    D = jnp.ones((H,))
    y_k, st_k = SSD.ssd_scan(x, dt_, B_, C_, A, D, chunk=chunk, hb=hb)
    y_r, st_r = SSDref.ssd_scan_ref(x, dt_, B_, C_, A, D)
    y_c, st_c = SSDref.ssd_chunked_ref(x, dt_, B_, C_, A, D, chunk=chunk)
    assert y_k.dtype == x.dtype and st_k.dtype == jnp.float32
    assert _rel_err(y_k, y_r) < (F32_TOL if dt == jnp.float32 else BF16_TOL)
    assert _rel_err(y_c, y_r) < F32_TOL
    assert _rel_err(st_k, st_r) < F32_TOL
    assert _rel_err(st_c, st_r) < F32_TOL


def test_ssd_exp_is_f32_accurate():
    """The kernel's exp, which replaces the chip's coarser native one, is
    within a few f32 units in the last place of exp over the arguments
    the scan meets (<= 0), and 1 at 0."""
    x = jnp.concatenate([jnp.linspace(-87.0, 0.0, 1 << 16),
                         -jnp.logspace(-8, 0, 1 << 12)])
    got = np.asarray(SSD._exp(x), np.float64)
    want = np.exp(np.asarray(x, np.float64))
    assert float(np.max(np.abs(got - want) / want)) < 4 * 2.0 ** -24
    assert float(SSD._exp(jnp.zeros(()))) == 1.0


@pytest.mark.parametrize("B,L,H,K", [(2, 64, 4, 16), (1, 32, 8, 32)])
def test_wkv_kernel(B, L, H, K):
    r = jax.random.normal(jax.random.fold_in(KEY, 8), (B, L, H, K))
    k = jax.random.normal(jax.random.fold_in(KEY, 9), (B, L, H, K))
    v = jax.random.normal(jax.random.fold_in(KEY, 10), (B, L, H, K))
    w = jnp.exp(-jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 11),
                                           (B, L, H, K))))
    u = jax.random.normal(jax.random.fold_in(KEY, 12), (H, K)) * 0.5
    y_k, st_k = WKV.wkv_scan(r, k, v, w, u, chunk=16, hb=4)
    y_r, st_r = WKVref.wkv_scan_ref(r, k, v, w, u)
    assert float(jnp.max(jnp.abs(y_k - y_r))) < 1e-3
    assert float(jnp.max(jnp.abs(st_k - st_r))) < 1e-3


def test_model_wkv_matches_kernel_path():
    """The model's lax time-mix chunk and the Pallas kernel agree."""
    from repro.models.rwkv6 import _wkv_chunk
    B, c, H, K = 2, 16, 4, 16
    r = jax.random.normal(jax.random.fold_in(KEY, 13), (B, c, H, K))
    k = jax.random.normal(jax.random.fold_in(KEY, 14), (B, c, H, K))
    v = jax.random.normal(jax.random.fold_in(KEY, 15), (B, c, H, K))
    w = jnp.exp(-jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 16),
                                           (B, c, H, K))))
    u = jax.random.normal(jax.random.fold_in(KEY, 17), (H, K)) * 0.5
    st0 = jnp.zeros((B, H, K, K))
    st_m, y_m = _wkv_chunk(st0, r, k, v, w, u)
    y_kk, st_kk = WKV.wkv_scan(r, k, v, w, u, chunk=16, hb=4)
    assert float(jnp.max(jnp.abs(y_m - y_kk))) < 1e-4
    assert float(jnp.max(jnp.abs(st_m - st_kk))) < 1e-4
