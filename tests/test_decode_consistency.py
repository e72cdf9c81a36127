"""Prefill+decode must reproduce full-prefill logits (KV/state-cache
bookkeeping correctness) across families — in f32 with no-drop MoE capacity
so the check is tight."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.models import (RunFlags, init_params, make_decode_fn,
                          make_prefill_fn)
from repro.models.inputs import make_prefill_batch

pytestmark = pytest.mark.slow      # decode sweep: ~40s across families

FLAGS = RunFlags(attn_impl="chunked", q_chunk=16, kv_chunk=16,
                 compute_dtype="float32")
B, S, S0 = 2, 64, 48

ARCHS = ["mistral-nemo-12b", "granite-20b", "zamba2-2.7b", "rwkv6-7b",
         "llama-3.2-vision-11b", "moonshot-v1-16b-a3b",
         "phi3.5-moe-42b-a6.6b"]


def _config(arch):
    cfg = smoke(get_config(arch))
    if cfg.moe is not None:   # lift capacity so no tokens drop (determinism)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
    return cfg


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / max(1e-6, np.max(np.abs(a)))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch):
    cfg = _config(arch)
    key = jax.random.PRNGKey(7)
    params = init_params(cfg, key)
    prefill = jax.jit(make_prefill_fn(cfg, FLAGS, None, max_len=S))
    decode = jax.jit(make_decode_fn(cfg, FLAGS, None))

    batch = make_prefill_batch(cfg, B, S, key)
    logits_full, _ = prefill(params, batch)

    b0 = dict(batch)
    b0["tokens"] = batch["tokens"][:, :S0]
    lg, cache = prefill(params, b0)
    for t in range(S0, S):
        lg, cache = decode(params, cache, batch["tokens"][:, t])
    err = _rel_err(logits_full, lg)
    assert err < 1e-4, f"{arch}: rel_err={err:.3e}"


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if get_config(a).family in
                                  ("dense", "moe", "vlm")])
def test_left_padded_prefill_matches_unpadded(arch):
    """A prompt left-padded to the prefill bucket S0, with its pad keys
    masked through batch["n_pad"], gives the unpadded prompt's logits at
    prefill and at every decode step after it (RoPE sees only position
    deltas, so the shift by the pad length is exact)."""
    cfg = _config(arch)
    key = jax.random.PRNGKey(7)
    params = init_params(cfg, key)
    prefill = jax.jit(make_prefill_fn(cfg, FLAGS, None, max_len=S))
    decode = jax.jit(make_decode_fn(cfg, FLAGS, None))
    batch = make_prefill_batch(cfg, 1, S, key)
    n_pad, toks = 8, batch["tokens"]
    plain = dict(batch, tokens=toks[:, :S0 - n_pad])
    padded = dict(batch, n_pad=jnp.asarray([n_pad], jnp.int32),
                  tokens=jnp.pad(toks[:, :S0 - n_pad], ((0, 0), (n_pad, 0))))
    la, ca = prefill(params, plain)
    lb, cb = prefill(params, padded)
    for t in range(S0 - n_pad, S - n_pad + 1):
        err = _rel_err(la, lb)
        assert err < 1e-4, f"{arch}: after token {t - 1}: rel_err={err:.3e}"
        if t < S - n_pad:
            la, ca = decode(params, ca, toks[:, t])
            lb, cb = decode(params, cb, toks[:, t])
