"""Compile rehearsals for one v5e chip, plus chip_smoke.py's serve phase.

The kernels of the co-verification path are compiled at real widths for a
described (not attached) v5e, so a block the TPU compiler refuses fails
here and costs no chip time.  The topology is described inside a fixture,
never at import: only one process may load the TPU library, and every
test worker imports this file.  Nothing here runs on a chip.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.configs import get_config, smoke
from repro.kernels.flash_attention import kernel as FK
from repro.kernels.flash_attention.sweep import flash_chip_backends
from repro.kernels.mamba2_scan.sweep import ssd_chip_backends
from repro.kernels.systolic_matmul.kernel import matmul
from repro.kernels.systolic_matmul.sweep import matmul_chip_backends
from repro.models import init_params
from repro.models.transformer import RunFlags, make_prefill_fn


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_compiles_for_v5e(one_chip, dtype):
    x = jax.ShapeDtypeStruct((2048, 2048), dtype, sharding=one_chip)
    _assert_kernel_compiles(
        functools.partial(matmul, bm=512, bn=512, bk=512, interpret=False),
        x, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dkdv", "flash_dq"])
def test_flash_compiles_for_v5e(one_chip, kernel, dtype):
    """B1 H32 KH8 S2048 D64 (llama3.2-1b's attention), bq = bk = 512."""
    def arg(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q, kv = arg((1, 32, 2048, 64)), arg((1, 8, 2048, 64))
    row = arg((1, 32, 2048, 1), jnp.float32)        # lse / delta
    args = (q, kv, kv) if kernel == "flash_fwd" else (q, kv, kv, q, row, row)
    _assert_kernel_compiles(
        functools.partial(getattr(FK, kernel), causal=True, bq=512, bk=512,
                          interpret=False), *args)


@pytest.mark.parametrize("kind,block,config",
                         [cell[:3] for cell in chip_smoke.COVERIFY_CELLS])
def test_coverify_compiled_tier_compiles_for_v5e(one_chip, kind, block,
                                                 config):
    """Each chip_smoke co-verify cell's compiled tier, taken from the
    backend table the smoke registers, is a Pallas kernel for the chip,
    and the program and its kernel carry the kernel's name (the device
    trace names the op by it)."""
    table = (matmul_chip_backends(block) if kind == "matmul"
             else flash_chip_backends(block, block))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in chip_smoke._kernel_inputs(kind, config)]
    text = table["compiled"].kernel.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    name = "systolic_matmul" if kind == "matmul" else "flash_attention_fwd"
    assert f"%{name}." in text                         # the device op
    assert f"jit({name})/{name}/pallas_call" in text   # jit and pallas_call


def test_ssd_compiles_for_v5e(one_chip):
    """Nemotron-H-47B's Mamba-2 SSD scan at its published widths over its
    whole 8192-token context (B 1, H 256, P 64, G 8, N 256, chunk 128,
    head block 8, bf16 x/B/C), through the chip backend table's compiled
    tier, which carries the kernel's name."""
    B, L, H, P, G, N = 1, 8192, 256, 64, 8, 256

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bc = arg((B, G, L, N), jnp.bfloat16)
    args = [arg((B, H, L, P), jnp.bfloat16), arg((B, H, L), jnp.float32),
            bc, bc, arg((H,), jnp.float32), arg((H,), jnp.float32)]
    kernel = ssd_chip_backends(128, 8)["compiled"].kernel
    text = kernel.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%ssd_scan." in text
    assert "jit(ssd_scan)/ssd_scan/pallas_call" in text


SMOKE_SERVE = dict(n_requests=3, max_len=128, prompt_lens=(16, 48),
                   new_tokens=(4, 8))


def test_serve_phase_on_cpu():
    """chip_smoke's serve phase at smoke() size: every request completes
    through the CSR protocol and agrees with the float32 reference,
    left-padded prompts included."""
    stats = chip_smoke.serve_phase(smoke(get_config("llama3.2-1b")),
                                   **SMOKE_SERVE)
    assert stats["requests"] == 3
    assert stats["padded"] > 0
    assert stats["exact"] + stats["tolerated"] == stats["tokens"]
    assert 3 * 4 <= stats["tokens"] <= 3 * 8


def test_serve_phase_rejects_attending_to_padding(monkeypatch):
    """Planted fault: a prefill that attends to its left-pad keys serves
    tokens the unpadded reference would not give, and the serve phase's
    logit-gap rule refuses them."""
    from repro.serving.engine import ServingEngine
    batchify = ServingEngine._batchify

    def unmasked(self, batch):
        batch.pop("n_pad")
        return batchify(self, batch)

    monkeypatch.setattr(ServingEngine, "_batchify", unmasked)
    with pytest.raises(RuntimeError, match="disagree with the float32"):
        chip_smoke.serve_phase(smoke(get_config("llama3.2-1b")),
                               **SMOKE_SERVE)


def test_reference_is_prefill_at_every_position():
    """The serve phase's reference keeps the logits of every position; at
    each one they are make_prefill_fn's over the prefix up to it."""
    cfg = smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0,
                                cfg.vocab_size)
    pos = jnp.asarray([[5, 11, 23]])
    arg, top, _, _ = chip_smoke._reference_fn(cfg)(params, tokens, pos,
                                                   jnp.zeros_like(pos))
    prefill = make_prefill_fn(cfg, RunFlags(compute_dtype="float32"), None,
                              max_len=24)
    for j, p in enumerate(np.asarray(pos[0])):
        logits, _ = prefill(params, {"tokens": tokens[:, :p + 1]})
        assert int(arg[0, j]) == int(jnp.argmax(logits[0]))
        np.testing.assert_allclose(float(top[0, j]), float(logits[0].max()),
                                   rtol=1e-5)
