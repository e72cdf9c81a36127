"""Run FireBridge's two main paths once on one TPU chip and check them.

    python chip_smoke.py

Everything runs in this one process, because a chip belongs to one
process at a time.  Phases:

* device   -- JAX must see a TPU.  Anything else exits nonzero and prints
  no result line: nothing falls back to the CPU.
* coverify -- one ``CoVerifySession`` over the systolic matmul and flash
  attention: the oracle against the Pallas kernel compiled for the chip at
  real widths, and all three tiers (oracle, interpret, compiled) at one
  small aligned size per op.
* serve    -- llama3.2-1b at its published widths (random bf16 weights from
  a seed) behind a continuous-batching ``ServingEngine``; 8 requests go in
  through the CSR doorbell protocol, and every served token is checked
  against a float32 no-cache reference.

Each phase prints one line of what it ran; the wall seconds there are a
record of this run, not a benchmark number.  The last line of standard
output is one JSON object naming the device.  A failed check raises, so
the script exits nonzero.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import CoVerifySession
from repro.kernels.flash_attention.sweep import (flash_chip_backends,
                                                 flash_firmware)
from repro.kernels.systolic_matmul.sweep import (matmul_chip_backends,
                                                 matmul_firmware)
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.models.transformer import RunFlags, forward, lm_logits
from repro.serving import ServingEngine

# Co-verification: a tier agrees with the oracle when every output element
# is within COVERIFY_TOL * max(1, max |oracle output|) of it (the relative
# rule of core/equivalence.compare, at the CPU sweeps' default tolerance).
COVERIFY_TOL = 1e-3
ALL_TIERS = ("oracle", "interpret", "compiled")
REAL_WIDTH = ("oracle", "compiled")
# (op, block or tile, cell config, tiers)
COVERIFY_CELLS = [
    ("matmul", 512, {"size": 2048}, REAL_WIDTH),
    ("matmul", 128, {"size": 2048}, REAL_WIDTH),
    ("matmul", 128, {"size": 256}, ALL_TIERS),
    ("flash", 512, {"batch": 1, "heads": 32, "seq": 2048, "dim": 64},
     REAL_WIDTH),
    ("flash", 128, {"batch": 1, "heads": 2, "seq": 256, "dim": 64},
     ALL_TIERS),
]

# Serving: a served token passes when it is the reference's argmax, or when
# its reference logit is within LOGIT_TOL standard deviations of that
# maximum, the deviation taken over the vocabulary at that position (a
# common offset of the logits does not move it).  bf16 rounding in the
# served model moves the logits by a small fraction of one deviation; a
# token chosen from the wrong context sits several below the maximum
# (tests/test_chip_compile.py plants such a fault and expects a failure).
LOGIT_TOL = 0.25


def _coverify_firmware(fb, op, backend, *, block, **config):
    if op.startswith("matmul"):
        matmul_firmware(fb, op, backend, tile=block, **config)
    else:
        flash_firmware(fb, op, backend, bq=block, bk=block, **config)


def _kernel_inputs(op: str, config: dict):
    if op.startswith("matmul"):
        n = config["size"]
        return [jax.ShapeDtypeStruct((n, n), jnp.float32)] * 2
    shape = (config["batch"], config["heads"], config["seq"], config["dim"])
    return [jax.ShapeDtypeStruct(shape, jnp.float32)] * 3


def coverify_phase() -> dict:
    """Run the co-verification cells; raise unless every check holds."""
    sess = CoVerifySession(_coverify_firmware)
    tables = {}
    for kind, block, config, tiers in COVERIFY_CELLS:
        op = f"{kind}/{block}"
        if op not in tables:
            tables[op] = (matmul_chip_backends(block) if kind == "matmul"
                          else flash_chip_backends(block, block))
            sess.register_op(op, **tables[op])
        for tier in tiers:
            sess.add_cell(op, tier, dict(config, block=block))
    t0 = time.perf_counter()
    report = sess.run(tol=COVERIFY_TOL)
    wall = time.perf_counter() - t0
    errors = [f"{r.cell.label}: {r.error}" for r in report.cells if r.error]
    if errors:
        raise RuntimeError("co-verify cells failed:\n  " + "\n  ".join(errors))
    if not report.passed:
        raise RuntimeError(f"co-verify sweep failed: {report.summary()}\n"
                           + "\n".join(str(e) for e in
                                       report.equivalence.values()))
    for cell in sess.cells:
        if cell.backend != "compiled":
            continue
        kernel = tables[cell.op]["compiled"].kernel
        text = kernel.lower(*_kernel_inputs(cell.op, cell.config)).as_text()
        if "tpu_custom_call" not in text:
            raise RuntimeError(f"{cell.label}: the compiled tier holds no "
                               "Pallas kernel (no tpu_custom_call)")
    return {"cells": len(report.cells), "groups": len(report.equivalence),
            "wall_s": wall}


def _reference_fn(cfg):
    """Float32, no-cache reference: ``make_prefill_fn``'s forward pass, with
    logits kept at chosen positions instead of only the last.  Attention is
    causal, so the logits at position p are those of a prefill over the
    first p + 1 tokens."""
    flags = RunFlags(compute_dtype="float32")

    def ref(params, tokens, pos, served):
        x, _, _ = forward(cfg, params, {"tokens": tokens}, flags, None)
        x = x[jnp.arange(x.shape[0])[:, None], pos]          # (R, N, d)
        logits = lm_logits(cfg, params, x, None)             # (R, N, V)
        top = jnp.max(logits, axis=-1)
        at = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
        return jnp.argmax(logits, axis=-1), top, at, jnp.std(logits, axis=-1)

    return jax.jit(ref)


def serve_phase(cfg, *, n_requests: int = 8, max_len: int = 512,
                prompt_lens=(16, 200), new_tokens=(8, 32),
                seed: int = 0) -> dict:
    """Serve ``n_requests`` through the CSR protocol and check every token
    against the float32 reference; raise unless every check holds.

    Prompt lengths and new-token counts are drawn uniformly from the
    inclusive ranges ``prompt_lens`` and ``new_tokens``, so most prompts
    are left-padded to the engine's prefill bucket.
    """
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.bfloat16)
    eng = ServingEngine(cfg, params, batching="continuous", max_slots=4,
                        max_len=max_len)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    for rid in range(n_requests):
        ln = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        eng.mem.buffers["prompt_in"].array[:ln] = \
            rng.integers(0, cfg.vocab_size, ln)
        eng.csr.fb_write_32(eng.csr.addr_of("SUBMIT_ID"), rid)
        eng.csr.fb_write_32(eng.csr.addr_of("SUBMIT_LEN"), ln)
        eng.csr.fb_write_32(eng.csr.addr_of("SUBMIT_MAXNEW"),
                            int(rng.integers(new_tokens[0],
                                             new_tokens[1] + 1)))
        eng.csr.fb_write_32(eng.csr.addr_of("DOORBELL"), 1)
    eng.run_until_done()
    serve_s = time.perf_counter() - t0
    if eng.csr.poll("STATUS", 0xFFFFFFFF, 2, max_reads=8) < 0:
        raise RuntimeError("engine never reached STATUS=done")
    done = eng.csr.fb_read_32(eng.csr.addr_of("COMPLETED"))
    if done != n_requests:
        raise RuntimeError(f"COMPLETED={done}, expected {n_requests}")
    if eng.csr.log.violations:
        raise RuntimeError(f"protocol violations: {eng.csr.log.violations}")
    reqs = [eng.requests[rid] for rid in range(n_requests)]
    for r in reqs:
        if not r.done or len(r.out_tokens) != r.max_new_tokens:
            raise RuntimeError(f"request {r.rid} served "
                               f"{len(r.out_tokens)}/{r.max_new_tokens}")

    # Teacher-forced reference: request r's token i is predicted at
    # position len(prompt) - 1 + i of prompt + served tokens.
    width = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    n_new = max(r.max_new_tokens for r in reqs)
    tokens = np.zeros((n_requests, width), np.int32)
    pos = np.zeros((n_requests, n_new), np.int32)
    served = np.zeros((n_requests, n_new), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens, np.int32)])
        tokens[i, :len(seq)] = seq
        pos[i] = len(r.prompt) - 1 + np.minimum(np.arange(n_new),
                                                r.max_new_tokens - 1)
        served[i, :r.max_new_tokens] = r.out_tokens
    ref_params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("float32"):
        arg, top, at, spread = jax.device_get(_reference_fn(cfg)(
            ref_params, jnp.asarray(tokens), jnp.asarray(pos),
            jnp.asarray(served)))
    ref_s = time.perf_counter() - t0
    live = np.arange(n_new)[None, :] < np.asarray(
        [r.max_new_tokens for r in reqs])[:, None]
    exact = live & (arg == served)
    gap = (top - at) / spread
    tolerated = live & ~exact & (gap <= LOGIT_TOL)
    bad = live & ~exact & ~tolerated
    if bad.any():
        i, j = map(int, np.argwhere(bad)[0])
        raise RuntimeError(
            f"{int(bad.sum())} served token(s) disagree with the float32 "
            f"reference; first: request {i} token {j} served {served[i, j]}"
            f", reference argmax {arg[i, j]}, logit gap {gap[i, j]:.4g} "
            f"reference std > {LOGIT_TOL:.4g}")
    return {"requests": n_requests, "tokens": int(live.sum()),
            "exact": int(exact.sum()), "tolerated": int(tolerated.sum()),
            "padded": sum(len(r.prompt) % eng.prompt_pad != 0 for r in reqs),
            "max_gap": float(gap[live].max()), "serve_s": serve_s,
            "reference_s": ref_s}


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing runs on the CPU instead", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}; "
          f"compile cache {use_compile_cache()}", flush=True)
    cv = coverify_phase()
    print(f"coverify: {cv['cells']} cells in {cv['groups']} groups agree "
          f"(tol {COVERIFY_TOL:g}), compiled tier is tpu_custom_call; "
          f"{cv['wall_s']:.1f} s wall incl. compile", flush=True)
    sv = serve_phase(get_config("llama3.2-1b"))
    print(f"serve: llama3.2-1b, {sv['requests']} requests ({sv['padded']} "
          f"left-padded), {sv['tokens']} tokens: {sv['exact']} reference "
          f"argmax, {sv['tolerated']} within tolerance (max gap "
          f"{sv['max_gap']:.4g} std, tol {LOGIT_TOL:g}); "
          f"{sv['serve_s']:.1f} s serving, {sv['reference_s']:.1f} s "
          "reference, incl. compile", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
