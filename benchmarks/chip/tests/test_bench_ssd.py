"""A planted fault of the SSD cell beyond the generic ones of
test_bench_faults.py: the compiled tier computes the scan with its state
held in bfloat16, a precision below the configuration's float32 state.
The run has to come out not correct, by the state's error."""
import jax
import jax.numpy as jnp
import numpy as np

import cells

CELL = "coverify-nemotronh-ssd-8k"


def _scan_with_bf16_state(x, dt, B, C, A, D):
    """The recurrence step by step, the state rounded to bfloat16 after
    every step; y in x's type, the state returned in float32."""
    f32 = jnp.float32
    H, G = x.shape[1], B.shape[1]
    group = np.arange(H) // (H // G)

    def step(s, inp):
        x_t, dt_t, B_t, C_t = inp
        s = (jnp.exp(dt_t * A)[:, :, None, None] * s.astype(f32)
             + (dt_t[:, :, None] * x_t)[..., None]
             * B_t[:, group][:, :, None, :]).astype(jnp.bfloat16)
        y_t = (jnp.einsum("bhpn,bhn->bhp", s.astype(f32), C_t[:, group])
               + D[:, None] * x_t)
        return s, y_t

    def scan(x, dt, B, C, A, D):
        s0 = jnp.zeros(x.shape[:2] + (x.shape[3], B.shape[3]), jnp.bfloat16)
        s, ys = jax.lax.scan(step, s0, tuple(
            jnp.moveaxis(a, 2, 0) for a in (x, dt, B, C)))
        return jnp.moveaxis(ys, 0, 2), s.astype(f32)
    with jax.default_matmul_precision("highest"):
        y, s = jax.jit(scan)(*(jnp.asarray(a, f32)
                               for a in (x, dt, B, C, A, D)))
    return np.asarray(y.astype(x.dtype)), np.asarray(s)


def test_scan_with_its_state_in_bf16_is_not_correct():
    case = cells.case(CELL)

    def tables(chunk, hb):
        t = case.cpu_tables(chunk, hb)
        t["scan"] = dict(t["scan"], compiled=_scan_with_bf16_state)
        return t
    result, _ = cells.run_cell(cells.found(CELL), tables=tables)
    assert not result["correct"], result["checks"]
    state = result["checks"]["ssd_state_err"]
    assert state["value"] > state["limit"], result["checks"]
