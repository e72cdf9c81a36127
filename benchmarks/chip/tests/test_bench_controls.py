"""The control of ``correct``, at a size a test run holds: the plain
reference put in the compiled kernel's place and computed in the next type
below the configuration's (the case's ``CONTROL``: float8 below bfloat16)
goes through the same session, DDR writeback and comparison as the
program, and the run comes out not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

import cells
from bench import common

F8 = jnp.float8_e4m3fn


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("workload", cells.WORKLOADS)
def test_control_is_not_correct(workload, seed):
    case = cells.case(workload)
    result, _ = cells.run_cell(cells.found(workload), seed=seed,
                               control=case.CONTROL)
    assert not result["correct"], result["checks"]
    checks = result["checks"]
    for name in case.CONTROL_FAILS:
        assert checks[name]["value"] > checks[name]["limit"], name


def test_kernel_control_reads_far_above_bf16_rounding():
    ref = common.load_module(common.BENCH_DIR / "configs" / "kernels_ref.py")
    cfg = common.load_json(common.BENCH_DIR / "configs" /
                           "nemo12b-layer-kernels.json")
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(128, 512)), jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(512, 256)), jnp.bfloat16)
    want = ref.matmul(a, b)
    lim = cfg["checks"]["matmul_err"]["limit"]
    sound = ref.rel_err(want.astype(jnp.bfloat16), want)
    control = ref.rel_err(ref.matmul(a, b, cast=F8).astype(jnp.bfloat16),
                          want)
    assert sound <= 2.0 ** -8 < lim < control
