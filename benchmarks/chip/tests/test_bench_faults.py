"""A run with the timed path broken underneath has to come out not
correct: the chip check is skipped, the rest of the run is driven as on
the chip, and the compiled tier's kernel answers wrongly in one of the
ways a co-verified kernel can (one chip: there is no exchange between
chips to leave out)."""
import numpy as np
import pytest

import cells


def _unchanged(out):
    """The launch leaves its output buffer as allocated."""
    return np.zeros_like(out)


def _half_left_out(out):
    """Only the first half of the rows (matmul) or heads (flash) is
    computed."""
    out = np.array(out)
    axis = 0 if out.ndim == 2 else 1
    idx = [slice(None)] * out.ndim
    idx[axis] = slice(out.shape[axis] // 2, None)
    out[tuple(idx)] = 0
    return out


def _altered(out):
    """One element of the answer moved by four times its rms."""
    out = np.array(out)
    flat = out.reshape(-1)
    flat[flat.size // 3] += 4.0 * np.sqrt(np.mean(
        np.square(flat.astype(np.float32))))
    return out


FAULTS = {"unchanged": _unchanged, "half_left_out": _half_left_out,
          "altered": _altered}


def _broken_tables(fault, ops=("matmul", "flash")):
    def tables(tile):
        t = cells.cpu_tables(tile)
        for op in ops:
            table = t[op]
            good = table["compiled"]
            t[op] = dict(table, compiled=lambda *a, _g=good: fault(_g(*a)))
        return t
    return tables


def _failing(checks):
    return sorted(n for n, c in checks.items() if c["value"] > c["limit"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", cells.WORKLOADS)
def test_broken_kernel_answer_is_not_correct(workload, fault):
    result, _ = cells.run_cell(cells.found(workload),
                               tables=_broken_tables(FAULTS[fault]))
    assert not result["correct"]
    checks = result["checks"]
    assert checks["matmul_err"]["value"] > checks["matmul_err"]["limit"]
    assert "sweeps_failed" in _failing(checks)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", cells.WORKLOADS)
def test_broken_flash_alone_is_not_correct(workload, fault):
    """Only the attention kernel answers wrongly.  A flash output left as
    allocated, or with half its heads left out, reads under 1 by
    ``flash_err`` (each element's error is at most its own |reference|),
    under that number's limit; the session's diff against the oracle
    catches it (``sweeps_failed``).  One element moved by 4 rms fails
    both."""
    result, _ = cells.run_cell(cells.found(workload),
                               tables=_broken_tables(FAULTS[fault],
                                                     ops=("flash",)))
    assert not result["correct"]
    failing = _failing(result["checks"])
    assert "matmul_err" not in failing
    assert "sweeps_failed" in failing
    if fault == "altered":
        assert "flash_err" in failing
