"""A run with the timed path broken underneath has to come out not
correct: the chip check is skipped, the rest of the run is driven as on
the chip, and the compiled tier's kernel answers wrongly in one of the
ways a co-verified kernel can (one chip: there is no exchange between
chips to leave out).  Each cell's case (``tests/cases/<driver>.py``) names
the ops broken and the checks each fault must fail."""
import numpy as np
import pytest

import cells


def _unchanged(out):
    """The launch leaves its output buffer as allocated."""
    return np.zeros_like(out)


def _half_left_out(out):
    """Only the first half of the rows (a 2-D answer, as the matmul's) or
    of the heads (axis 1, as flash's) is computed."""
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


def _broken_tables(case, fault, ops):
    def tables(*args):
        t = case.cpu_tables(*args)
        for op in ops:
            table = t[op]
            good = table["compiled"]
            t[op] = dict(table, compiled=lambda *a, _g=good: fault(_g(*a)))
        return t
    return tables


def _failing(checks):
    return {n for n, c in checks.items() if c["value"] > c["limit"]}


def _check_broken(workload, fault, ops=None):
    case = cells.case(workload)
    ops = case.OPS if ops is None else ops
    result, _ = cells.run_cell(cells.found(workload),
                               tables=_broken_tables(case, FAULTS[fault],
                                                     ops))
    assert not result["correct"]
    must_fail, must_pass = case.fault_checks(ops, fault)
    failing = _failing(result["checks"])
    assert must_fail <= failing, (must_fail, result["checks"])
    assert not must_pass & failing, (must_pass, result["checks"])


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", cells.WORKLOADS)
def test_broken_kernel_answer_is_not_correct(workload, fault):
    """Every op of the cell answers wrongly."""
    _check_broken(workload, fault)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", cells.cells_breaking("flash"))
def test_broken_flash_alone_is_not_correct(workload, fault):
    """Only the attention kernel answers wrongly; the case says which
    checks that fails (the session's diff, and ``flash_err`` for an altered
    element) and which it leaves passing (the matmul's)."""
    _check_broken(workload, fault, ops=("flash",))
