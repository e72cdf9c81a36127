"""core/equivalence.py against the diff it replaced: byte-identical leaves
are settled without arithmetic and the rest are diffed in float64 chunks,
and every report must equal the one the full-size float64 diff gave."""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.core import equivalence
from repro.core.equivalence import Divergence, EquivalenceReport

BF16 = ml_dtypes.bfloat16


# -- the diff before chunking, verbatim: the oracle --------------------------

def _leaf_paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        p = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) or "<root>"
        out.append((p, np.asarray(leaf, dtype=np.float64)
                    if np.issubdtype(np.asarray(leaf).dtype, np.floating)
                    else np.asarray(leaf).astype(np.float64)))
    return out


def _compare(a, b, names, tol):
    for (pa, la), (_, lb) in zip(_leaf_paths(a), _leaf_paths(b)):
        if la.shape != lb.shape:
            return Divergence(names, pa, (), float("nan"), float("nan"),
                              float("inf"), float("inf"))
        diff = np.abs(la - lb)
        if diff.size == 0:
            continue
        scale = max(np.max(np.abs(la)), 1e-9)
        if np.max(diff) > tol * max(1.0, scale):
            idx = np.unravel_index(int(np.argmax(diff)), diff.shape)
            return Divergence(names, pa, tuple(int(i) for i in idx),
                              float(la[idx]), float(lb[idx]),
                              float(np.max(diff)),
                              float(np.max(diff) / scale))
    return None


def _compare_outputs(outs, tol):
    names = list(outs)
    divs = []
    base = names[0]
    for other in names[1:]:
        d = _compare(outs[base], outs[other], (base, other), tol)
        if d is not None:
            divs.append(d)
    return EquivalenceReport(passed=not divs, tol=tol, backends=names,
                             divergences=divs)


def _exact(rep: EquivalenceReport):
    """Every field, floats by ``repr`` so NaN meets NaN and -0.0 is kept."""
    def f(x):
        assert type(x) is float
        return repr(x)
    return (rep.passed, repr(rep.tol), rep.backends,
            [(d.pair, d.leaf_path, d.index, f(d.lhs), f(d.rhs),
              f(d.max_abs_err), f(d.rel_err)) for d in rep.divergences])


# -- cases: (backend outputs, tol, elements settled by byte equality) --------

def _r(shape, dtype=np.float32, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(dtype)


def _two(a, b):
    return {"oracle": a, "compiled": b}


def _bumped(x, at, by):
    y = x.copy()
    y[at] += by
    return y


def _with(x, at, value):
    y = x.copy()
    y[at] = value
    return y


def _identical_bf16():
    x = _r((3, 5, 7), BF16)
    return _two({"x": x}, {"x": x.copy()}), 2 ** -6, 105


def _within_tol():
    x = _r((4, 9))
    return _two({"x": x}, {"x": x + np.float32(1e-6)}), 1e-4, 0


def _over_tol():
    x = _r((4, 9))
    return _two({"x": x}, {"x": _bumped(x, (2, 3), 1.0)}), 1e-4, 0


def _max_in_later_chunk():
    x = _r(40)
    y = _bumped(_bumped(x, 1, 0.5), 33, 2.0)
    return _two({"x": x}, {"x": y}), 1e-3, 0


def _tied_maxima_first_wins():
    x = np.zeros(40, np.float32)
    y = _with(_with(_with(x, 30, 1.0), 5, 1.0), 31, -1.0)
    return _two({"x": x}, {"x": y}), 0.1, 0


def _tie_inside_a_later_chunk():
    x = np.zeros(40, np.float32)
    y = _with(_with(_with(x, 2, 0.5), 27, 1.0), 25, 1.0)
    return _two({"x": x}, {"x": y}), 0.1, 0


def _shape_mismatch():
    same = _r(6, BF16)
    a = {"a": same, "z": np.zeros((2, 3), np.float32)}
    b = {"a": same.copy(), "z": np.zeros((3, 2), np.float32)}
    return _two(a, b), 1e-4, 6


def _equal_bytes_other_dtypes():
    x = _r((6, 4))
    return _two({"x": x}, {"x": x.view(np.int32)}), 1e-3, 0


def _bf16_against_its_bits():
    x = _r(12, BF16)
    return _two({"x": x}, {"x": x.view(np.uint16)}), 1e-3, 0


def _nan_one_side():
    x = _r((5, 5))
    y = _bumped(_with(x, (1, 1), np.nan), (0, 0), 5.0)
    return _two({"x": x}, {"x": y}), 1e-4, 0


def _nan_lhs_side():
    x = _r((5, 5))
    return _two({"x": _with(x, (4, 0), np.nan)}, {"x": x}), 1e-4, 0


def _nan_both_same_place():
    x = _with(_r((5, 5)), (1, 1), np.nan)
    return _two({"x": x}, {"x": x.copy()}), 1e-4, 25


def _nan_both_and_a_diff():
    x = _with(_r((5, 5)), (1, 1), np.nan)
    return _two({"x": x}, {"x": _bumped(x, (3, 3), 5.0)}), 1e-4, 0


def _nan_other_sign():
    x = _with(_r(9), 4, np.nan)
    return _two({"x": x}, {"x": _with(x, 4, -np.nan)}), 1e-4, 0


def _inf_both():
    x = _with(_with(_r(9), 2, np.inf), 7, -np.inf)
    return _two({"x": x}, {"x": x.copy()}), 1e-4, 9


def _inf_lhs():
    x = _r(9)
    return _two({"x": _with(x, 2, np.inf)}, {"x": x}), 1e-4, 0


def _inf_rhs():
    x = _r(9)
    return _two({"x": x}, {"x": _with(x, 6, -np.inf)}), 1e-4, 0


def _inf_opposite():
    x = _with(_r(9), 2, np.inf)
    return _two({"x": x}, {"x": _with(x, 2, -np.inf)}), 1e-4, 0


def _negative_zero():
    x = np.zeros(8, np.float32)
    return _two({"x": -x}, {"x": x}), 0.0, 0


def _negative_zero_reported():
    x = _with(np.zeros(8, np.float32), 3, -0.0)
    return _two({"x": x}, {"x": _with(x, 3, 1.0)}), 1e-4, 0


def _int64_equal():
    x = np.arange(-20, 20, dtype=np.int64) * 7919
    return _two({"tok": x}, {"tok": x.copy()}), 0.0, 40


def _int64_over():
    x = np.arange(-20, 20, dtype=np.int64) * 7919
    return _two({"tok": x}, {"tok": _bumped(x, 31, 3)}), 0.0, 0


def _int64_beyond_float64():
    x = np.full(10, 2 ** 60, np.int64)
    return _two({"tok": x}, {"tok": _bumped(x, 4, 1)}), 0.0, 0


def _bool_leaves():
    x = np.zeros(10, bool)
    return _two({"m": x}, {"m": _with(x, 8, True)}), 0.5, 0


def _empty():
    e = np.zeros((0, 3), BF16)
    return _two({"e": e, "x": _r(4)}, {"e": e.copy(), "x": _r(4)}), 1e4, 4


def _empty_shape_mismatch():
    return _two({"e": np.zeros((0, 3))}, {"e": np.zeros((3, 0))}), 1e-4, 0


def _transposed_over():
    x = _r((8, 6)).T
    return _two({"x": x}, {"x": _bumped(x, (5, 2), 1.0)}), 1e-4, 0


def _transposed_same():
    x = _r((8, 6), BF16).T
    return _two({"x": x}, {"x": np.ascontiguousarray(x)}), 1e-4, 48


def _strided_same():
    x = _r(20, BF16)[::2]
    return _two({"x": x}, {"x": x.copy()}), 1e-4, 10


def _jax_arrays():
    x = _r((4, 8), BF16)
    a = {"k": jnp.asarray(x), "o": jnp.asarray(x)}
    b = {"k": jnp.asarray(x), "o": jnp.asarray(_bumped(x, (3, 1), 1.0))}
    return _two(a, b), 2 ** -6, 32


def _unequal_leaf_counts():
    x = _r(10)
    a = {"x": x, "y": x}
    b = {"x": x.copy(), "y": x.copy(), "z": _bumped(x, 0, 9.0)}
    return _two(a, b), 1e-4, 20


def _fewer_leaves_on_the_right():
    x = _r(10)
    return _two({"x": x, "y": _bumped(x, 1, 9.0)}, {"x": x}), 1e-4, 10


def _scalars():
    a = {"s": 1.0, "t": np.float32(2.0)}
    return _two(a, {"s": 1.0, "t": np.float32(2.5)}), 1e-4, 1


def _nested_tree():
    x, y = _r((3, 4)), _r(7, seed=1)
    a = {"p": [x, (y, 3)]}
    b = {"p": [x.copy(), (_bumped(y, 6, 0.25), 3)]}
    return _two(a, b), 1e-3, 12


def _negative_tol_identical():
    x = _r(9)
    return _two({"x": x}, {"x": x.copy()}), -1.0, 0


def _nan_tol():
    x = _r(9)
    return _two({"x": x}, {"x": _bumped(x, 2, 1.0)}), float("nan"), 0


def _three_backends():
    x = _r((6, 6), BF16)
    outs = {"oracle": {"c": x}, "interpret": {"c": x.copy()},
            "compiled": {"c": _bumped(x, (5, 5), 1.0)}}
    return outs, 2 ** -6, 36


CASES = [_identical_bf16, _within_tol, _over_tol, _max_in_later_chunk,
         _tied_maxima_first_wins, _tie_inside_a_later_chunk, _shape_mismatch,
         _equal_bytes_other_dtypes, _bf16_against_its_bits, _nan_one_side,
         _nan_lhs_side, _nan_both_same_place, _nan_both_and_a_diff,
         _nan_other_sign, _inf_both, _inf_lhs, _inf_rhs, _inf_opposite,
         _negative_zero, _negative_zero_reported, _int64_equal, _int64_over,
         _int64_beyond_float64, _bool_leaves, _empty, _empty_shape_mismatch,
         _transposed_over, _transposed_same, _strided_same, _jax_arrays,
         _unequal_leaf_counts, _fewer_leaves_on_the_right, _scalars,
         _nested_tree, _negative_tol_identical, _nan_tol, _three_backends]


@pytest.mark.parametrize("chunk", [3, 1 << 20], ids=["chunks3", "whole"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[1:])
def test_report_equals_the_full_float64_diff(case, chunk, monkeypatch):
    monkeypatch.setattr(equivalence, "_CHUNK", chunk)
    outs, tol, same = case()
    with np.errstate(invalid="ignore", over="ignore"):
        want = _compare_outputs(outs, tol)
        got = equivalence.compare_outputs(outs, tol)
    assert _exact(got) == _exact(want)
    assert got.same_elems == same
    base, *others = outs
    for other in others:
        with np.errstate(invalid="ignore", over="ignore"):
            d_new = equivalence.compare(outs[base], outs[other],
                                        (base, other), tol)
            d_old = _compare(outs[base], outs[other], (base, other), tol)
        assert (d_new is None) == (d_old is None)
