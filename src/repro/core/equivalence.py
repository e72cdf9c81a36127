"""Three-way functional-equivalence checking with first-divergence
localization (the paper's "ensuring functional equivalence", §I/§IV-B).

oracle (ref.py jnp) ≡ interpret (Pallas interpret mode) ≡ compiled (XLA).
On mismatch the report pinpoints the leaf path, flat index, and values —
the co-verification analogue of dropping a waveform cursor on the first
diverging signal.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np


@dataclasses.dataclass
class Divergence:
    pair: Tuple[str, str]
    leaf_path: str
    index: Tuple[int, ...]
    lhs: float
    rhs: float
    max_abs_err: float
    rel_err: float


@dataclasses.dataclass
class EquivalenceReport:
    passed: bool
    tol: float
    backends: List[str]
    divergences: List[Divergence]
    # elements whose pair of leaves held the same bytes, so the diff
    # settled them without arithmetic (a cost counter, not a result)
    same_elems: int = dataclasses.field(default=0, compare=False)

    def __str__(self) -> str:
        if self.passed:
            return f"EQUIVALENT across {self.backends} (tol={self.tol:g})"
        lines = [f"DIVERGENT (tol={self.tol:g}):"]
        for d in self.divergences:
            lines.append(
                f"  {d.pair[0]} vs {d.pair[1]} @ {d.leaf_path}{list(d.index)}"
                f": {d.lhs:.6g} vs {d.rhs:.6g} "
                f"(abs={d.max_abs_err:.3g}, rel={d.rel_err:.3g})")
        return "\n".join(lines)


# Elements of one leaf that the diff casts to float64 at a time; a leaf
# larger than this is diffed chunk by chunk on a thread pool.
_CHUNK = 1 << 20


def _leaves(tree: Any) -> List[Tuple[str, np.ndarray]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) or "<root>", np.asarray(leaf))
            for path, leaf in flat]


def _chunks(fn: Callable[[int, int], Any], n: int) -> List[Any]:
    """``fn(lo, hi)`` over ``[0, n)`` in ``_CHUNK`` pieces, in order; on
    threads when there is more than one piece (numpy drops the GIL)."""
    bounds = [(lo, min(lo + _CHUNK, n)) for lo in range(0, n, _CHUNK)]
    if len(bounds) == 1:
        return [fn(*bounds[0])]
    with ThreadPoolExecutor(min(len(bounds), os.cpu_count() or 1)) as ex:
        return list(ex.map(lambda b: fn(*b), bounds))


def _same_bytes(fa: np.ndarray, fb: np.ndarray) -> bool:
    """Whether two flat leaves of one dtype hold the same bytes, in C
    order: every byte is compared, as words as wide as the size allows."""
    ba = np.ascontiguousarray(fa).view(np.uint8)
    bb = np.ascontiguousarray(fb).view(np.uint8)
    word = next(w for w in (8, 4, 2, 1) if ba.size % w == 0)
    wa, wb = ba.view(f"u{word}"), bb.view(f"u{word}")
    return all(_chunks(lambda lo, hi: np.array_equal(wa[lo:hi], wb[lo:hi]),
                       wa.size))


def _f64(x: np.ndarray) -> np.ndarray:
    return np.asarray(x, dtype=np.float64)


def _absdiff(fa: np.ndarray, fb: np.ndarray, lo: int, hi: int
             ) -> np.ndarray:
    d = _f64(fa[lo:hi]) - _f64(fb[lo:hi])
    return np.abs(d, out=d)


def _maxima(fa: np.ndarray, fb: np.ndarray, lo: int, hi: int
            ) -> Tuple[np.float64, np.float64]:
    """max |a - b| and max |a| over one chunk, NaN if either meets one."""
    d = _absdiff(fa, fb, lo, hi)
    dmax = np.max(d)
    return dmax, np.max(np.abs(_f64(fa[lo:hi]), out=d))


def _diff(a: Any, b: Any, names: Tuple[str, str], tol: float
          ) -> Tuple[Optional[Divergence], int]:
    """The first divergent leaf of ``b`` against ``a`` (``None`` if none),
    and the elements settled by byte equality.

    Every element is compared in float64.  A leaf pair of one dtype whose
    bytes are all equal has a diff of zeros (NaN where both hold the same
    NaN or infinity), so it cannot exceed a tolerance ``>= 0`` and skips
    the arithmetic.  The others are reduced chunk by chunk to the maxima
    of ``|a - b|`` and ``|a|``; the chunk that holds the first largest
    diff is diffed again to name the index.  A NaN in the diff makes its
    maximum NaN, which exceeds no limit, so such a leaf passes."""
    same = 0
    for (path, la), (_, lb) in zip(_leaves(a), _leaves(b)):
        if la.shape != lb.shape:
            return Divergence(names, path, (), float("nan"), float("nan"),
                              float("inf"), float("inf")), same
        if la.size == 0:
            continue
        fa, fb = la.reshape(-1), lb.reshape(-1)
        if (tol >= 0 and la.dtype == lb.dtype and not la.dtype.hasobject
                and _same_bytes(fa, fb)):
            same += la.size
            continue
        maxima = _chunks(lambda lo, hi: _maxima(fa, fb, lo, hi), la.size)
        dmax = np.max([m[0] for m in maxima])
        scale = max(np.max([m[1] for m in maxima]), 1e-9)
        if dmax > tol * max(1.0, scale):
            k = next(k for k, m in enumerate(maxima) if m[0] == dmax)
            lo = k * _CHUNK
            flat = lo + int(np.argmax(
                _absdiff(fa, fb, lo, min(lo + _CHUNK, la.size))))
            idx = np.unravel_index(flat, la.shape)
            return Divergence(names, path, tuple(int(i) for i in idx),
                              float(_f64(fa[flat])), float(_f64(fb[flat])),
                              float(dmax), float(dmax / scale)), same
    return None, same


def compare(a: Any, b: Any, names: Tuple[str, str], tol: float
            ) -> Optional[Divergence]:
    """The first leaf of ``b`` whose largest ``|a - b|`` exceeds ``tol``
    times ``max(1, max |a|)``, located at its first largest element; a
    shape mismatch diverges at once.  Leaves pair up in tree order, as
    far as the shorter tree goes."""
    return _diff(a, b, names, tol)[0]


def compare_outputs(outs: Dict[str, Any],
                    tol: float = 1e-4) -> EquivalenceReport:
    """Compare already-computed per-backend outputs, all vs the first.

    This is the comparison consumed by the CoVerifySession sweep scheduler
    (core/scheduler.py): each sweep group hands in the final DDR state per
    backend and gets back one localized report per group.
    """
    names = list(outs)
    divs: List[Divergence] = []
    same = 0
    base = names[0]
    for other in names[1:]:
        d, n = _diff(outs[base], outs[other], (base, other), tol)
        same += n
        if d is not None:
            divs.append(d)
    return EquivalenceReport(passed=not divs, tol=tol, backends=names,
                             divergences=divs, same_elems=same)


def check_equivalence(fns: Dict[str, Callable], args: tuple,
                      tol: float = 1e-4) -> EquivalenceReport:
    """Run every backend on identical inputs and compare all vs the first."""
    return compare_outputs({n: fn(*args) for n, fn in fns.items()}, tol)
