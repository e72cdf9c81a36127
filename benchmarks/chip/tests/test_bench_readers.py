"""Every per-layer metric's reader, on the hand-made traced run of the
first cell it lists (its case's ``sample_run``; a metric that lists no
cells takes every cell): each finds its events and returns a number, and
finds nothing (and returns None) on a run without them."""
import pytest

import cells
from bench import common

BENCH = cells.BENCH
READERS = sorted(p.stem for p in (common.BENCH_DIR / "metrics").glob("*.py"))


def _case_of(metric):
    listed = {m["name"]: m for m in BENCH["per_layer"]}.get(metric, {})
    return cells.case(listed.get("workloads", cells.WORKLOADS)[0])


def test_every_listed_metric_has_a_reader():
    assert {m["name"] for m in BENCH["per_layer"]} <= set(READERS)


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_its_cells(metric):
    case = _case_of(metric)
    mod = common.load_module(common.BENCH_DIR / "metrics" / f"{metric}.py")
    v = mod.read(case.sample_run())
    assert v is not None and v > 0
    if any(w in metric for w in ("share", "roofline", "mfu")):
        assert v <= 100.0
    assert mod.read(case.empty_run()) is None


def test_sweep_median_ignores_a_stalled_sweep():
    mod = common.load_module(common.BENCH_DIR / "metrics" /
                             "sweep_median_ms.py")
    assert mod.read(_case_of("sweep_median_ms").sample_run()) == \
        pytest.approx(900.0)
