"""Trace reduction: device busy union, idle share, idle gaps named by the
open span, per-op time, on hand-made traces."""
import pytest

from bench import trace as tr

E = tr.Event


def _trace():
    ops = [E("a", 100, 200, "m1", "d0"), E("b", 150, 300, "m1", "d0"),
           E("c", 500, 600, "m2", "d0"), E("d", 900, 950, "m2", "d0")]
    spans = [E(tr.WINDOW_SPAN, 0, 1000), E("bench.step", 80, 320),
             E("bench.step", 470, 970), E("bench.host", 650, 850)]
    return tr.Trace(ops, spans, ["d0"])


def test_union_merges_overlaps_and_clips():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)], 8, 35) == 17
    assert tr.union_ns([], 0, 10) == 0


def test_idle_gaps_cover_what_no_op_covers():
    assert tr.idle_gaps([(10, 20), (15, 30), (50, 60)], 0, 100) == \
        [(0, 10), (30, 50), (60, 100)]
    assert tr.idle_gaps([(0, 100)], 0, 100) == []


def test_busy_and_idle_share():
    t = _trace()
    assert t.busy_s() == pytest.approx(350e-9)          # 200 + 100 + 50
    assert t.idle_share() == pytest.approx(1 - 0.35)


def test_op_times():
    t = _trace()
    assert t.op_time(lambda e: e.name in "ab") == (pytest.approx(250e-9), 2)


def test_gaps_are_named_by_the_innermost_open_span():
    t = _trace()
    b = t.breakdown(n=3)
    names = [g[0] for g in b["idle_gaps"]]
    lengths = [g[1] for g in b["idle_gaps"]]
    assert lengths == pytest.approx([300e-9, 200e-9, 100e-9])
    # (600, 900) -> bench.host opened inside bench.step; (300, 500) -> the
    # first step span until 320: midpoint 400 lies in no span
    assert names[0] == "bench.host"
    assert names[1] == "(no span)"
    assert b["device_ops"][0][0] == "b"


def test_window_span_must_be_unique():
    t = _trace()
    t.spans.append(E(tr.WINDOW_SPAN, 0, 5))
    with pytest.raises(ValueError):
        t.window

