"""core/spans.py: a span times itself whether or not a profiler runs, and
the session's report takes its times from its spans
(benchmarks/chip/tests/test_bench_spans.py reads them from a trace)."""
import time

import jax
import numpy as np
import pytest

from repro.core import CongestionConfig, CoVerifySession
from repro.core.spans import span
from repro.kernels.systolic_matmul.sweep import (matmul_backends,
                                                 matmul_firmware)


def test_span_times_itself_without_a_profiler():
    with span("fb.test", bytes=4) as s:
        s.set(bursts=2)
        time.sleep(0.01)
    assert 0.01 <= s.seconds < 1.0


def test_span_closes_and_keeps_its_time_when_the_work_raises():
    with pytest.raises(ValueError):
        with span("fb.test") as s:
            raise ValueError("firmware fault")
    assert 0.0 <= s.seconds < 1.0


def test_report_phases_split_the_run():
    sess = CoVerifySession(matmul_firmware)
    sess.register_op("mm", **matmul_backends(jit=False))
    sess.add_sweep("mm", ("oracle", "interpret"), [{"size": 32}])
    rep = sess.run(max_workers=2)
    assert set(rep.phase_seconds) == {"cells", "precheck", "compare",
                                      "bisect"}
    assert rep.wall_seconds == rep.phase_seconds["cells"]
    assert rep.phase_seconds["bisect"] == 0.0
    assert rep.phase_seconds["compare"] > 0.0
    # a cell's firmware runs inside the cell phase
    assert all(0 < r.seconds <= rep.wall_seconds for r in rep.cells)
    assert rep.summary()["phase_seconds"] == {
        k: round(v, 3) for k, v in rep.phase_seconds.items()}


def test_a_failing_sweep_times_its_bisection():
    table = matmul_backends(jit=False)

    def buggy(a, b):
        out = table["oracle"](a, b).copy()
        out[0, 0] += 1.0
        return out

    sess = CoVerifySession(matmul_firmware)
    sess.register_op("mm", oracle=table["oracle"], interpret=buggy)
    sess.add_sweep("mm", ("oracle", "interpret"), [{"size": 32}])
    rep = sess.run()
    assert not rep.passed and rep.divergences
    assert rep.phase_seconds["bisect"] > 0.0


def _host_events(trace_dir, name):
    """Arguments of every host event called ``name`` in the trace."""
    (path,) = trace_dir.glob("**/*.xplane.pb")
    return [dict(ev.stats)
            for plane in jax.profiler.ProfileData.from_file(
                str(path)).planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events if ev.name == name]


def test_compare_span_counts_what_byte_equality_settled(tmp_path):
    """Both backends host-wrote ``a`` and ``b`` from one source, so the diff
    settles them by byte equality; ``c`` differs by an ulp and is diffed."""
    table = matmul_backends(jit=False)

    def nudged(a, b):
        c = table["oracle"](a, b)
        return np.nextafter(c, np.inf, dtype=c.dtype)

    sess = CoVerifySession(matmul_firmware)
    sess.register_op("mm", oracle=table["oracle"], compiled=nudged)
    sess.add_sweep("mm", ("oracle", "compiled"), [{"size": 32}])
    jax.profiler.start_trace(str(tmp_path))
    rep = sess.run(max_workers=2)
    jax.profiler.stop_trace()
    assert rep.passed
    assert rep.phase_seconds["compare"] > 0.0
    (eq,) = rep.equivalence.values()
    inputs = 2 * 32 * 32
    assert eq.same_elems == inputs
    compares = _host_events(tmp_path, "fb.sweep.compare")
    assert [(s["elems"], s["same_elems"]) for s in compares] == \
        [(3 * 32 * 32, inputs)]


def test_collect_builds_no_transaction(tmp_path):
    """A congested bridge cell collects its link statistics and counter
    digest from the batches' columns and one values suffix per tick:
    every link batch is still unmaterialized afterwards, and the collect
    span counts the rows digested, the suffixes formatted and no
    Transaction built."""
    targets = []

    def firmware(fb, op, backend, **kw):
        targets.append(fb)
        matmul_firmware(fb, op, backend, **kw)

    sess = CoVerifySession(firmware,
                           congestion=CongestionConfig(dos_prob=0.05, seed=7))
    sess.register_op("mm", **matmul_backends(tile=16, jit=False))
    sess.add_sweep("mm", ("oracle", "interpret"), [{"size": 32, "tile": 16}])
    jax.profiler.start_trace(str(tmp_path))
    rep = sess.run(max_workers=2)
    jax.profiler.stop_trace()
    assert rep.passed
    assert all(r.congestion is not None and len(r.congestion.timeline) > 0
               for r in rep.cells)
    for fb in targets:
        batches = fb.mem.link._tl_pending
        assert batches and all(b._txs is None for b in batches)
    collects = _host_events(tmp_path, "fb.cell.collect")
    rows = sorted(fb.mem.counters.stream.n_samples for fb in targets)
    assert sorted(c["samples"] for c in collects) == rows
    assert all(0 < c["runs"] < c["samples"] for c in collects)
    assert [c["materialized"] for c in collects] == [0, 0]
    assert all(c["bytes"] > 0 for c in collects)
