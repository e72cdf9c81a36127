"""core/spans.py: a span times itself whether or not a profiler runs, and
the session's report takes its times from its spans
(benchmarks/chip/tests/test_bench_spans.py reads them from a trace)."""
import time

import pytest

from repro.core import CoVerifySession
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
