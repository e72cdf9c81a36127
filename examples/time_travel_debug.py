"""Time-travel debugging walkthrough (core/replay.py): record a
fault-injected co-verification run, replay an arbitrary window
bit-identically, then let a failing sweep localize its own divergence by
checkpoint bisection.

Every line printed is deterministic (modeled clocks, seeded faults,
content digests — no wall time), so the transcript in
docs/replay.md is verified verbatim against this output by
tests/test_replay.py::test_docs_transcript_matches_example.

    PYTHONPATH=src python examples/time_travel_debug.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core import CongestionConfig, CoVerifySession, FireBridge
from repro.core import replay as rp
from repro.core.fuzz import FaultPlan
from repro.kernels.systolic_matmul.sweep import (matmul_backends,
                                                 matmul_firmware)

CONG = CongestionConfig(dos_prob=0.05, seed=7)


def planted_bug(table: dict) -> dict:
    """``table`` with a divergent ``interpret`` backend: the oracle's own
    output plus a planted +1.0 at ``c[1, 2]``.  Both sides of the
    divergence are then the oracle's bits, so the digests printed below
    do not depend on how an interpret-mode kernel rounds."""
    oracle = table["oracle"]

    def buggy(a, b):
        out = np.array(oracle(a, b))
        out[1, 2] += 1.0
        return out
    return dict(table, interpret=buggy)


def main() -> None:
    # ---- 1. record a fault-injected run as a deterministic timeline
    table = matmul_backends(tile=16, jit=False)

    def factory():
        fb = FireBridge(congestion=CONG, fault_plan=FaultPlan(seed=3))
        fb.register_op("mm", **table)
        return fb

    sess = rp.DebugSession(factory, checkpoint_interval=3, label="run")

    def program(rec):
        for j, size in enumerate((32, 48, 32)):
            rng = np.random.default_rng(size)
            a = rng.normal(size=(size, size)).astype(np.float32)
            b = rng.normal(size=(size, size)).astype(np.float32)
            rec.do("alloc", f"a{j}", a.shape, np.float32)
            rec.do("alloc", f"b{j}", b.shape, np.float32)
            rec.do("alloc", f"c{j}", (size, size), np.float32)
            rec.do("host_write", f"a{j}", a)
            rec.do("host_write", f"b{j}", b)
            rec.do("launch", "mm", "oracle", (f"a{j}", f"b{j}"),
                   (f"c{j}",), "mm", None, {})

    rec = sess.record(program)
    print(f"recorded: {rec.n_ops} ops, "
          f"checkpoints at {[c.op_index for c in rec.checkpoints]}, "
          f"{len(rec.lines)} trace lines, "
          f"{len(rec.preamble)} construction line(s)")
    print(f"log digest: {rec.log_digest[:16]}")

    # ---- 2. bit-identical window replay from the nearest checkpoint
    lo, hi = 10, rec.n_ops
    w = sess.replay(rec, lo, hi)
    print(f"replayed window [{lo}, {hi}) from checkpoint "
          f"@op {w.from_checkpoint}: "
          f"{'IDENTICAL' if w.lines == rec.window_lines(lo, hi) else 'DIVERGED'}"
          f" ({len(w.lines)} lines, digest "
          f"{'match' if w.digest() == rec.window_digest(lo, hi) else 'MISMATCH'})")

    # ---- 3. a failing sweep bisects its own divergence
    sweep = CoVerifySession(matmul_firmware, congestion=CONG)
    sweep.register_op("mm", **planted_bug(table))
    sweep.add_sweep("mm", ("oracle", "interpret"),
                    [{"size": 32, "tile": 16}])
    report = sweep.run(max_workers=1)
    print(f"sweep passed: {report.passed}")
    (d,) = report.divergences.values()
    print(d.render())


if __name__ == "__main__":
    main()
