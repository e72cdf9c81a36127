"""Differential tier gating the vectorized modeled-time hot path.

The vectorization (core/congestion.py ``submit``/``submit_batch``, the
``BurstBatch`` columns, the lazy ``TransactionLog`` digests) is only
admissible because it is *bit-exact* against the retained scalar
reference ``LinkModel._submit_scalar``.  This module is the gate:

* three-way differential — scalar loop vs vectorized object path vs
  column-batch path over randomized burst batches × engine priorities ×
  DoS injection, asserting identical per-transaction timing, canonical
  trace bytes, link statistics, and post-run arbiter state (including
  the RNG stream position, so the paths stay interchangeable mid-run);
* the same differential through same-seeded fault perturbation
  (``perturb_bursts`` vs ``perturb_batch``);
* lazy-digest semantics — invalidation on every mutation channel,
  equality with an eager sha256 recompute, memoization, checkpoint/
  restore identity;
* a slow-marked floor check on the committed simspeed benchmark.

When hypothesis is available (CI property lane) the differential also
runs property-based; locally the 200 seeded random cases below cover
the same space deterministically.
"""
import copy
import dataclasses
import hashlib
import struct

import numpy as np
import pytest

from repro.core.congestion import (CongestionConfig, CongestionResult,
                                   LinkModel)
from repro.core.fuzz import FaultPlan
from repro.core.transactions import (BURST_DTYPE, BurstBatch, Transaction,
                                     TransactionLog, materialized_tally)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # container image ships without hypothesis
    HAVE_HYPOTHESIS = False

ENGINES = ("dma_a", "dma_b", "host", "csr")


# ------------------------------------------------------------ case factory

def _random_case(rng):
    """(cfg, batches): each batch is (times, engines, kinds, addrs,
    nbytes, tags) column lists — contention-heavy on purpose."""
    n_eng = int(rng.integers(1, len(ENGINES) + 1))
    cfg = CongestionConfig(
        link_bytes_per_cycle=float(rng.choice([8.0, 64.0, 128.0])),
        base_latency=float(rng.choice([0.0, 40.0, 100.0])),
        dos_prob=float(rng.choice([0.0, 0.2, 0.5])),
        dos_stall=float(rng.choice([50.0, 200.0])),
        per_engine_issue_gap=float(rng.choice([0.0, 1.0, 3.0])),
        seed=int(rng.integers(1 << 31)),
        priorities=tuple((e, int(p)) for e, p in
                         zip(ENGINES, rng.integers(0, 3, len(ENGINES))))
        if rng.random() < 0.5 else (),
    )
    batches = []
    t = 0.0
    for _ in range(int(rng.integers(1, 5))):
        n = int(rng.integers(1, 33))
        t += float(rng.integers(0, 200))
        batches.append((
            (t + rng.integers(0, 50, n).astype(np.float64)).tolist(),
            [ENGINES[int(i)] for i in rng.integers(0, n_eng, n)],
            ["read" if b else "write" for b in rng.integers(0, 2, n)],
            [int(a) for a in rng.integers(0, 1 << 24, n)],
            [int(b) for b in rng.integers(1, 1 << 16, n)],
            ["" if b else "tile" for b in rng.integers(0, 2, n)],
        ))
    return cfg, batches


def _txs(spec):
    times, engines, kinds, addrs, nbs, tags = spec
    return [Transaction(t, e, k, a, nb, tg) for t, e, k, a, nb, tg in
            zip(times, engines, kinds, addrs, nbs, tags)]


def _batch(spec):
    times, engines, kinds, addrs, nbs, tags = spec
    rec = np.zeros(len(times), dtype=BURST_DTYPE)
    rec["time"] = times
    rec["addr"] = addrs
    rec["nbytes"] = nbs
    return BurstBatch(rec, list(engines), list(kinds), list(tags))


def _assert_identical(pair_a, pair_b):
    """Full observable equality of two (LinkModel, TransactionLog) runs:
    trace bytes, profiling-only columns, link statistics, arbiter state
    (rr pointer, horizons, RNG stream position)."""
    (lm_a, log_a), (lm_b, log_b) = pair_a, pair_b
    assert log_a.canonical() == log_b.canonical()
    assert log_a.digest() == log_b.digest()
    # dos/fault_delay are profiling attribution — never rendered, so
    # canonical equality alone would not catch a divergence here
    assert ([(t.dos, t.fault_delay) for t in log_a.txs]
            == [(t.dos, t.fault_delay) for t in log_b.txs])
    ra, rb = lm_a.result(), lm_b.result()
    assert ra.makespan == rb.makespan
    assert ra.per_engine_stall == rb.per_engine_stall
    assert ra.per_engine_busy == rb.per_engine_busy
    assert ra.link_utilization == rb.link_utilization
    assert ra.summary() == rb.summary()
    sa, sb = lm_a.get_state(), lm_b.get_state()
    assert sa["rng"] == sb["rng"], "RNG stream positions diverged"
    assert {k: v for k, v in sa.items() if k != "rng"} \
        == {k: v for k, v in sb.items() if k != "rng"}


def _run_three_ways(cfg, batches):
    runs = []
    for submit in ("scalar", "object", "batch"):
        lm, log = LinkModel(cfg), TransactionLog()
        for spec in batches:
            if submit == "scalar":
                lm._submit_scalar(_txs(spec), log)
            elif submit == "object":
                lm.submit(_txs(spec), log)
            else:
                lm.submit_batch(_batch(spec), log)
        runs.append((lm, log))
    return runs


# ------------------------------------------------------------ differential

def test_differential_random_cases():
    """200 seeded random cases: the two vectorized paths are bit-exact
    against the scalar reference in every observable."""
    for seed in range(200):
        cfg, batches = _random_case(np.random.default_rng(seed))
        scalar, objs, batch = _run_three_ways(cfg, batches)
        _assert_identical(scalar, objs)
        _assert_identical(scalar, batch)


def test_differential_single_engine_rr_pointer():
    """A single-engine batch still advances the round-robin pointer once
    per grant (the scalar loop's bookkeeping), so a later contended batch
    arbitrates identically no matter which path ran first."""
    cfg = CongestionConfig(dos_prob=0.0, seed=1)
    solo = ([0.0] * 7, ["dma_a"] * 7, ["read"] * 7, list(range(7)),
            [64] * 7, [""] * 7)
    contended = ([0.0] * 6, ["dma_a", "dma_b", "host"] * 2, ["read"] * 6,
                 list(range(6)), [64] * 6, [""] * 6)
    scalar, objs, batch = _run_three_ways(cfg, [solo, contended])
    assert scalar[0]._rr == objs[0]._rr == batch[0]._rr
    _assert_identical(scalar, objs)
    _assert_identical(scalar, batch)


def test_differential_priority_contention():
    """Priorities + heavy multi-engine contention exercise the closed-form
    phase computation of the grant order."""
    cfg = CongestionConfig(dos_prob=0.3, seed=9,
                           priorities=(("dma_a", 2), ("host", 1)))
    rng = np.random.default_rng(123)
    batches = []
    for _ in range(6):
        n = 24
        batches.append((
            [0.0] * n,
            [ENGINES[int(i)] for i in rng.integers(0, 4, n)],
            ["read"] * n,
            [int(a) for a in rng.integers(0, 1 << 20, n)],
            [int(b) for b in rng.integers(1, 8192, n)],
            [""] * n,
        ))
    scalar, objs, batch = _run_three_ways(cfg, batches)
    _assert_identical(scalar, objs)
    _assert_identical(scalar, batch)


def test_differential_fault_perturbation():
    """Same-seeded fault plans perturb the object list and the column
    batch draw-for-draw identically: same audit lines, same injected
    events, same post-arbitration trace, same plan RNG position."""
    rates = {"dma_reorder": 0.6, "dma_split": 0.6, "dma_delay": 0.6}
    for seed in range(60):
        cfg, batches = _random_case(np.random.default_rng(1000 + seed))
        plan_o = FaultPlan(seed=seed, rates=rates)
        plan_b = FaultPlan(seed=seed, rates=rates)
        lm_o, log_o = LinkModel(cfg), TransactionLog()
        lm_b, log_b = LinkModel(cfg), TransactionLog()
        for spec in batches:
            txs = plan_o.perturb_bursts(_txs(spec), log_o)
            lm_o._submit_scalar(txs, log_o)
            batch = _batch(spec)
            plan_b.perturb_batch(batch, log_b)
            lm_b.submit_batch(batch, log_b)
        assert log_o.faults == log_b.faults
        assert plan_o.events == plan_b.events
        assert (plan_o.rng.bit_generator.state
                == plan_b.rng.bit_generator.state)
        _assert_identical((lm_o, log_o), (lm_b, log_b))


if HAVE_HYPOTHESIS:
    @st.composite
    def _cases(draw):
        return _random_case(
            np.random.default_rng(draw(st.integers(0, 2 ** 31 - 1))))

    @given(_cases())
    @settings(max_examples=60, deadline=None)
    def test_differential_property(case):
        cfg, batches = case
        scalar, objs, batch = _run_three_ways(cfg, batches)
        _assert_identical(scalar, objs)
        _assert_identical(scalar, batch)
else:
    @pytest.mark.skip(reason="hypothesis not installed; space covered by "
                             "the 200 seeded random cases")
    def test_differential_property():
        pass


# ------------------------------------------------------------- lazy digest

def _eager_digest(log):
    h = hashlib.sha256()
    for line in log.canonical():
        h.update(line.encode())
        h.update(b"\n")
    return h.hexdigest()


def _seeded_log():
    log = TransactionLog()
    log.extend(_txs(_random_case(np.random.default_rng(7))[1][0]))
    return log


def test_digest_invalidates_on_every_mutation_channel():
    log = _seeded_log()
    seen = {log.digest()}
    log.log(Transaction(1.0, "dma_a", "read", 0x10, 64, stall=1.0,
                        complete=2.0))
    seen.add(log.digest())
    log.extend([Transaction(2.0, "host", "write", 0x20, 32, complete=3.0)])
    seen.add(log.digest())
    log.log_batch(_batch(_random_case(np.random.default_rng(8))[1][0]))
    seen.add(log.digest())
    log.violation("late completion")
    seen.add(log.digest())
    log.fault("dma_delay injected")
    seen.add(log.digest())
    assert len(seen) == 6, "every mutation channel must change the digest"
    for d in seen:
        assert len(d) == 64


def test_digest_matches_eager_recompute():
    """The incremental hash is byte-for-byte the pre-vectorization eager
    digest, through any interleaving of object and batch logging."""
    log = _seeded_log()
    assert log.digest() == _eager_digest(log)
    log.log_batch(_batch(_random_case(np.random.default_rng(9))[1][0]))
    log.violation("v1")
    assert log.digest() == _eager_digest(log)
    log.log(Transaction(5.0, "csr", "read", 0x0, 4, complete=6.0))
    log.fault("f1")
    log.log_batch(_batch(_random_case(np.random.default_rng(10))[1][0]))
    assert log.digest() == _eager_digest(log)


def test_digest_memoized_between_mutations():
    log = _seeded_log()
    d1 = log.digest()
    assert log.digest() is d1, "unchanged log must return the memo"
    log.fault("poke")
    assert log.digest() is not d1


def test_digest_lazy_batches_do_not_materialize():
    """digest()/canonical() render straight from the columns — the cheap
    path must not build Transaction objects as a side effect."""
    log = TransactionLog()
    batch = _batch(_random_case(np.random.default_rng(11))[1][0])
    batch.rec["complete"] = batch.rec["time"] + 1.0
    log.log_batch(batch)
    assert log.digest() == _eager_digest(log) != hashlib.sha256().hexdigest()
    assert batch._txs is None, "digest must not materialize lazy segments"
    assert log.n_txs == len(batch)


def test_set_state_restores_digest_identity():
    """Checkpoint/restore round-trips the digest — including restoring
    into a log whose later history diverged, and into a fresh log."""
    log = _seeded_log()
    log.violation("v")
    snap_digest = log.digest()
    state = log.get_state()
    log.log_batch(_batch(_random_case(np.random.default_rng(12))[1][0]))
    log.fault("later fault")
    assert log.digest() != snap_digest
    log.set_state(state)
    assert log.digest() == snap_digest
    fresh = TransactionLog()
    fresh.set_state(state)
    assert fresh.digest() == snap_digest
    assert fresh.canonical() == log.canonical()


def test_batch_timeline_log_aliasing():
    """A batch submitted through the link materializes once: the link
    timeline and the log share the same Transaction objects, exactly as
    object-path submission does."""
    cfg, batches = _random_case(np.random.default_rng(13))
    lm, log = LinkModel(cfg), TransactionLog()
    for spec in batches:
        lm.submit_batch(_batch(spec), log)
    assert len(lm.timeline) == len(log.txs)
    assert all(a is b for a, b in zip(lm.timeline, log.txs))


# ------------------------------------------------------ lazy link result

def _eager_result(lm):
    """``LinkModel.result()`` as it was before its timeline became a
    snapshot: materialize every batch, take the max over the objects and
    copy the list."""
    tl = lm.timeline
    makespan = max((t.complete for t in tl), default=0.0)
    util = ((lm._total_bytes / lm.cfg.link_bytes_per_cycle) / makespan
            if makespan else 0.0)
    return CongestionResult(makespan, dict(lm._stall), dict(lm._busy), util,
                            list(tl))


def _bits(x: float) -> bytes:
    return struct.pack("<d", x)


def _assert_same_result(got, want):
    """Bit-for-bit equal statistics, and a timeline of the same objects
    in the same order."""
    assert type(got.makespan) is float
    assert _bits(got.makespan) == _bits(want.makespan)
    assert _bits(got.link_utilization) == _bits(want.link_utilization)
    # repr of a dict of floats: same keys in the same order, same bits
    assert repr(got.per_engine_stall) == repr(want.per_engine_stall)
    assert repr(got.per_engine_busy) == repr(want.per_engine_busy)
    assert got.summary() == want.summary()
    assert len(got.timeline) == len(want.timeline)
    assert all(a is b for a, b in zip(got.timeline, want.timeline))
    assert got.timeline == want.timeline


@pytest.mark.parametrize("seed", range(8))
def test_link_result_matches_the_eager_result(seed):
    """Two links with one mixed history of object and batch submissions:
    a lazy ``result()`` after every submission, read only at the end,
    equals the eager result taken at that point, and builds no
    Transaction when it is taken."""
    cfg, batches = _random_case(np.random.default_rng(2000 + seed))
    batches = batches * 2
    lazy, eager = LinkModel(cfg), LinkModel(cfg)
    got, want = [], []
    for i, spec in enumerate(batches):
        for lm in (lazy, eager):
            if (i + seed) % 3 == 0:
                lm.submit(_txs(spec))
            else:
                lm.submit_batch(_batch(spec), TransactionLog())
        built = materialized_tally()
        got.append(lazy.result())
        assert materialized_tally() == built
        want.append(_eager_result(eager))
    for g, w in zip(got, want):
        # the two links built their own objects: compare them by value
        assert [dataclasses.astuple(t) for t in g.timeline] == \
            [dataclasses.astuple(t) for t in w.timeline]
        w.timeline = list(g.timeline)
        _assert_same_result(g, w)
    # on one link, the lazy result aliases the objects the link holds
    _assert_same_result(lazy.result(), _eager_result(lazy))


def test_link_result_of_an_empty_link():
    lm = LinkModel(CongestionConfig())
    got = lm.result()
    assert got.makespan == 0.0 and got.link_utilization == 0.0
    assert len(got.timeline) == 0 and list(got.timeline) == []
    _assert_same_result(got, _eager_result(lm))


def test_link_result_timeline_is_a_snapshot():
    """Batches and objects submitted after ``result()`` do not appear in
    its timeline, whether it is first read before or after them; the
    snapshot's objects are the link's own."""
    cfg, batches = _random_case(np.random.default_rng(31))
    lm, log = LinkModel(cfg), TransactionLog()
    first = [_batch(spec) for spec in batches]
    for b in first:
        lm.submit_batch(b, log)
    n = sum(len(b) for b in first)
    r_late, r_early = lm.result(), lm.result()
    assert all(b._txs is None for b in first), "result() materialized"
    assert len(r_late.timeline) == n
    early = list(r_early.timeline)
    lm.submit_batch(_batch(batches[0]), log)
    lm.submit(_txs(batches[-1]), log)
    assert len(r_late.timeline) == len(list(r_late.timeline)) == n
    assert all(a is b for a, b in zip(r_late.timeline, lm.timeline[:n]))
    assert all(a is b for a, b in zip(early, r_late.timeline))
    assert len(lm.result().timeline) > n


def test_fabric_stats_match_the_eager_results():
    """``FabricCluster.congestion_stats()`` and the switch's port stats
    on one small routed, congested fabric equal what eager link results
    give, and neither builds a Transaction to get them."""
    from repro.core import FABRIC_LINK, FabricCluster, ring
    from repro.kernels.systolic_matmul.sweep import (matmul_backends,
                                                     matmul_fabric_firmware)
    fab = FabricCluster(4, congestion=CongestionConfig(dos_prob=0.1, seed=5),
                        link_config=FABRIC_LINK, topology=ring(4))
    fab.register_op("mm", **matmul_backends(tile=16, jit=False))
    matmul_fabric_firmware(fab, "mm", "oracle", size=32, tile=16)
    built = materialized_tally()
    merged = fab.congestion_stats()
    ports = fab.switch.port_stats()
    assert materialized_tally() == built
    per = [(i, _eager_result(d.mem.link)) for i, d in enumerate(fab.devices)]
    want = CongestionResult(
        makespan=max(r.makespan for _, r in per),
        per_engine_stall={f"d{i}/{e}": v for i, r in per
                          for e, v in r.per_engine_stall.items()},
        per_engine_busy={f"d{i}/{e}": v for i, r in per
                         for e, v in r.per_engine_busy.items()},
        link_utilization=sum(r.link_utilization for _, r in per) / len(per),
        timeline=[t for _, r in per for t in r.timeline])
    assert len(want.timeline) > 0
    _assert_same_result(merged, want)
    assert sum(p["flits"] for p in ports.values()) > 0
    for p in fab.switch.ports:
        r = _eager_result(p.link)
        assert ports[p.label] == {
            "stall": sum(r.per_engine_stall.values()),
            "credit_stall": p.credit_stall,
            "busy": sum(r.per_engine_busy.values()),
            "flits": len(r.timeline)}


# ---------------------------------------------------------------- simspeed

@pytest.mark.slow
def test_simspeed_floor():
    """The committed acceptance floor: the vectorized pipeline clears
    >= 5x scenarios/sec on the 200-launch fuzz workload (arbitration +
    per-launch digest checkpoints) vs the scalar reference, and the two
    pipelines' checkpoint digests are identical (asserted inside
    measure())."""
    from benchmarks.bench_simspeed import (SPEEDUP_FLOOR, capture_workload,
                                           measure)
    specs = capture_workload()
    m = measure(specs, reps=2)
    assert m["txs"] > 10_000, "workload capture lost the fuzz stream"
    assert m["speedup"] >= SPEEDUP_FLOOR, m
    assert m["arb_speedup"] > 1.0, m
