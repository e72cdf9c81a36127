"""Always-on counter instrumentation + counter-diff oracle tests
(core/counters.py).

Four pillars, mirroring the module's design rules:

* **Closure** — the link-probed counters close BIT-EXACTLY against the
  profiler's stall attribution (same float folds in the same order), on
  the golden runs and on a profiled CNN workload.
* **Digest identity** — same seed, same counter-stream digest across
  oracle/interpret/compiled backends; same functional digest across
  1/2/4 devices (the counter-diff oracle's two scopes).
* **Sampling invariance** — a stream sampled at 2I is exactly the
  even-boundary subsequence of the stream sampled at I.
* **Oracle economics** — a planted timing-only bug (invisible to the
  output diff) is flagged by the oracle and localized with fewer scalar
  comparisons than a full trace diff, and the CoVerifySession pre-check
  escalates it into the replay-bisection lane.

``check_counter_replay_invariants`` is shared with the hypothesis tier
(tests/test_property.py) — the seeded run here is its pre-validated
numpy fallback for environments without hypothesis.
"""
import functools
import hashlib
import pickle
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from repro.core import CongestionConfig, FireBridge
from repro.core.counters import (CounterBank, CounterSpec, CounterStream,
                                 counter_banks, diff_streams,
                                 functional_digest, functional_totals,
                                 merged_digest, merged_totals, render_tally,
                                 sampling_disabled)
from repro.core.profiler import CATEGORIES
from repro.core.scheduler import CoVerifySession
from repro.kernels.systolic_matmul.sweep import (matmul_backends,
                                                 matmul_firmware)

BACKENDS = ("oracle", "interpret", "compiled")
CONG = CongestionConfig(dos_prob=0.05, seed=7)


def _mm_run(backend: str, interval=None) -> FireBridge:
    """One fixed-seed matmul launch under online congestion."""
    fb = FireBridge(congestion=CONG)
    fb.register_op("mm", **matmul_backends(tile=16, jit=False))
    if interval is not None:
        fb.mem.counters.set_interval(interval)
    matmul_firmware(fb, "mm", backend, size=32, tile=16)
    return fb


# ------------------------------------------------------------------ closure
def _assert_link_closure(bank, ch) -> None:
    """One link-backed bank against its profiler channel: every shared
    quantity must agree BIT-exactly — the probes and the profiler fold
    the same float sequences in the same order."""
    eng = ch.engines
    assert bank.value("bytes_moved") == sum(e.bytes for e in eng.values())
    # grant_stall / busy are the arbiter's per-engine accumulators folded
    # again by the profiler in timeline (= grant) order; the bank's probe
    # sums them in sorted-engine order — replicate that exact fold
    stall = 0.0
    for name in sorted(eng):
        stall += eng[name].grant_stall
    assert bank.value("stall_cycles") == stall
    busy = 0.0
    for name in sorted(eng):
        busy += eng[name].busy
    assert bank.value("busy_cycles") == busy
    assert bank.value("dos_cycles") == ch.breakdown.cycles["dos"]
    # stall-category closure: the six categories sum (left fold in
    # CATEGORIES order) exactly to the channel horizon == the bank's
    # sampled clock
    total = 0.0
    for c in CATEGORIES:
        total += ch.breakdown.cycles[c]
    assert total == ch.horizon == bank.value("cycles")


def test_counter_closure_single_device_golden():
    import test_golden_traces as gt
    run = gt.single_device_run()
    fb = run.recording.target
    prof = fb.profiler("closure")
    _assert_link_closure(fb.mem.counters, prof.channel("ddr"))


def test_counter_closure_routed_torus_golden():
    """Every fabric bank of the 8-device routed torus golden run — host
    attachment, device ports, and all credit-flow-controlled switch
    ports — closes against its profiler channel."""
    import test_golden_traces as gt
    run = gt.fabric_torus_all_reduce_run()
    fab = run.recording.target
    prof = fab.profiler("closure")
    checked = 0
    for bank in fab._counter_banks:
        if bank.name.startswith("fabric/sw:"):
            ch = prof.channel("fabric/" + bank.name[len("fabric/sw:"):])
        else:
            ch = prof.channel(bank.name)
        _assert_link_closure(bank, ch)
        checked += 1
    assert checked >= 1 + 8 + 8          # host + ports + >=8 switch ports


def test_counter_closure_profiled_cnn():
    """The profiled Fig. 8 CNN workload (op marks active): attribution
    still closes bit-exactly against the always-on counters."""
    from benchmarks.cnn_driver import run_cnn, small_cnn_specs
    cong = CongestionConfig(
        link_bytes_per_cycle=64.0, dos_prob=0.02, seed=7,
        priorities=(("dma_input", 2), ("dma_output", 1),
                    ("dma_weights", 0)))
    fb = run_cnn(small_cnn_specs(16), backend="oracle", congestion=cong,
                 profile=True)
    prof = fb.profiler("closure")
    _assert_link_closure(fb.mem.counters, prof.channel("ddr"))


# ----------------------------------------------------------- digest identity
def test_backend_digest_identity():
    """Same seed ⇒ byte-identical counter streams across all three
    backends: modeled timing is backend-invariant, and the digest is the
    cheap witness the oracle compares."""
    runs = {be: _mm_run(be) for be in BACKENDS}
    digests = {be: merged_digest(counter_banks(fb))
               for be, fb in runs.items()}
    assert len(set(digests.values())) == 1, digests
    # the canonical streams themselves are line-identical, not just
    # hash-identical
    ref = runs["oracle"].mem.counters.canonical()
    for be in BACKENDS[1:]:
        assert runs[be].mem.counters.canonical() == ref
    assert runs["oracle"].mem.counters.stream.n_samples > 0


@functools.lru_cache(maxsize=None)
def _cluster(n: int):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, smoke
    from repro.models import init_params
    from repro.models.transformer import RunFlags
    from repro.serving.cluster import ClusterServingEngine
    cfg = smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    return ClusterServingEngine(
        cfg, params, n_devices=n, max_slots=2, max_len=32, prompt_pad=8,
        flags=RunFlags(attn_impl="chunked", q_chunk=16, kv_chunk=16))


@pytest.mark.slow
def test_scale_functional_digest_identity():
    """The cross-scale side of the oracle: the same (unique-rid) request
    storm on 1/2/4-device clusters retires identical functional totals —
    doorbells, requests, tokens — while the full per-bank streams differ
    per scale (more engines, different timing)."""
    import test_golden_traces as gt
    from repro.core import replay as rp
    reqs = gt._storm_requests()          # rids 0..5, all unique
    functional, full = {}, {}
    for n in (1, 2, 4):
        clu = _cluster(n)

        def factory(clu=clu):
            clu.reset(None)
            return clu

        sess = rp.DebugSession(factory, checkpoint_interval=0,
                               label=f"counters_scale_x{n}")
        rp.record_serving_storm(sess, reqs)
        banks = counter_banks(clu)
        functional[n] = functional_digest(banks)
        full[n] = merged_digest(banks)
        totals = functional_totals(banks)
        assert totals["doorbells"] == len(reqs)
        assert totals["requests_retired"] == len(reqs)
        assert totals["tokens_retired"] > 0
    assert len(set(functional.values())) == 1, functional
    assert len(set(full.values())) == 3, full


# ------------------------------------------------------- sampling invariance
def test_sampling_interval_invariance():
    """A stream sampled at 2I is exactly the even-boundary subsequence of
    the stream sampled at I — boundary times come from multiplication and
    rows are sample-and-hold, so coarser sampling loses rows, never
    changes them."""
    fine = _mm_run("oracle", interval=128.0).mem.counters.stream
    coarse = _mm_run("oracle", interval=256.0).mem.counters.stream
    assert fine.n_samples > coarse.n_samples > 0
    sub = [(t, r) for t, r in zip(fine.times, fine.rows) if t % 256.0 == 0.0]
    assert sub == list(zip(coarse.times, coarse.rows))


def test_sampling_disabled_is_scoped():
    with sampling_disabled():
        fb = _mm_run("oracle")
        assert fb.mem.counters.stream.n_samples == 0
    assert _mm_run("oracle").mem.counters.stream.n_samples > 0


# --------------------------------------------------------- state round-trip
def test_counter_state_roundtrip():
    """get_state/set_state moves a bank between structurally identical
    owners bit-exactly, and the epoch bump keeps digests honest after a
    restore (no stale memo)."""
    bank = _mm_run("oracle").mem.counters
    d0 = bank.digest()
    fresh = FireBridge(congestion=CONG).mem.counters
    assert fresh.stream.n_samples == 0
    fresh.set_state(bank.get_state())
    assert fresh.canonical() == bank.canonical()
    assert fresh.digest() == bank.digest() == d0
    # restoring over an already-digested bank must recompute, not serve
    # the memo for the old epoch
    bank.set_state(bank.get_state())
    assert bank.digest() == d0


# ------------------------------------------- grouped rendering vs per row
def _reference_lines(stream) -> list:
    """The per-row renderer the grouped one replaced: every row formats
    its time and all of its values."""
    def fmt(values):
        return " ".join(f"{v:.6f}" if s.is_float else str(v)
                        for s, v in zip(stream.specs, values))
    return [f"{t:.6f} {fmt(row)}" for t, row in zip(stream.times, stream.rows)]


def _reference_digest(stream) -> str:
    h = hashlib.sha256()
    for line in _reference_lines(stream):
        h.update(line.encode())
        h.update(b"\n")
    return h.hexdigest()


def _check_stream(stream) -> None:
    assert stream.canonical() == _reference_lines(stream)
    assert stream.digest() == _reference_digest(stream)


def _probed_bank(interval=256.0):
    """A bank of one int and one float probe over a mutable state, plus
    an owned int counter."""
    state = {"n": 0, "c": 0.0}
    bank = CounterBank("t", interval)
    bank.register(CounterSpec("n", "events"), lambda: state["n"])
    bank.register(CounterSpec("c", "cycles"), lambda: state["c"])
    bank.register(CounterSpec("owned", "events"))
    return bank, state


def _case_boundaries():
    """Ticks that cross no boundary, one, and many, with values that
    change between ticks and fractional floats; checked once at the end
    and again after one more tick."""
    bank, st = _probed_bank()
    for now, n, c in ((100.0, 1, 1 / 3), (256.0, 2, 2.5), (300.0, 3, 3.25),
                      (511.9, 4, 1e9 / 7), (512.0, 4, 1e9 / 7),
                      (256.0 * 70 + 3, 5, 0.1), (256.0 * 70 + 4, 6, 0.2),
                      (256.0 * 4000, 7, 12345.678901234)):
        st["n"], st["c"] = n, c
        bank.inc("owned", 2)
        bank.tick(now)
    _check_stream(bank.stream)
    st["n"] += 1
    bank.tick(256.0 * 4003)
    return [bank.stream]


def _case_digest_then_more():
    """digest(), more ticks, digest() again: the running hash resumes
    where the last digest stopped, also in the middle of equal values."""
    bank, st = _probed_bank()
    for k in range(1, 40):
        if k % 3 == 0:
            st["c"] += 0.75
        st["n"] = k // 5
        bank.tick(256.0 * k * k / 4)
        if k % 7 == 0:
            _check_stream(bank.stream)
    return [bank.stream]


def _case_interval_subsequence():
    """A non-integer interval at I, 2I and 4I over one tick sequence:
    each stream renders as the reference does, and the coarser streams
    are the fine stream's every second and every fourth row."""
    interval = 0.1 * 3
    banks = []
    for mult in (1, 2, 4):
        bank, st = _probed_bank(interval * mult)
        for now in np.cumsum(np.random.default_rng(5).uniform(0, 9, 60)):
            st["n"] += 1
            st["c"] = float(now) / 3
            bank.tick(float(now))
        banks.append(bank.stream)
    fine, two, four = banks
    assert fine.n_samples > two.n_samples > four.n_samples > 0
    assert _reference_lines(two) == _reference_lines(fine)[1::2]
    assert _reference_lines(four) == _reference_lines(fine)[3::4]
    return banks


def _case_set_state_then_more():
    """set_state back to a checkpoint, then more ticks; and a fresh bank
    restored from a pickled copy, whose rows are equal but not
    identical."""
    bank, st = _probed_bank()
    for k in range(1, 9):
        st["n"] = k
        bank.tick(256.0 * 3 * k)
    saved = bank.get_state()
    _check_stream(bank.stream)
    st["n"] = 100
    bank.tick(256.0 * 40)
    _check_stream(bank.stream)
    bank.set_state(saved)
    st["n"] = 200
    bank.tick(256.0 * 30)
    fresh, _ = _probed_bank()
    fresh.set_state(pickle.loads(pickle.dumps(saved)))
    assert fresh.stream.rows[0] is not saved["stream"]["rows"][0]
    _check_stream(fresh.stream)
    fresh.tick(256.0 * 30)
    return [bank.stream, fresh.stream]


def _case_equal_not_identical():
    """Rows that are equal but not the same tuple, including equal values
    that render differently: -0.0 against 0.0, and 1 against 1.0 and
    True in an integer column."""
    stream = CounterStream((CounterSpec("n", "events"),
                            CounterSpec("c", "cycles")))
    rows = [(1, 0.0), (1, 0.0), (1, -0.0), (1, -0.0), (1.0, 0.0),
            (True, 0.0), (1, 0.0), (2, 1.5), (2, 1.5), (2, 1.5)]
    for k, row in enumerate(rows, 1):
        stream.append(256.0 * k, tuple(list(row)))
    _check_stream(stream)
    rows0, runs0 = render_tally()
    stream.canonical()
    rows1, runs1 = render_tally()
    assert (rows1 - rows0, runs1 - runs0) == (10, 6)
    return [stream]


@pytest.mark.parametrize("case", [
    _case_boundaries, _case_digest_then_more, _case_interval_subsequence,
    _case_set_state_then_more, _case_equal_not_identical])
def test_grouped_render_equals_per_row_reference(case):
    """The grouped renderer (one values suffix per run of rows) gives
    the per-row reference's lines and digest on every stream, and
    formats one suffix per tick, not per row."""
    for stream in case():
        rows0, runs0 = render_tally()
        lines = stream.canonical()
        rows1, runs1 = render_tally()
        assert lines == _reference_lines(stream)
        assert stream.digest() == _reference_digest(stream)
        assert rows1 - rows0 == stream.n_samples
        assert runs1 - runs0 <= len({id(r) for r in stream.rows})


# ------------------------------------------------ replay/monotone invariants
def _bridge_session(case, interval):
    """Recorded bridge session for the replay invariants — the same op
    vocabulary as the hypothesis tier's ``replay_programs`` strategy."""
    from repro.core import replay as rp
    from repro.core.fuzz import FaultPlan
    shapes, ops, cong_seed, fault_seed = case

    def factory():
        return FireBridge(
            congestion=CongestionConfig(dos_prob=0.2, seed=cong_seed,
                                        max_burst_bytes=64),
            fault_plan=FaultPlan(seed=fault_seed))

    def program(rec):
        for i, (m, n) in enumerate(shapes):
            rec.do("alloc", f"b{i}", (m, n), np.float32)
        for kind, b, v in ops:
            name = f"b{b}"
            m, n = shapes[b]
            if kind == "dev_read":
                rec.do("dev_read", name, "dma")
            elif kind == "dev_write":
                rec.do("dev_write", name,
                       np.full((m, n), float(v % 97), np.float32), "dma")
            elif kind == "host_write":
                rec.do("host_write", name,
                       np.full((m, n), float(v % 89), np.float32))
            else:
                rec.do("log_burst_list",
                       [("eng_a", "read", 0x1000, 1 + v % 512),
                        ("eng_b", "write", 0x2000, 1 + v % 256)], None)

    return rp.DebugSession(factory, checkpoint_interval=interval), program


def check_counter_replay_invariants(case, interval, lo, hi) -> None:
    """Shared property checker (hypothesis tier + seeded fallback):

    * every ``monotone`` counter is non-decreasing across samples;
    * replaying any ``[lo, hi)`` window regenerates a counter stream that
      is an exact prefix of the recorded one (the restored checkpoint
      carries the stream prefix; re-run ops regenerate the suffix
      bit-identically);
    * full-range replay regenerates the entire stream.
    """
    sess, program = _bridge_session(case, interval)
    rec = sess.record(program)
    banks = counter_banks(rec.target)
    for b in banks:
        for j, s in enumerate(b.specs):
            if not s.monotone:
                continue
            col = [row[j] for row in b.stream.rows]
            assert all(x <= y for x, y in zip(col, col[1:])), \
                f"{b.name}/{s.name} decreased across samples"
    orig = [b.canonical() for b in banks]
    lo, hi = min(lo, rec.n_ops), min(hi, rec.n_ops)
    w = sess.replay(rec, lo, hi)
    for b, ref in zip(counter_banks(w.target), orig):
        live = b.canonical()
        assert live == ref[:len(live)], f"{b.name}: replay diverged"
    w = sess.replay(rec, 0, rec.n_ops)
    assert [b.canonical() for b in counter_banks(w.target)] == orig


def test_counter_replay_invariants_randomized():
    """Seeded numpy fallback of the hypothesis property
    (tests/test_property.py::test_counter_stream_replay_and_monotonicity)
    — pre-validated here so the property tier never guards an unexercised
    checker."""
    for seed in range(4):
        rng = np.random.default_rng(seed)
        shapes = [(int(rng.integers(1, 24)), 4)
                  for _ in range(rng.integers(1, 4))]
        kinds = ("dev_read", "dev_write", "host_write", "burst")
        ops = [(kinds[rng.integers(0, 4)],
                int(rng.integers(0, len(shapes))),
                int(rng.integers(0, 2 ** 16)))
               for _ in range(rng.integers(4, 18))]
        case = (shapes, ops, int(rng.integers(0, 2 ** 20)),
                int(rng.integers(0, 2 ** 20)))
        n = len(shapes) + len(ops)
        lo = int(rng.integers(0, n + 1))
        hi = int(rng.integers(lo, n + 1))
        check_counter_replay_invariants(case, 1 + seed % 4, lo, hi)


# -------------------------------------------------- the counter-diff oracle
def _stream_workload(fb: FireBridge, rogue: bool) -> None:
    """Fixed DMA workload; ``rogue`` plants one extra early read — a
    timing-only perturbation that never changes functional state."""
    rng = np.random.default_rng(7)
    a = rng.normal(size=(32, 32)).astype(np.float32)
    fb.mem.alloc("a", a.shape, np.float32)
    fb.mem.host_write("a", a)
    if rogue:
        fb.mem.dev_read("a", engine="dma_rogue")
    for _ in range(12):
        fb.mem.dev_read("a", engine="dma")
        fb.mem.dev_write("a", a, engine="dma")


def test_counter_diff_localizes_cheaper_than_trace_diff():
    """The oracle's economics: the planted timing bug is found in fewer
    scalar comparisons than a full trace-line diff would spend — the
    ~10x-cheaper pre-check the sweep runs before output comparison."""
    good, bad = FireBridge(congestion=CONG), FireBridge(congestion=CONG)
    _stream_workload(good, rogue=False)
    _stream_workload(bad, rogue=True)
    diff, comparisons = diff_streams(counter_banks(good),
                                     counter_banks(bad))
    assert diff is not None
    assert diff.bank == "ddr"
    assert "counter divergence" in diff.render()
    trace_lines = (len(good.log.canonical()) + len(bad.log.canonical()))
    assert comparisons < trace_lines, \
        f"oracle spent {comparisons} vs {trace_lines} trace lines"
    # identical runs: no diff, and confirming equality is still cheap
    twin = FireBridge(congestion=CONG)
    _stream_workload(twin, rogue=False)
    none_diff, _ = diff_streams(counter_banks(good), counter_banks(twin))
    assert none_diff is None


def _buggy_firmware(fb, op, backend, *, size, tile=16):
    """matmul firmware with a planted backend-conditional timing bug:
    one backend issues an extra DMA read.  Outputs are unchanged, so the
    output diff alone passes — only the counter oracle sees it."""
    matmul_firmware(fb, op, backend, size=size, tile=tile)
    if backend == "interpret":
        fb.mem.dev_read("a", engine="dma_rogue")


def test_sweep_counter_oracle_clean_pass():
    """Clean sweep: every cell carries the oracle payload, same-timing-key
    digests agree, and no mismatch is recorded."""
    sess = CoVerifySession(matmul_firmware, congestion=CONG)
    sess.register_op("mm", **matmul_backends(tile=16, jit=False))
    sess.add_sweep("mm", ("oracle", "interpret"),
                   [{"size": 32, "tile": 16}])
    rep = sess.run(max_workers=1, bisect_failures=False)
    assert rep.passed and rep.counter_mismatches == {}
    cs = [r.counters for r in rep.cells]
    assert all(c is not None for c in cs)
    assert cs[0]["timing_key"] == cs[1]["timing_key"]
    assert cs[0]["digest"] == cs[1]["digest"]
    assert cs[0]["functional"] == cs[1]["functional"]
    assert cs[0]["totals"]["transactions"] > 0


def test_sweep_counter_oracle_flags_planted_timing_bug():
    """The planted bug fails the sweep via counter_mismatches (kind
    ``stream``) even though the output diff PASSES, and the mismatch is
    escalated into the replay-bisection lane."""
    sess = CoVerifySession(_buggy_firmware, congestion=CONG)
    sess.register_op("mm", **matmul_backends(tile=16, jit=False))
    sess.add_sweep("mm", ("oracle", "interpret"), [{"size": 32}])
    rep = sess.run(max_workers=1)
    assert not rep.passed
    (lab, m), = rep.counter_mismatches.items()
    assert m["kind"] == "stream"
    assert set(m["pair"]) == {"oracle", "interpret"}
    assert set(m["totals"]) == {"oracle", "interpret"}
    # the timing-only bug is INVISIBLE to the output diff — this is
    # exactly the class of divergence the oracle exists to catch
    assert all(e.passed for e in rep.equivalence.values())
    assert lab in rep.divergences
    assert "stream mismatch" in \
        str(rep.summary()["counter_mismatches"].values())


def test_sweep_digest_identity_across_backends_and_scales():
    """The acceptance bar, end to end through the sweep: one seed, two
    backends, devices 1/2/4 — within every device count the full counter
    stream digests are identical across backends (no fault plan, so all
    cells of a scale share a timing key), and no oracle mismatch fires."""
    from repro.kernels.systolic_matmul.sweep import matmul_fabric_firmware
    sess = CoVerifySession(matmul_firmware, congestion=CONG,
                           fabric_firmware=matmul_fabric_firmware)
    sess.register_op("mm", **matmul_backends(tile=16, jit=False))
    sess.add_sweep("mm", ("oracle", "interpret"),
                   [{"size": 32, "tile": 16}], devices=(1, 2, 4))
    rep = sess.run(max_workers=1, bisect_failures=False)
    assert rep.passed and rep.counter_mismatches == {}
    by_key = {}
    for r in rep.cells:
        assert r.counters is not None
        by_key.setdefault(r.counters["timing_key"],
                          set()).add(r.counters["digest"])
    assert sorted(k[0] for k in by_key) == [1, 2, 4]
    for key, digests in by_key.items():
        assert len(digests) == 1, f"stream digests diverge at {key}"
    assert len({r.counters["functional"] for r in rep.cells}) == 1
