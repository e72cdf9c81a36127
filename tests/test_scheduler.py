"""CoVerifySession: batched sweep execution, cross-backend grouping,
divergence localization, congestion-aware cells, per-tile kernel burst
lists (core/scheduler.py; paper Fig. 5 batched lane)."""
import numpy as np
import pytest

from repro.core import CongestionConfig, CoVerifySession
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.mamba2_scan import ops as ssd_ops
from repro.kernels.rwkv6_wkv import ops as wkv_ops
from repro.kernels.systolic_matmul.sweep import (matmul_backends,
                                                 matmul_firmware)

_firmware = matmul_firmware


def _session(bug: bool = False, congestion=None) -> CoVerifySession:
    table = matmul_backends(jit=False)

    def interp(a, b):
        out = np.array(table["interpret"](a, b))
        if bug:
            out[1, 2] += 1.0                  # injected hardware bug
        return out

    sess = CoVerifySession(_firmware, congestion=congestion)
    sess.register_op("mm", oracle=table["oracle"], interpret=interp)
    return sess


def test_sweep_runs_all_cells_and_groups():
    sess = _session()
    cells = sess.add_sweep("mm", ("oracle", "interpret"),
                           [{"size": 32}, {"size": 64}])
    assert len(cells) == 4
    report = sess.run(max_workers=2)
    assert report.passed
    assert len(report.cells) == 4
    assert len(report.equivalence) == 2       # one group per config
    assert all(r.seconds > 0 for r in report.cells)
    assert report.summary()["cells"] == 4
    assert len(report.to_rows()) == 5         # header + 4 cells


def test_sweep_localizes_divergence_per_group():
    sess = _session(bug=True)
    sess.add_sweep("mm", ("oracle", "interpret"), [{"size": 32}])
    report = sess.run()
    assert not report.passed
    (eq,) = report.equivalence.values()
    d = eq.divergences[0]
    assert d.leaf_path == "c" and d.index == (1, 2)
    assert abs(d.max_abs_err - 1.0) < 1e-3


def test_sweep_cells_carry_online_congestion():
    cong = CongestionConfig(seed=3, priorities=(("dma_a", 1),))
    sess = _session(congestion=cong)
    sess.add_sweep("mm", ("oracle",), [{"size": 64}])
    report = sess.run()
    (r,) = report.cells
    assert r.congestion is not None and r.congestion.makespan > 0
    assert sum(r.congestion.per_engine_stall.values()) > 0
    assert r.bridge_time >= r.congestion.makespan


def test_config_key_groups_equal_ndarray_configs():
    """Regression: _config_key used repr(v), so equal-valued numpy-array
    configs landed in different equivalence groups and the cross-backend
    diff was silently skipped.  Structural hashing must group them."""
    from repro.core.scheduler import _config_key

    def firmware(fb, op, backend, *, scale):
        fb.mem.alloc("c", scale.shape, np.float32)
        fb.launch(op, backend, [], ["c"], scale=scale)

    table = matmul_backends(jit=False)

    def interp(scale):
        return np.asarray(table["oracle"](scale, np.eye(2,
                                                        dtype=np.float32)))
    sess = CoVerifySession(firmware)
    sess.register_op("sc", oracle=lambda scale: scale @ np.eye(
        2, dtype=np.float32), interpret=interp)
    # two *distinct but equal* ndarray objects, one per backend
    sess.add_cell("sc", "oracle",
                  {"scale": np.ones((2, 2), np.float32)})
    sess.add_cell("sc", "interpret",
                  {"scale": np.ones((2, 2), np.float32)})
    report = sess.run(max_workers=1)
    # one group containing BOTH backends => the diff actually ran
    assert len(report.equivalence) == 1
    (eq,) = report.equivalence.values()
    assert set(eq.backends) == {"oracle", "interpret"}
    # and unequal arrays must NOT collide (repr truncation used to)
    big_a = {"scale": np.arange(4000, dtype=np.float32)}
    big_b = {"scale": np.arange(4000, dtype=np.float32)}
    big_b["scale"][2000] += 1.0          # differs deep inside the "..."
    assert _config_key(big_a) != _config_key(big_b)
    assert _config_key(big_a) == _config_key(
        {"scale": np.arange(4000, dtype=np.float32)})


def test_config_key_groups_equal_dataclass_configs():
    import dataclasses

    from repro.core.scheduler import _config_key

    @dataclasses.dataclass
    class Tile:
        bm: int
        weights: np.ndarray

    a = {"tile": Tile(32, np.ones(3, np.float32))}
    b = {"tile": Tile(32, np.ones(3, np.float32))}
    c = {"tile": Tile(32, np.zeros(3, np.float32))}
    assert _config_key(a) == _config_key(b)
    assert _config_key(a) != _config_key(c)
    # containers recurse
    assert _config_key({"x": [np.ones(2), 3]}) == \
        _config_key({"x": [np.ones(2), 3]})
    # numpy scalars hash by bit pattern: NaN configs must still group
    assert _config_key({"x": np.float32("nan")}) == \
        _config_key({"x": np.float32("nan")})
    assert _config_key({"x": np.float32(1)}) != \
        _config_key({"x": np.float64(1)})


def test_cell_error_is_contained():
    sess = _session()
    sess.register_op("boom", oracle=lambda *a: (_ for _ in ()).throw(
        RuntimeError("dead op")))
    sess.add_cell("mm", "oracle", {"size": 32})
    sess.add_cell("boom", "oracle", {"size": 32})
    report = sess.run(max_workers=2)
    assert not report.passed
    errs = [r for r in report.cells if r.error]
    assert len(errs) == 1 and "dead op" in errs[0].error


def test_add_cell_rejects_unknown_op():
    sess = _session()
    with pytest.raises(KeyError):
        sess.add_cell("nope", "oracle")


def test_sequential_and_batched_agree():
    sess = _session()
    sess.add_sweep("mm", ("oracle", "interpret"),
                   [{"size": 32}, {"size": 64}])
    seq = sess.run(max_workers=1)
    bat = sess.run(max_workers=4)
    assert seq.passed and bat.passed
    for a, b in zip(seq.cells, bat.cells):
        assert a.cell.label == b.cell.label
        for name in a.outputs:
            np.testing.assert_array_equal(a.outputs[name], b.outputs[name])


def test_a_report_keeps_its_outputs_across_sweeps():
    """Each cell lends its finished target's DDR to the report: a second
    sweep of the same session builds new targets, so it leaves the first
    report's outputs as they were and shares no memory with them."""
    sess = _session()
    sess.add_sweep("mm", ("oracle", "interpret"), [{"size": 32}])
    first = sess.run(max_workers=2)
    held = [{n: a.copy() for n, a in r.outputs.items()}
            for r in first.cells]
    second = sess.run(max_workers=2)
    assert first.passed and second.passed
    for r, kept in zip(first.cells, held):
        assert set(r.outputs) == set(kept)
        for name, arr in r.outputs.items():
            np.testing.assert_array_equal(arr, kept[name])
            assert not any(np.shares_memory(arr, b)
                           for s in second.cells
                           for b in s.outputs.values())


def test_report_is_independent_of_thread_completion_order():
    """Satellite regression: on a seeded 20-cell sweep (faults + online
    congestion + a coverage sink + one planted divergence), report rows,
    equivalence verdicts, divergence attachments, and the merged coverage
    model must be byte-identical between ``max_workers=1`` and
    ``max_workers=8`` — thread completion order may change wall-clock
    only, never any reported artifact (the run-farm digests depend on
    this)."""
    from repro.core import CoverageModel
    from repro.core.fuzz import FaultPlan

    configs = ([{"size": 32, "tile": t} for t in (4, 8, 16, 32)]
               + [{"size": 64, "tile": t} for t in (8, 16, 32, 64)]
               + [{"size": 96, "tile": 32}, {"size": 96, "tile": 48}])

    def run(max_workers):
        table = matmul_backends(jit=False)

        def interp(a, b):
            out = np.array(table["interpret"](a, b))
            if out.shape[0] == 96:
                out[1, 2] += 1.0          # planted divergence, size-96 only
            return out

        cov = CoverageModel()
        sess = CoVerifySession(_firmware,
                               congestion=CongestionConfig(seed=7),
                               fault_plan=FaultPlan(seed=11),
                               coverage=cov)
        sess.register_op("mm", oracle=table["oracle"], interpret=interp)
        cells = sess.add_sweep("mm", ("oracle", "interpret"), configs)
        assert len(cells) == 20
        return sess.run(max_workers=max_workers), cov

    seq, cov_seq = run(1)
    par, cov_par = run(8)
    # modeled rows: byte-identical once the wall-clock column is masked
    assert seq.to_rows(wall=False) == par.to_rows(wall=False)
    # equivalence verdicts + localized divergence attachments
    s, p = seq.summary(), par.summary()
    for k in ("cells", "groups", "passed", "failures", "divergences"):
        assert s[k] == p[k], k
    assert not seq.passed and len(s["divergences"]) == 2
    # per-cell fault traces fork from the cell label, not pool order
    assert [[e.key() for e in r.faults] for r in seq.cells] == \
        [[e.key() for e in r.faults] for r in par.cells]
    # merged functional coverage: exact counts, not just covered-bins
    assert cov_seq.counts == cov_par.counts
    assert cov_seq.covered("burst_size"), cov_seq.holes("burst_size")
    assert sum(cov_seq.counts["congestion"].values()) > 0
    assert sum(cov_seq.counts["fault_kind"].values()) > 0
    assert seq.coverage is cov_seq and par.coverage is cov_par


# ------------------------------------------------- per-tile burst lists
def _check_bursts(txs, n_engines_min=2):
    assert txs, "burst list is empty"
    assert all(nb > 0 and addr >= 0 for _, _, addr, nb in txs)
    assert len({e for e, _, _, _ in txs}) >= n_engines_min
    kinds = {k for _, k, _, _ in txs}
    assert kinds <= {"read", "write"} and "read" in kinds


def test_flash_burst_list_per_tile():
    txs = fa_ops.transactions(2, 4, 256, 256, 64, bq=128, bk=128,
                              causal=True, dtype_bytes=2)
    _check_bursts(txs, 4)
    # causal skips the upper-triangular KV tiles: fewer k reads than full
    full = fa_ops.transactions(2, 4, 256, 256, 64, bq=128, bk=128,
                               causal=False, dtype_bytes=2)
    n_k = sum(1 for e, _, _, _ in txs if e == "dma_k")
    n_k_full = sum(1 for e, _, _, _ in full if e == "dma_k")
    assert n_k < n_k_full
    # per-tile: every burst is one tile, not a whole buffer
    assert max(nb for _, _, _, nb in txs) == 128 * 64 * 2


def test_ssd_burst_list_per_tile():
    txs = ssd_ops.transactions(2, 256, 16, 32, 64, chunk=128, hb=8)
    _check_bursts(txs, 4)
    # state writes once per (batch, head block), not per chunk
    n_state = sum(1 for e, _, _, _ in txs if e == "dma_state")
    assert n_state == 2 * (16 // 8)
    # grouped and head-major (bf16 x/B/C/y, f32 dt/state): a head block
    # reads only its own group's B/C chunks, and x, dt and y move once
    B, L, H, P, N, G, hb, cl = 2, 256, 16, 32, 64, 2, 4, 128
    txs = ssd_ops.transactions(B, L, H, P, N, G=G, chunk=cl, hb=hb,
                               dtype_bytes=2)
    _check_bursts(txs, 4)
    for engine, nbytes in (("dma_x", B * H * L * P * 2),
                           ("dma_y", B * H * L * P * 2),
                           ("dma_dt", B * H * L * 4),
                           ("dma_state", B * H * P * N * 4)):
        assert sum(n for e, _, _, n in txs if e == engine) == nbytes
    bc = L * N * 2                          # one (batch, group)'s B or C
    b_base = B * H * L * P * 2 + B * H * L * 4
    blocks, cur = [], []
    for t in txs:
        cur.append(t)
        if t[0] == "dma_state":
            blocks.append(cur)
            cur = []
    assert len(blocks) == B * H // hb and not cur
    for i, blk in enumerate(blocks):
        b, h0 = divmod(i, H // hb)
        g = h0 * hb // (H // G)
        reads = sorted(a for e, _, a, _ in blk if e == "dma_bc")
        want = [base + (b * G + g) * bc + c * cl * N * 2
                for base in (b_base, b_base + B * G * bc)
                for c in range(L // cl)]
        assert reads == want


def test_wkv_burst_list_per_tile():
    txs = wkv_ops.transactions(2, 64, 16, 32, chunk=16, hb=8)
    _check_bursts(txs, 4)
    n_state = sum(1 for e, _, _, _ in txs if e == "dma_state")
    assert n_state == 2 * (16 // 8)
