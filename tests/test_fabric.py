"""FabricCluster: modeled interconnect, sharded launches, collectives,
devices= sweep axis, cluster serving (core/fabric.py, serving/cluster.py).

The acceptance surface for the multi-device fabric: 4-device sharded
sweep cells bit-identical to the single-device oracle with non-zero
modeled inter-device link stalls, and same-seed transaction-log digest
reproducibility.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FABRIC_LINK, CongestionConfig, CoVerifySession,
                        CoverageModel, FabricCluster, FaultPlan)
from repro.kernels.flash_attention.sweep import (flash_backends,
                                                 flash_fabric_firmware,
                                                 flash_firmware)
from repro.kernels.systolic_matmul.sweep import (matmul_backends,
                                                 matmul_fabric_firmware,
                                                 matmul_firmware)

LINK = FABRIC_LINK


# ------------------------------------------------------------- primitives
def test_scatter_gather_roundtrip_bit_identical():
    fab = FabricCluster(4, link_config=LINK)
    data = np.arange(7 * 6, dtype=np.float32).reshape(7, 6)   # uneven split
    fab.host.alloc("x", data.shape, np.float32)
    fab.host.host_write("x", data)
    fab.scatter("x")
    for i, sh in enumerate(np.array_split(data, 4)):
        assert np.array_equal(fab.devices[i].mem.buffers["x"].array, sh)
    fab.host.buffers["x"].array[:] = 0          # prove gather repopulates
    fab.gather("x")
    assert np.array_equal(fab.host.host_read("x"), data)
    assert fab.time > 0 and len(fab.log.txs) > 0


def test_dev_copy_moves_data_and_advances_clock():
    fab = FabricCluster(3, link_config=LINK)
    fab.devices[0].mem.alloc("w", (16, 16), np.float32)
    w = np.random.default_rng(0).normal(size=(16, 16)).astype(np.float32)
    fab.devices[0].mem.host_write("w", w)
    t0 = fab.time
    fab.dev_copy(0, 2, "w")
    assert np.array_equal(fab.devices[2].mem.buffers["w"].array, w)
    assert fab.time > t0
    engines = {t.engine for t in fab.log.txs}
    assert "d0->d2" in engines


def test_broadcast_contends_on_host_channel():
    fab = FabricCluster(4, link_config=LINK)
    fab.host.alloc("b", (64, 64), np.float32)
    fab.host.host_write("b", np.ones((64, 64), np.float32))
    fab.broadcast("b")
    for d in fab.devices:
        assert np.array_equal(d.mem.buffers["b"].array,
                              np.ones((64, 64), np.float32))
    # four replicas crossing one channel: somebody waited
    host = fab.link_stats()["host"]
    assert sum(host.per_engine_stall.values()) > 0


def test_all_reduce_sum_and_determinism():
    arrs = [np.random.default_rng(i).normal(size=(8, 8)).astype(np.float32)
            for i in range(4)]

    def build():
        fab = FabricCluster(4, link_config=LINK)
        for i, a in enumerate(arrs):
            fab.devices[i].mem.alloc("g", a.shape, np.float32)
            fab.devices[i].mem.host_write("g", a)
        fab.all_reduce("g")
        return fab

    fab = build()
    ref = arrs[0] + arrs[1] + arrs[2] + arrs[3]
    for d in fab.devices:
        got = d.mem.buffers["g"].array
        assert np.allclose(got, ref, atol=1e-5)
        # every device converged to the same bits
        assert np.array_equal(got, fab.devices[0].mem.buffers["g"].array)
    # ring steps put a tx and an rx leg on every port: stalls are modeled
    assert fab.total_link_stall() > 0
    # same data, fresh cluster => identical transaction-log digest
    assert build().digest() == fab.digest()


def test_scatter_gather_empty_shards_move_nothing():
    """More devices than rows: empty shards must not emit zero-byte
    bursts (which would pay full base_latency) on either leg."""
    fab = FabricCluster(6, link_config=LINK)
    data = np.arange(4 * 2, dtype=np.float32).reshape(4, 2)
    fab.host.alloc("x", data.shape, np.float32)
    fab.host.host_write("x", data)
    fab.scatter("x")
    fab.gather("x")
    assert np.array_equal(fab.host.host_read("x"), data)
    assert all(t.nbytes > 0 for t in fab.log.txs)
    # exactly the 4 non-empty shards crossed, each with 2 legs each way
    assert len(fab.log.txs) == 4 * 2 * 2


def test_device_congestion_seeds_are_decorrelated():
    """Per-device DDR links must not share one DoS stream; device 0 keeps
    the caller's seed so it times like a standalone bridge."""
    cong = CongestionConfig(dos_prob=0.5, seed=9)
    fab = FabricCluster(3, congestion=cong, link_config=LINK)
    assert fab.devices[0].mem.congestion.seed == 9
    assert len({d.mem.congestion.seed for d in fab.devices}) == 3

    def stalls(dev):
        dev.mem.alloc("x", (64, 64), np.float32)
        dev.mem.dev_read("x")
        return [t.stall for t in dev.log.txs]

    streams = [stalls(d) for d in fab.devices]
    assert streams[0] != streams[1] or streams[0] != streams[2]


def test_all_reduce_degenerate_chunks_move_nothing():
    """More devices than elements: empty ring chunks must not emit
    zero-byte bursts or advance the fabric clock for moving no data."""
    fab = FabricCluster(4, link_config=LINK)
    for i in range(4):
        fab.devices[i].mem.alloc("g", (2,), np.float32)
        fab.devices[i].mem.host_write("g", np.float32([i, i]))
    fab.all_reduce("g")
    assert np.array_equal(fab.devices[0].mem.buffers["g"].array,
                          np.float32([6, 6]))
    assert all(t.nbytes > 0 for t in fab.log.txs)


def test_all_reduce_single_device_is_noop():
    fab = FabricCluster(1, link_config=LINK)
    fab.devices[0].mem.alloc("g", (4,), np.float32)
    fab.devices[0].mem.host_write("g", np.ones(4, np.float32))
    fab.all_reduce("g")
    assert np.array_equal(fab.devices[0].mem.buffers["g"].array,
                          np.ones(4, np.float32))
    assert len(fab.log.txs) == 0


def test_fault_plan_forks_are_deterministic_and_audited():
    def run():
        fab = FabricCluster(2, link_config=LINK, fault_plan=FaultPlan(7))
        fab.host.alloc("x", (32, 32), np.float32)
        fab.host.host_write("x", np.ones((32, 32), np.float32))
        fab.scatter("x")
        fab.gather("x")
        return fab

    a, b = run(), run()
    assert a.digest() == b.digest()
    # fabric-link faults are audited in the fabric log, and the data still
    # arrives intact (faults perturb timing, never function)
    assert len(a.log.faults) == len(a.fault_plan.events)
    assert np.array_equal(a.host.host_read("x"), np.ones((32, 32),
                                                         np.float32))


def test_timing_monotonicity_extra_traffic_never_helps():
    def total_time(extra: bool) -> float:
        fab = FabricCluster(2, link_config=CongestionConfig(
            dos_prob=0.0, max_burst_bytes=4096))
        fab.host.alloc("x", (64, 64), np.float32)
        fab.host.host_write("x", np.zeros((64, 64), np.float32))
        if extra:
            fab.host.alloc("y", (64, 64), np.float32)
            fab.host.host_write("y", np.zeros((64, 64), np.float32))
            fab.broadcast("y")                  # contending traffic
        fab.scatter("x")
        fab.gather("x")
        return fab.time

    assert total_time(extra=True) >= total_time(extra=False)


def test_inner_axis_shard_addresses_are_strided():
    """Host-side DMA legs of an inner-axis scatter/gather must be logged
    at the shard's true strided byte runs, not one contiguous block —
    regression for the Fig. 9 address-attribution bug."""
    from repro.core.fabric import shard_runs
    # (2, 4, 3) f32, shard axis 1 into [0,2) and [2,4)
    assert shard_runs((2, 4, 3), 4, 1, 0, 2) == [(0, 24), (48, 24)]
    assert shard_runs((2, 4, 3), 4, 1, 2, 4) == [(24, 24), (72, 24)]
    # axis 0 stays one contiguous run (golden-trace compatible)
    assert shard_runs((8, 6), 4, 0, 2, 4) == [(2 * 24, 2 * 24)]
    assert shard_runs((4,), 4, 0, 2, 2) == []          # empty shard

    fab = FabricCluster(2, link_config=LINK)
    fab.host.alloc("q", (2, 4, 3), np.float32)
    data = np.arange(24, dtype=np.float32).reshape(2, 4, 3)
    fab.host.host_write("q", data)
    fab.scatter("q", axis=1)
    hbuf = fab.host.buffers["q"]
    reads = sorted((t.addr - hbuf.addr, t.nbytes) for t in fab.log.txs
                   if t.kind == "read" and t.engine.startswith("h->"))
    assert reads == [(0, 24), (24, 24), (48, 24), (72, 24)]
    fab.gather("q", axis=1)
    assert np.array_equal(fab.host.host_read("q"), data)


def test_fabric_feeds_coverage():
    cov = CoverageModel()
    fab = FabricCluster(2, link_config=LINK, coverage=cov)
    fab.host.alloc("x", (16, 16), np.float32)
    fab.host.host_write("x", np.zeros((16, 16), np.float32))
    fab.host.alloc("w", (8, 8), np.float32)
    fab.host.host_write("w", np.zeros((8, 8), np.float32))
    fab.scatter("x")
    fab.broadcast("w")
    fab.gather("x")
    fab.devices[0].mem.alloc("g", (4,), np.float32)
    fab.devices[1].mem.alloc("g", (4,), np.float32)
    fab.all_reduce("g")
    fab.dev_copy(0, 1, "x", dst_name="x2")
    assert cov.covered("fabric"), cov.holes("fabric")
    assert sum(cov.counts["burst_size"].values()) > 0


# ------------------------------------------------- sharded sweeps (tentpole)
@pytest.mark.slow
def test_matmul_sweep_4dev_bit_identical_with_link_stalls():
    """Acceptance: 4-device systolic_matmul cells bit-identical to the
    single-device oracle in the SweepReport, with non-zero modeled
    inter-device link stalls."""
    sess = CoVerifySession(matmul_firmware,
                           fabric_firmware=matmul_fabric_firmware,
                           link_config=LINK)
    sess.register_op("mm", **matmul_backends(tile=32))
    sess.add_sweep("mm", ("oracle", "interpret", "compiled"),
                   [{"size": 128}], devices=(1, 4))
    report = sess.run(max_workers=4)
    assert report.passed, report.summary()
    (eq,) = report.equivalence.values()
    assert set(eq.backends) == {"oracle", "interpret", "compiled",
                                "oracle@4dev", "interpret@4dev",
                                "compiled@4dev"}
    by = {r.cell.group_member: r for r in report.cells}
    for be in ("oracle", "interpret", "compiled"):
        assert np.array_equal(by[be].outputs["c"],
                              by[f"{be}@4dev"].outputs["c"])
    for r in report.cells:
        if r.cell.devices > 1:
            assert r.link_stall > 0, r.cell.label
            # inter-device ports specifically, not just the host channel
            port_stall = sum(sum(c.per_engine_stall.values())
                             for n, c in r.links.items() if n != "host")
            assert port_stall >= 0 and r.links["host"] is not None


@pytest.mark.slow
def test_flash_sweep_4dev_bit_identical_with_link_stalls():
    """Acceptance: 4-device flash_attention cells bit-identical to the
    single-device oracle."""
    sess = CoVerifySession(flash_firmware,
                           fabric_firmware=flash_fabric_firmware,
                           link_config=LINK)
    sess.register_op("fa", **flash_backends())
    cfg = {"batch": 1, "heads": 8, "seq": 64, "dim": 16}
    sess.add_sweep("fa", ("oracle", "interpret"), [cfg], devices=(1, 4))
    report = sess.run(max_workers=4)
    assert report.passed, report.summary()
    by = {r.cell.group_member: r for r in report.cells}
    for be in ("oracle", "interpret"):
        assert np.array_equal(by[be].outputs["o"],
                              by[f"{be}@4dev"].outputs["o"])
    assert by["oracle@4dev"].link_stall > 0


def test_devices_sweep_seed_reproducibility():
    """Acceptance: same seed => identical fabric transaction-log digests
    across two runs of a sharded launch."""
    def digest():
        fab = FabricCluster(4, link_config=LINK, fault_plan=FaultPlan(3))
        fab.register_op("mm", **matmul_backends(tile=32, jit=False))
        matmul_fabric_firmware(fab, "mm", "oracle", size=64, tile=32)
        return fab.digest()

    assert digest() == digest()


def test_sweep_report_scaling_rows():
    sess = CoVerifySession(matmul_firmware,
                           fabric_firmware=matmul_fabric_firmware,
                           link_config=LINK)
    sess.register_op("mm", **matmul_backends(tile=32, jit=False))
    sess.add_sweep("mm", ("oracle",), [{"size": 64}], devices=(1, 2))
    report = sess.run(max_workers=2)
    assert report.passed
    rows = report.scaling()
    assert rows[0].startswith("op,backend,devices")
    assert len(rows) == 3
    assert ",1," in rows[1] and ",2," in rows[2]
    # to_rows carries the devices + link-stall columns too
    assert "link_stall_cycles" in report.to_rows()[0]


def test_fabric_cell_error_does_not_kill_sweep():
    def bad_firmware(fab, op, backend, **cfg):
        raise RuntimeError("boom")

    sess = CoVerifySession(matmul_firmware, fabric_firmware=bad_firmware,
                           link_config=LINK)
    sess.register_op("mm", **matmul_backends(tile=32, jit=False))
    sess.add_cell("mm", "oracle", {"size": 32}, devices=2)
    report = sess.run()
    assert not report.passed
    assert "RuntimeError" in report.cells[0].error


@pytest.mark.slow
def test_bench_fabric_scaling_quick_mode():
    """The scaling benchmark's quick mode reports 1/2/4-device crossbar
    rows plus a routed 4-device torus with per-hop stall columns, modeled
    cycles, and non-zero link stalls at every multi-device scale."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.bench_fabric_scaling import run
    rows = run(quick=True)
    assert rows[0].startswith("case,op,backend,devices,topology")
    body = [r.split(",") for r in rows[1:] if r.startswith("fabric,")]
    hops = [r.split(",") for r in rows[1:]
            if r.startswith("hop,") and not r.startswith("hop,op,")]
    assert {int(r[3]) for r in body} == {1, 2, 4}
    assert {r[4] for r in body} == {"crossbar", "torus2d"}
    for r in body:
        assert r[-1] == "True"
        if int(r[3]) > 1:
            assert float(r[6]) > 0          # link stalls modeled
        if r[4] != "crossbar":
            assert float(r[7]) >= float(r[8]) >= 0   # hop columns close
    # routed cells break down per switch port
    assert hops and all(h[4] == "torus2d" for h in hops)
    assert any(float(h[6]) > 0 for h in hops)


# ------------------------------------------------------- cluster serving
FLAGS = None


def _smoke_model():
    from repro.configs import get_config, smoke
    from repro.models import init_params
    from repro.models.transformer import RunFlags
    cfg = smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    return cfg, params, RunFlags(attn_impl="chunked", q_chunk=16,
                                 kv_chunk=16)


def _submit(e, cfg, prompts, mx=5):
    for rid, p in prompts.items():
        e.mem.buffers["prompt_in"].array[:len(p)] = p
        e.csr.fb_write_32(e.csr.addr_of("SUBMIT_ID"), rid)
        e.csr.fb_write_32(e.csr.addr_of("SUBMIT_LEN"), len(p))
        e.csr.fb_write_32(e.csr.addr_of("SUBMIT_MAXNEW"), mx)
        e.csr.fb_write_32(e.csr.addr_of("DOORBELL"), 1)
    e.run_until_done()


@pytest.mark.slow
def test_cluster_serving_matches_single_engine():
    from repro.serving import ClusterServingEngine, ServingEngine
    cfg, params, flags = _smoke_model()
    single = ServingEngine(cfg, params, max_slots=3, max_len=64,
                           flags=flags)
    clu = ClusterServingEngine(cfg, params, n_devices=2, max_slots=2,
                               max_len=64, flags=flags)
    rng = np.random.default_rng(0)
    prompts = {rid: rng.integers(0, cfg.vocab_size,
                                 int(rng.integers(5, 30)))
               for rid in range(6)}
    _submit(single, cfg, prompts)
    _submit(clu, cfg, prompts)
    assert single.completed == clu.completed == 6
    assert clu.csr.hw_get("COMPLETED") == 6
    assert clu.csr.hw_get("NDEV") == 2
    # round-robin placement across both device-local engines
    assert set(clu.placement.values()) == {0, 1}
    # identical generations regardless of placement
    for rid in prompts:
        assert single.requests[rid].out_tokens == \
            clu.requests[rid].out_tokens
    # prompt upload + token writeback both crossed the shared channel
    st = clu.congestion_stats()
    assert any(e.startswith("h->e") for e in st.per_engine_stall)
    assert any(e.startswith("e") and "->h" in e
               for e in st.per_engine_stall)
    # concurrent retirements contend on the channel
    assert sum(st.per_engine_stall.values()) > 0
    assert not clu.violations
    # reset + identical storm reproduces the transaction digest
    clu.reset()
    _submit(clu, cfg, prompts)
    d1 = clu.digest()
    clu.reset()
    _submit(clu, cfg, prompts)
    assert clu.digest() == d1


@pytest.mark.slow
def test_cluster_serving_rejects_propagate():
    from repro.serving import ClusterServingEngine
    cfg, params, flags = _smoke_model()
    clu = ClusterServingEngine(cfg, params, n_devices=2, max_slots=2,
                               max_len=64, flags=flags)
    clu.csr.fb_write_32(clu.csr.addr_of("SUBMIT_ID"), 0)
    clu.csr.fb_write_32(clu.csr.addr_of("SUBMIT_LEN"), 10_000)
    clu.csr.fb_write_32(clu.csr.addr_of("SUBMIT_MAXNEW"), 4)
    clu.csr.fb_write_32(clu.csr.addr_of("DOORBELL"), 1)
    assert any("SUBMIT_LEN" in v for v in clu.violations)
    assert 0 not in clu.placement
    clu.run_until_done()
    assert clu.completed == 0
    # the rejected submission must not burn engine 0's round-robin turn
    p = np.random.default_rng(3).integers(0, cfg.vocab_size, 8)
    clu.mem.buffers["prompt_in"].array[:8] = p
    clu.csr.fb_write_32(clu.csr.addr_of("SUBMIT_ID"), 1)
    clu.csr.fb_write_32(clu.csr.addr_of("SUBMIT_LEN"), 8)
    clu.csr.fb_write_32(clu.csr.addr_of("SUBMIT_MAXNEW"), 2)
    clu.csr.fb_write_32(clu.csr.addr_of("DOORBELL"), 1)
    assert clu.placement[1] == 0


@pytest.mark.slow
def test_cluster_rejects_cross_engine_duplicate_rid():
    """Regression: a duplicate in-flight SUBMIT_ID used to slip past the
    per-engine check when round-robin routed it to a different engine.
    The front-end must reject it cluster-wide; retired ids may recycle."""
    from repro.serving import ClusterServingEngine
    cfg, params, flags = _smoke_model()
    clu = ClusterServingEngine(cfg, params, n_devices=2, max_slots=2,
                               max_len=64, flags=flags)
    rng = np.random.default_rng(2)

    def ring(rid, mx=4):
        p = rng.integers(0, cfg.vocab_size, 10)
        clu.mem.buffers["prompt_in"].array[:10] = p
        clu.csr.fb_write_32(clu.csr.addr_of("SUBMIT_ID"), rid)
        clu.csr.fb_write_32(clu.csr.addr_of("SUBMIT_LEN"), 10)
        clu.csr.fb_write_32(clu.csr.addr_of("SUBMIT_MAXNEW"), mx)
        clu.csr.fb_write_32(clu.csr.addr_of("DOORBELL"), 1)

    ring(7)
    ring(7)                   # would land on the OTHER engine
    assert clu.violations == [
        "duplicate SUBMIT_ID 7: request still in flight"]
    clu.run_until_done()
    assert clu.completed == 1
    assert len(clu.requests[7].out_tokens) == 4
    # retired id recycles cleanly — and the merged view stays unambiguous
    ring(7, mx=2)
    assert len(clu.violations) == 1         # no new violation
    clu.run_until_done()
    assert clu.completed == 2
    assert len(clu.requests[7].out_tokens) == 2
    assert sum(7 in e.requests for e in clu.engines) == 1
    # recycle landing back on the SAME engine must re-arm the writeback
    # (a stale _written marker used to freeze COMPLETED forever)
    ring(8)                   # advance round-robin so 7 -> its old engine
    ring(7, mx=3)
    clu.run_until_done()
    assert clu.completed == 4
    assert clu.csr.hw_get("COMPLETED") == 4
    assert len(clu.requests[7].out_tokens) == 3
