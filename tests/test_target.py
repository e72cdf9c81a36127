"""The co-verification target contract (core/target.py).

Every target kind provides every member; ``logs()`` is the order the
golden traces concatenate; and what the session's collect reads through
the contract equals what it read kind by kind before the contract
existed (the pins below).  A sweep cell and its bisection's re-recording
come from one target builder, so they log the same stream.
"""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.core import CongestionConfig, CoVerifySession, FireBridge
from repro.core.bridge import MemoryBridge
from repro.core.fabric import FabricCluster, sharded_launch
from repro.core.fuzz import FaultPlan
from repro.core.target import CoVerifyTarget, log_digest
from repro.kernels.systolic_matmul import ops as mm_ops
from repro.models import init_params
from repro.models.transformer import RunFlags
from repro.serving import (ClusterServingEngine, ServingEngine,
                           replayed_trace, run_open_loop)
from repro.sharding.specs import FABRIC_OP_SPECS

N = 32
TILE = 16
CONG = CongestionConfig(dos_prob=0.1, seed=5)
RATES = {"dma_delay": 0.5, "dma_split": 0.5, "dma_reorder": 0.5}
FLAGS = RunFlags(attn_impl="chunked", q_chunk=16, kv_chunk=16)
# four requests, the last past the KV capacity of max_len 32 (a violation)
TRACE = replayed_trace([(0, 0.0, (5, 6, 7), 3), (1, 40.0, (1, 2), 2),
                        (2, 90.0, (9, 8, 7, 6, 5), 4),
                        (3, 120.0, (4, 4), 40)])


def _ints(seed):
    """Integer-valued float32 operands: their matmul is exact in any
    summation order, so the pinned output digests are portable."""
    rng = np.random.default_rng(seed)
    return rng.integers(-3, 4, size=(N, N)).astype(np.float32)


def _matmul(a, b):
    return np.matmul(a, b)


def _bursts(m=N):
    return mm_ops.transactions(m, N, N, bm=min(TILE, m), bn=TILE, bk=TILE,
                               dtype_bytes=4)


def _firmware(fb, op, backend, **_):
    """One matmul launch plus a CSR write to an unmapped address."""
    for name, seed in (("a", 1), ("b", 2)):
        fb.mem.alloc(name, (N, N), np.float32)
        fb.mem.host_write(name, _ints(seed))
    fb.mem.alloc("c", (N, N), np.float32)
    fb.launch(op, backend, ["a", "b"], ["c"], burst_list=_bursts)
    fb.csr.fb_write_32(0x40, 1)


def _bridge():
    fb = FireBridge(congestion=CONG, fault_plan=FaultPlan(seed=4,
                                                          rates=RATES))
    fb.register_op("mm", oracle=_matmul)
    _firmware(fb, "mm", "oracle")
    return fb, [fb.log]


def _fabric():
    fab = FabricCluster(2, congestion=CONG,
                        fault_plan=FaultPlan(seed=9, rates=RATES))
    fab.register_op("mm", oracle=_matmul)
    sharded_launch(fab, "mm", "oracle",
                   inputs={"a": _ints(1), "b": _ints(2)},
                   output=("c", (N, N), np.float32),
                   specs=FABRIC_OP_SPECS["systolic_matmul"],
                   burst_list=lambda dev, shapes: _bursts(shapes["c"][0]))
    fab.devices[1].csr.fb_read_32(0x40)
    return fab, [fab.log] + [d.log for d in fab.devices]


@functools.lru_cache(maxsize=1)
def _model():
    cfg = smoke(get_config("llama3.2-1b"))
    return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)


def _serving_kw(plan):
    return dict(max_slots=2, max_len=32, prompt_pad=8, flags=FLAGS,
                congestion=CONG, fault_plan=plan, batching="continuous",
                kv_pages=4, kv_page_size=8)


def _serving():
    cfg, params = _model()
    eng = ServingEngine(cfg, params,
                        **_serving_kw(FaultPlan(seed=11, rates=RATES)))
    run_open_loop(eng, TRACE)
    return eng, [eng.mem.log]


def _cluster():
    cfg, params = _model()
    clu = ClusterServingEngine(cfg, params, n_devices=2,
                               **_serving_kw(FaultPlan(seed=11,
                                                       rates=RATES)))
    run_open_loop(clu, TRACE)
    return clu, [clu.log] + [e.mem.log for e in clu.engines]


BUILDERS = {"bridge": _bridge, "fabric": _fabric, "serving": _serving,
            "cluster": _cluster}


def _digest(outputs):
    h = hashlib.sha256()
    for name, arr in sorted(outputs.items()):
        h.update(f"{name}{arr.shape}{arr.dtype}".encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()[:16]


def _congestion(res):
    return (repr(res.makespan), dict(sorted(res.per_engine_stall.items())))


# what the session's collect read off each kind at the parent of the
# contract: output digest, modeled time, violations, the kinds of the
# (bridge-layer) fault events, congestion (makespan, per-engine stall).  Serving token
# values come from a model's argmax, so their pin is the stream lengths.
PINS = {
    "bridge": {
        "outputs": "1df12eb7bb0bd7f2",
        "modeled_time": "2545.0",
        "violations": ["write to unmapped address 0x40"],
        "faults": "bitflip_read dma_split dma_split dma_split dma_delay",
        "congestion": ("2545.0", {
            "accel_rd": 200.0,
            "accel_wr": 0.0,
            "dma_a": 1369.0,
            "dma_b": 1321.0,
            "dma_c": 685.0,
        }),
    },
    "fabric": {
        "outputs": "1df12eb7bb0bd7f2",
        "modeled_time": "1783.0",
        "violations": ["[d1] read from unmapped address 0x40"],
        "faults": (
            "dma_split dma_delay dma_delay dma_delay dma_split "
            "dma_delay dma_split dma_delay dma_split dma_delay "
            "dma_split dma_split dma_split dma_reorder dma_delay "
            "dma_delay dma_split dma_split dma_delay dma_split "
            "dma_split"
        ),
        "congestion": ("1729.0", {
            "d0/accel_rd": 0.0,
            "d0/accel_wr": 0.0,
            "d0/dma_a": 886.0,
            "d0/dma_b": 789.0,
            "d0/dma_c": 743.0,
            "d1/accel_rd": 0.0,
            "d1/accel_wr": 200.0,
            "d1/dma_a": 686.0,
            "d1/dma_b": 389.0,
            "d1/dma_c": 143.0,
        }),
    },
    "serving": {
        "outputs": {"tokens[0]": 3, "tokens[1]": 2, "tokens[2]": 4},
        "modeled_time": "2647.7368983501665",
        "violations": [
            "request 3 exceeds KV capacity: padded prompt 8 + 40 new "
            "tokens > max_len 32",
        ],
        "faults": (
            "congestion_perturb dma_split dma_delay dma_split "
            "dma_split dma_delay dma_split dma_split dma_delay"
        ),
        "congestion": ("2647.7368983501665", {
            "serve_dma": 400.0,
        }),
    },
    "cluster": {
        "outputs": {"tokens[0]": 3, "tokens[1]": 2, "tokens[2]": 4},
        "modeled_time": "2863.146163459759",
        "violations": [
            "[e1] request 3 exceeds KV capacity: padded prompt 8 + 40 new"
            " tokens > max_len 32",
        ],
        "faults": "",
        "congestion": ("2863.146163459759", {
            "e0->h": 456.33972232511314,
            "e1->h": 0.0,
            "h->e0": 0.0,
            "h->e1": 0.0,
        }),
    },
}


@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_every_kind_meets_the_contract(kind):
    target, golden_order = BUILDERS[kind]()
    assert isinstance(target, CoVerifyTarget)
    assert target.kind in ("bridge", "fabric", "serving")
    assert [id(log) for log in target.logs()] == \
        [id(log) for log in golden_order]

    pin = PINS[kind]
    outputs = target.outputs()
    if target.kind == "serving":
        assert {k: len(v) for k, v in outputs.items()} == pin["outputs"]
        for rid, req in target.requests.items():
            if req.done:
                np.testing.assert_array_equal(
                    outputs[f"tokens[{rid}]"],
                    np.asarray(req.out_tokens, np.int64))
    else:
        assert _digest(outputs) == pin["outputs"]
    assert repr(target.modeled_time) == pin["modeled_time"]
    assert target.violations == pin["violations"]
    faults = target.fault_events()
    assert {e.layer for e in faults} <= {"bridge"}
    assert " ".join(e.kind for e in faults) == pin["faults"]
    assert _congestion(target.congestion_stats()) == pin["congestion"]


@pytest.mark.parametrize("kind", ["bridge", "fabric"])
def test_outputs_lend_the_final_state_read_only(kind):
    """``outputs()`` copies nothing: every buffer is a read-only view of
    the target's own DDR (a fabric's host buffers), which a write cannot
    change through it, and it holds the pinned bytes."""
    target, _ = BUILDERS[kind]()
    ddr = (target.mem if kind == "bridge" else target.host).buffers
    outputs = target.outputs()
    assert set(outputs) == set(ddr)
    for name, arr in outputs.items():
        assert np.shares_memory(arr, ddr[name].array)
        assert not arr.flags.writeable
        with pytest.raises(ValueError):
            arr[(0,) * arr.ndim] = 1
    assert _digest(outputs) == PINS[kind]["outputs"]
    assert _digest(target.outputs()) == PINS[kind]["outputs"]


def _bridge_session(plan):
    sess = CoVerifySession(_firmware, congestion=CONG, fault_plan=plan)
    sess.register_op("mm", oracle=_matmul)
    return sess, sess.add_cell("mm", "oracle")


def _serving_session(plan):
    cfg, params = _model()
    jit_fns = ServingEngine(cfg, params, **_serving_kw(None)).jit_fns
    sess = CoVerifySession(_firmware, fault_plan=plan)
    sess.register_serving(lambda backend, devices, p: ServingEngine(
        cfg, params, jit_fns=jit_fns, **_serving_kw(p)))
    return sess, sess.add_serving_cell("compiled", TRACE)


@pytest.mark.parametrize("session", [_bridge_session, _serving_session],
                         ids=["bridge", "serving"])
def test_bisection_records_what_the_sweep_ran(session, monkeypatch):
    """A fault-injected sweep cell and the bisection's re-recording of it
    log the same stream: one builder, one fault fork."""
    sess, cell = session(FaultPlan(seed=6, rates=RATES))
    built = []
    make = sess._make_target

    def spy(*args, **kw):
        built.append(make(*args, **kw))
        return built[-1]
    monkeypatch.setattr(sess, "_make_target", spy)
    (res,) = sess.run(max_workers=1).cells
    assert res.error is None and res.faults
    (live,) = built
    _, rec = sess._record_cell(cell)
    assert rec.log_digest == log_digest(live)
    assert rec.target.fault_events() == live.fault_events()


def test_bare_memory_bridge_stays_a_profiler_target():
    """A ``MemoryBridge`` alone profiles as one DDR channel that closes
    to its clock."""
    from repro.core.profiler import DataMovementProfiler
    for cong in (None, CONG):
        mem = MemoryBridge(congestion=cong)
        mem.alloc("x", (N, N), np.float32)
        mem.dev_write("x", _ints(3))
        mem.dev_read("x")
        prof = DataMovementProfiler(mem)
        (ch,) = prof.channels
        assert ch.name == "ddr"
        assert ch.kind == ("clock" if cong is None else "link")
        assert sum(ch.breakdown.cycles.values()) == mem.time
        assert [b for b, *_ in prof.counter_tracks] == ["ddr"]
