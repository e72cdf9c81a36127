"""Built-in work-unit executors: fuzz batches, co-verify sweep slices,
golden-trace regeneration.

Every executor is a pure function of its ``WorkUnit`` — fresh fuzzer /
session / coverage model per call, nothing read from ambient state — so a
unit executes bit-identically in the sequential oracle (``workers=0``)
and in any spawned worker process, all on the CPU device
(``execute_unit``).  Executor imports are lazy, so a worker imports only
what its units use.

Failure harvesting happens HERE, worker-side, where the failing state is
live: a failing fuzz scenario is minimized with the existing
``ProtocolFuzzer.shrink`` (checkpointed replay, core/replay.py) and a
divergent sweep group is localized by the scheduler's
``bisect_divergence`` lane; the shrunk repro rides back to the manager in
``UnitResult.harvest`` and lands in the campaign's ``bundles/``.
"""
from __future__ import annotations

import hashlib
import time
from typing import Callable, Dict

from repro.runfarm.units import UnitResult, WorkUnit


def execute_unit(unit: WorkUnit) -> UnitResult:
    """Run one unit under its registered executor (timed) on the host CPU.

    Units are host-side modeled simulation, and their float outputs enter
    the digest.  So every lane computes on the CPU device: spawned workers
    see only the CPU platform (runfarm/worker.py), and the
    ``workers=0`` lane, which runs here in the parent, is placed on the CPU
    too, even on a machine whose default device is an accelerator."""
    import jax
    try:
        fn = EXECUTORS[unit.kind]
    except KeyError:
        raise KeyError(f"no executor for unit kind {unit.kind!r} "
                       f"(known: {sorted(EXECUTORS)})") from None
    t0 = time.perf_counter()
    # a platform name, not a device: jax resolves it only when a unit
    # dispatches a computation, so a unit that never does starts no backend
    with jax.default_device("cpu"):
        res = fn(unit)
    res.seconds = time.perf_counter() - t0
    return res


# ---------------------------------------------------------- fuzz batches
def _planted_table(index, delta):
    """No-jit variant of core/fuzz.planted_bug_table: the same known
    interpret-backend divergence, but on the un-jitted backend table so
    fuzz workers stay trace-compilation-free."""
    import numpy as np

    from repro.core.fuzz import ProtocolFuzzer
    from repro.kernels.systolic_matmul.sweep import matmul_backends
    table = matmul_backends(tile=ProtocolFuzzer.TILE, jit=False)
    good = table["interpret"]

    def buggy(a, b):
        out = np.array(good(a, b))
        out[int(index[0]), int(index[1])] += delta
        return out
    return dict(table, interpret=buggy)


def _run_fuzz_batch(unit: WorkUnit) -> UnitResult:
    from repro.core.coverage import CoverageModel
    from repro.core.fuzz import ProtocolFuzzer
    p = unit.params
    kw = {}
    if p.get("rates"):
        kw["rates"] = dict(p["rates"])
    if p.get("bridge_ops"):
        kw["bridge_ops"] = tuple(p["bridge_ops"])
    if p.get("mm_bug"):
        i, j, delta = p["mm_bug"]
        kw["mm_table"] = _planted_table((i, j), float(delta))
    cov = CoverageModel()
    fz = ProtocolFuzzer(seed=unit.seed, layers=tuple(p["layers"]),
                        coverage=cov, **kw)
    report = fz.run(int(p["count"]))
    failing = report.failures()
    harvest = None
    if failing and p.get("shrink_failures", True):
        # minimize the FIRST failing scenario (checkpointed-replay shrink
        # for bridge scenarios, linear prefix search otherwise) — the
        # batch is seed-closed, so the bundle alone reproduces it
        r0 = failing[0]
        scn = fz.scenario(r0.index)
        sub, res = fz.shrink(scn)
        harvest = {"scenario": r0.index, "layer": r0.layer,
                   "seed": unit.seed,
                   "full_ops": len(scn.ops), "shrunk_ops": len(sub.ops),
                   "ops": [repr(op) for op in sub.ops],
                   "failures": res.failures[:4]}
    return UnitResult(
        uid=unit.uid, kind=unit.kind, ok=report.passed,
        digest=report.digest, counts=cov.to_counts(),
        scenarios=len(report.results),
        failures=[f"scn{r.index}[{r.layer}]: {r.failures[0]}"
                  for r in failing][:8],
        harvest=harvest)


# ------------------------------------------------------------ sweep cells
def _run_sweep(unit: WorkUnit) -> UnitResult:
    import numpy as np

    from repro.core import CongestionConfig, CoVerifySession
    from repro.core.coverage import CoverageModel
    from repro.core.fuzz import FaultPlan
    from repro.kernels.systolic_matmul.sweep import (matmul_backends,
                                                     matmul_firmware)
    p = unit.params
    table = matmul_backends(jit=False)
    interp = table["interpret"]
    if p.get("mm_bug"):
        bi, bj, delta = p["mm_bug"]
        good = interp

        def interp(a, b, _good=good, _i=int(bi), _j=int(bj),
                   _d=float(delta)):
            out = np.array(_good(a, b))
            out[_i, _j] += _d
            return out
    cov = CoverageModel()
    sess = CoVerifySession(
        matmul_firmware,
        congestion=CongestionConfig(seed=int(p.get("congestion_seed", 7))),
        fault_plan=FaultPlan(unit.seed), coverage=cov)
    sess.register_op("mm", oracle=table["oracle"], interpret=interp)
    for cfg in p["configs"]:
        for be in p.get("backends", ("oracle", "interpret")):
            sess.add_cell("mm", be, dict(cfg))
    # in-unit max_workers=1: parallelism is the FARM's axis; the unit
    # itself stays the sequential oracle (bisect_failures localizes any
    # divergent group via the replay machinery)
    rep = sess.run(max_workers=1, bisect_failures=True)
    h = hashlib.sha256()
    for row in rep.to_rows(wall=False):
        h.update(row.encode())
        h.update(b"\n")
    for r in rep.cells:
        for name in sorted(r.outputs):
            h.update(name.encode())
            h.update(np.ascontiguousarray(r.outputs[name]).tobytes())
    summary = rep.summary()
    harvest = None
    if summary["divergences"]:
        harvest = {"seed": unit.seed, "divergences": summary["divergences"],
                   "failures": summary["failures"]}
    # always-on counter totals summed over the unit's cells (each cell
    # already carries its oracle payload)
    counters: Dict[str, float] = {}
    for r in rep.cells:
        for name, v in (r.counters or {}).get("totals", {}).items():
            counters[name] = counters.get(name, 0) + v
    return UnitResult(
        uid=unit.uid, kind=unit.kind, ok=rep.passed, digest=h.hexdigest(),
        counts=cov.to_counts(), scenarios=len(rep.cells),
        failures=summary["failures"][:8], harvest=harvest,
        counters=counters)


# --------------------------------------------------- open-loop serving SLO
def _run_serving_campaign(unit: WorkUnit) -> UnitResult:
    """One open-loop serving unit: regenerate the arrival trace from the
    unit's forked seed (serving/arrivals.build_trace — the trace is pure
    JSON + seed), drive it against a fresh continuous-batching engine with
    a paged KV cache, and witness the run with ``SLOReport.digest()`` —
    rows AND token streams, so any latency-model or behavioral drift flips
    the campaign digest.  Admission invariants (exact token budgets, pool
    fully drained) are checked worker-side where the engine is live."""
    from repro.core.coverage import CoverageModel
    from repro.serving import SLOReport, build_trace, run_open_loop

    p = unit.params
    trace = build_trace(p["kind"], unit.seed, **dict(p.get("trace") or {}))
    pool = dict(p.get("pool") or {})
    target = _serving_target(
        devices=int(p.get("devices", 1)),
        max_slots=int(pool.get("max_slots", 2)),
        max_len=int(pool.get("max_len", 32)),
        prompt_pad=int(pool.get("prompt_pad", 8)),
        kv_pages=pool.get("kv_pages"),
        kv_page_size=int(pool.get("kv_page_size", 8)))
    failures = []
    slo = None
    try:
        run_open_loop(target, trace,
                      max_ticks=int(p.get("max_ticks", 50_000)))
        slo = SLOReport.from_run(trace, target,
                                 label=f"{unit.uid}:{trace.label}")
    except Exception as e:
        failures.append(f"{type(e).__name__}: {e}")
    violations = target.violations
    engines = getattr(target, "engines", None) or [target]
    # admission invariants: every admitted request retired with its exact
    # decode budget, and every reserved page came back to the pool
    rejected = {int(v.split()[1]) for v in violations
                if "exceeds KV page pool" in v}
    for a in trace.arrivals:
        req = target.requests.get(a.rid)
        if a.rid in rejected:
            if req is not None:
                failures.append(f"rejected rid {a.rid} holds a slot")
            continue
        if req is None or not req.done:
            failures.append(f"admitted rid {a.rid} never retired")
        elif len(req.out_tokens) != a.max_new_tokens:
            failures.append(
                f"rid {a.rid}: {len(req.out_tokens)} tokens != "
                f"budget {a.max_new_tokens}")
    for i, eng in enumerate(engines):
        kp = eng.kv_pool
        if kp is not None and (kp.n_free != kp.n_pages or kp.pages):
            failures.append(f"engine {i} leaked KV pages: "
                            f"{kp.n_free}/{kp.n_pages} free after drain")
    cov = CoverageModel()
    cov.hit_logs(target.logs())
    cov.hit("arrivals", trace.kind)
    pools = [e.kv_pool for e in engines if e.kv_pool is not None]
    deferrals = sum(kp.deferrals for kp in pools)
    if deferrals:
        cov.hit("arrivals", "deferred", deferrals)
    if any(kp.peak_in_use == kp.n_pages for kp in pools):
        cov.hit("arrivals", "pool_full")
    if rejected:
        cov.hit("arrivals", "infeasible_reject", len(rejected))
    if slo is not None:
        digest = slo.digest()
    else:
        digest = hashlib.sha256(
            "\n".join(failures).encode()).hexdigest()
    harvest = None
    if failures:
        harvest = {"seed": unit.seed, "trace": trace.label,
                   "failures": failures[:8], "violations": violations[:8]}
    from repro.core.counters import counter_banks, merged_totals
    return UnitResult(
        uid=unit.uid, kind=unit.kind, ok=not failures, digest=digest,
        counts=cov.to_counts(), scenarios=len(trace.arrivals),
        failures=failures[:8], harvest=harvest,
        counters=merged_totals(counter_banks(target)))


def _serving_target(*, devices: int, max_slots: int, max_len: int,
                    prompt_pad: int, kv_pages, kv_page_size: int):
    """Fresh continuous-batching serving target on the smoke model —
    jax-lazy so non-serving workers never pay the import."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, smoke
    from repro.models import init_params
    from repro.models.transformer import RunFlags
    from repro.serving import ClusterServingEngine, ServingEngine

    cfg = smoke(get_config("llama3.2-1b"))
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    kw = dict(max_slots=max_slots, max_len=max_len, prompt_pad=prompt_pad,
              flags=RunFlags(attn_impl="chunked", q_chunk=16, kv_chunk=16),
              batching="continuous", kv_pages=kv_pages,
              kv_page_size=kv_page_size)
    if devices > 1:
        return ClusterServingEngine(cfg, params, n_devices=devices, **kw)
    return ServingEngine(cfg, params, **kw)


# ------------------------------------------------------ golden-trace regen
def _run_golden(unit: WorkUnit) -> UnitResult:
    import importlib
    try:
        mod = importlib.import_module("tests.test_golden_traces")
    except ModuleNotFoundError:
        # sequential in-process lane with only src/ on the path: the
        # golden suite lives at the repo root, one level above src/
        import sys
        from pathlib import Path

        import repro
        root = Path(next(iter(repro.__path__))).resolve().parents[1]
        if str(root) not in sys.path:
            sys.path.insert(0, str(root))
        mod = importlib.import_module("tests.test_golden_traces")
    name = unit.params["name"]
    run = mod.TRACES[name]()
    text = "\n".join(run.lines) + "\n"
    golden_path = mod.GOLDEN / f"{name}.trace"
    committed = golden_path.read_text() if golden_path.exists() else None
    ok = text == committed
    failures = [] if ok else [
        f"regenerated trace diverges from committed {golden_path.name} "
        f"({len(run.lines)} live lines vs "
        f"{len(committed.splitlines()) if committed else 0} golden)"]
    from repro.core.counters import counter_banks, merged_totals
    target = getattr(getattr(run, "recording", None), "target", None)
    return UnitResult(
        uid=unit.uid, kind=unit.kind, ok=ok,
        digest=hashlib.sha256(text.encode()).hexdigest(),
        counts={}, scenarios=1, failures=failures,
        counters=merged_totals(counter_banks(target))
        if target is not None else {})


EXECUTORS: Dict[str, Callable[[WorkUnit], UnitResult]] = {
    "fuzz_batch": _run_fuzz_batch,
    "sweep": _run_sweep,
    "golden": _run_golden,
    "serving": _run_serving_campaign,
}
