"""Continuous-batching serving engine driven through a FireBridge
register-file control plane (paper §IV-A adapted to an inference server).

Hardware-style interface: firmware submits a request by writing its prompt
into a bridge DDR buffer, programming SUBMIT_* CSRs, and ringing the
DOORBELL; it polls STATUS/COMPLETED and reads generated tokens back from
DDR.  Internally the engine runs batched prefill/decode with slot-based
continuous batching over a shared KV/state cache (cache_insert).

The CSR protocol (and its violation audit) is what the register-protocol
fuzz tests exercise — serving *is* the paper's "accelerator with
memory-mapped configuration registers", deployed as a first-class feature.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.bridge import MemoryBridge
from repro.core.congestion import CongestionConfig, CongestionResult
from repro.core.counters import CounterBank, CounterSpec
from repro.core.registers import RO, RegisterFile
from repro.core.target import ProfileView
from repro.models.transformer import (RunFlags, ShardCtx, cache_insert,
                                      init_cache, make_decode_fn,
                                      make_prefill_fn)
from repro.serving.kvpool import KVPool

CTRL, STATUS, DOORBELL = 0x00, 0x04, 0x08
SUBMIT_ID, SUBMIT_LEN, SUBMIT_MAXNEW = 0x0C, 0x10, 0x14
COMPLETED, ACTIVE = 0x18, 0x1C


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (len,) int32
    max_new_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # lifecycle stamps on the engine's modeled clock (continuous-batching
    # mode; -1.0 = not reached).  serving/slo.py reads them into the SLO
    # report: queueing = admit - arrival, TTFT = first - arrival.
    t_submit: float = -1.0
    t_admit: float = -1.0
    t_first: float = -1.0
    t_done: float = -1.0


def _copy_request(r: "Request") -> "Request":
    return Request(r.rid, r.prompt.copy(), r.max_new_tokens,
                   list(r.out_tokens), r.done, r.t_submit, r.t_admit,
                   r.t_first, r.t_done)


def token_outputs(requests: Dict[int, Request]) -> Dict[str, np.ndarray]:
    """The serving equivalence payload: every completed request's token
    stream, compared exactly across backends and device counts."""
    return {f"tokens[{rid}]": np.asarray(req.out_tokens, np.int64)
            for rid, req in sorted(requests.items()) if req.done}


def request_spans(placed) -> List[dict]:
    """Completed continuous-batching request lifecycles of the
    ``(device, engine)`` pairs ``placed``, as (queue, prefill, decode)
    stamps in modeled cycles, for the profiler.  Storm-mode requests
    carry no admission stamps (t_admit == -1) and are skipped."""
    spans = []
    for dev, eng in placed:
        for rid, req in eng.requests.items():
            if req.t_admit < 0 or req.t_done < 0:
                continue                # storm-mode or still in flight
            spans.append({"rid": int(rid), "device": int(dev),
                          "t_submit": float(req.t_submit),
                          "t_admit": float(req.t_admit),
                          "t_first": float(req.t_first),
                          "t_done": float(req.t_done),
                          "tokens": len(req.out_tokens)})
    return sorted(spans, key=lambda s: (s["t_submit"], s["rid"]))


def serving_summary(front, time: float, engines,
                    violations: List[str]) -> dict:
    """``state_summary`` of a serving target: ``front`` owns the staging
    buffers, the completion count and the merged request table."""
    out = {"time": round(time, 6), "buffers": front.mem.buffer_digests(),
           "completed": front.completed,
           "tokens": {rid: list(r.out_tokens)
                      for rid, r in sorted(front.requests.items())},
           "violations": len(violations)}
    pools = {f"e{i}": e.kv_pool.n_free for i, e in enumerate(engines)
             if e.kv_pool is not None}
    if pools:
        out["kv_free_pages"] = pools
    return out


def cover_admission(cov, engines, violations: List[str]) -> None:
    """KV-pool admission bins of a finished open-loop run: deferrals,
    a saturated pool, an infeasible request rejected at the doorbell."""
    pools = [e.kv_pool for e in engines if e.kv_pool is not None]
    deferrals = sum(p.deferrals for p in pools)
    if deferrals:
        cov.hit("arrivals", "deferred", deferrals)
    if any(p.peak_in_use == p.n_pages for p in pools):
        cov.hit("arrivals", "pool_full")
    if any("exceeds KV page pool" in v for v in violations):
        cov.hit("arrivals", "infeasible_reject")


class ServingEngine:
    """One serving engine behind its CSR control plane; a co-verification
    target (core/target.py)."""

    kind = "serving"

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 4,
                 max_len: int = 256,
                 flags: RunFlags = RunFlags(microbatches=1),
                 ctx: Optional[ShardCtx] = None,
                 prompt_pad: int = 16,
                 congestion: Optional[CongestionConfig] = None,
                 fault_plan=None,
                 jit_fns=None,
                 profile: bool = False,
                 batching: str = "storm",
                 kv_pages: Optional[int] = None,
                 kv_page_size: int = 16,
                 kv_leak_every: int = 0,
                 step_cycles: float = 64.0):
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.flags = flags
        self.prompt_pad = prompt_pad
        self.congestion = congestion
        self.profile = profile
        # scheduling mode: "storm" is the closed-loop legacy tick (admit
        # ONE request or decode — the committed golden traces);
        # "continuous" is the open-loop tick (admit as many as slots AND
        # KV pages allow, then decode the whole batch) with a modeled
        # clock advanced by per-step costs — serving/arrivals.py drives it
        if batching not in ("storm", "continuous"):
            raise ValueError(f"unknown batching mode {batching!r}")
        self.batching = batching
        # KV paging (serving/kvpool.py): kv_pages=None runs unpaged;
        # kv_leak_every is the planted late-firing paging bug for the
        # replay-bisect tier
        self.kv_pages = kv_pages
        self.kv_page_size = kv_page_size
        self.kv_leak_every = kv_leak_every
        # modeled cost of one decode step (and of one prompt bucket of
        # prefill) on the engine clock, in cycles
        self.step_cycles = float(step_cycles)

        # `jit_fns` shares one (prefill, decode) executable pair across
        # device-local engines of a ClusterServingEngine — N devices, one
        # compilation (the FireSim "build once, run many" economy).
        if jit_fns is not None:
            self._prefill, self._decode = jit_fns
        else:
            self._prefill = jax.jit(make_prefill_fn(cfg, flags, ctx,
                                                    max_len))
            self._decode = jax.jit(make_decode_fn(cfg, flags, ctx))
        self.reset(fault_plan=fault_plan)

    @property
    def jit_fns(self):
        """The shareable (prefill, decode) executable pair."""
        return (self._prefill, self._decode)

    def reset(self, fault_plan=None, **overrides) -> None:
        """Restore fresh-engine state (cache, slots, queues, control plane,
        KV page pool, modeled clock) while keeping the jitted prefill/
        decode executables — used by the fuzz harness (core/fuzz.py) to
        run many randomized submit streams at warm-cache cost.
        ``fault_plan`` routes the engine's prompt/token DMA through
        bridge-level fault injection.  ``overrides`` reconfigures the
        scheduling axes for the rerun: ``batching``, ``kv_pages``,
        ``kv_page_size``, ``kv_leak_every``, ``step_cycles``."""
        for key in ("batching", "kv_pages", "kv_page_size",
                    "kv_leak_every", "step_cycles"):
            if key in overrides:
                setattr(self, key, overrides.pop(key))
        if overrides:
            raise TypeError(f"unknown reset overrides: {sorted(overrides)}")
        self.cache = init_cache(self.cfg, self.max_slots, self.max_len)
        self.slots: List[Optional[Request]] = [None] * self.max_slots
        self.pending: deque[Request] = deque()
        self.requests: Dict[int, Request] = {}
        self.completed = 0
        self.clock = 0.0
        self.kv_pool: Optional[KVPool] = (
            KVPool(self.kv_pages, self.kv_page_size,
                   leak_every=self.kv_leak_every)
            if self.kv_pages is not None else None)

        # control plane; with `congestion` the prompt/token DMA traffic is
        # arbitrated online through the shared-link model (paper §IV-C)
        self.mem = MemoryBridge(congestion=self.congestion,
                                fault_plan=fault_plan,
                                profile=self.profile)
        self.csr = RegisterFile("serve.csr", self.mem.log)
        self.csr.define("CTRL", CTRL)
        self.csr.define("STATUS", STATUS, access=RO)
        self.csr.define("DOORBELL", DOORBELL, on_write=self._on_doorbell)
        self.csr.define("SUBMIT_ID", SUBMIT_ID)
        self.csr.define("SUBMIT_LEN", SUBMIT_LEN)
        self.csr.define("SUBMIT_MAXNEW", SUBMIT_MAXNEW)
        self.csr.define("COMPLETED", COMPLETED, access=RO)
        self.csr.define("ACTIVE", ACTIVE, access=RO)
        self.mem.alloc("prompt_in", (self.max_len,), np.int32)
        self.mem.alloc("tokens_out", (self.max_slots, self.max_len),
                       np.int32)

        # always-on sampled counters (core/counters.py).  Functional-
        # scope counters (doorbells, requests/tokens retired) have
        # cumulative totals invariant across 1/2/4 devices — the
        # cross-scale side of the counter-diff oracle; the KV gauges are
        # per-engine timing-scope.  Rebuilt here because the pool and
        # bridge the probes read are rebuilt on every reset.
        self.counters = CounterBank("serving")
        self.counters.register(
            CounterSpec("doorbells", "events", scope="functional"))
        self.counters.register(
            CounterSpec("requests_retired", "events", scope="functional"))
        self.counters.register(
            CounterSpec("tokens_retired", "tokens", scope="functional"))
        if self.kv_pool is not None:
            pool = self.kv_pool
            self.counters.register(
                CounterSpec("kv_pages_in_use", "pages", monotone=False),
                lambda: pool.in_use)
            self.counters.register(CounterSpec("kv_peak_pages", "pages"),
                                   lambda: pool.peak_in_use)
            self.counters.register(CounterSpec("kv_deferrals", "events"),
                                   lambda: pool.deferrals)
            self.counters.register(CounterSpec("kv_releases", "events"),
                                   lambda: pool.releases)

    # -------------------------------------------------- register protocol
    def _on_doorbell(self, _data: int) -> None:
        self.counters.inc("doorbells")
        rid = self.csr.hw_get("SUBMIT_ID")
        ln = self.csr.hw_get("SUBMIT_LEN")
        mx = self.csr.hw_get("SUBMIT_MAXNEW")
        if ln <= 0 or ln > self.max_len:
            self.csr.log.violation(f"SUBMIT_LEN out of range: {ln}")
            return
        if self.batching == "continuous":
            # keep the DMA time domain and the engine clock in lockstep:
            # the prompt upload happens "now" on the modeled clock, and the
            # clock absorbs whatever the (possibly congested/faulted) link
            # charged for it
            self.mem.time = max(self.mem.time, self.clock)
        prompt = self.mem.dev_read("prompt_in", engine="serve_dma")[:ln]
        if self.batching == "continuous":
            self.clock = max(self.clock, self.mem.time)
        self.submit(Request(rid, prompt.astype(np.int32), mx))

    # ---------------------------------------------------------- scheduler
    def submit(self, req: Request) -> None:
        """Enqueue one request; rejects (with a logged violation, never a
        silent overwrite) non-positive token budgets and duplicate ids."""
        if req.max_new_tokens <= 0:
            self.csr.log.violation(
                f"SUBMIT_MAXNEW must be positive: {req.max_new_tokens} "
                f"(request {req.rid})")
            return
        # ids may be recycled once their request retired (bounded-width
        # SUBMIT_ID CSR); only an in-flight duplicate is a violation
        existing = self.requests.get(req.rid)
        if existing is not None and not existing.done:
            self.csr.log.violation(
                f"duplicate SUBMIT_ID {req.rid}: request still in flight")
            return
        # KV-cache capacity: prefill occupies the padded prompt bucket and
        # each decode step appends one entry — past max_len the cache
        # scatter would be silently dropped and generations corrupted
        pl = self._pad_len(len(req.prompt))
        if (len(req.prompt) > self.max_len
                or pl + req.max_new_tokens - 1 > self.max_len):
            self.csr.log.violation(
                f"request {req.rid} exceeds KV capacity: padded prompt "
                f"{pl} + {req.max_new_tokens} new tokens > max_len "
                f"{self.max_len}")
            return
        # page-pool feasibility: a request whose worst-case footprint
        # exceeds the WHOLE pool could never be admitted — deferring it
        # would livelock the FIFO, so it is rejected at the doorbell
        if self.kv_pool is not None and \
                not self.kv_pool.fits(pl + req.max_new_tokens - 1):
            self.csr.log.violation(
                f"request {req.rid} exceeds KV page pool: "
                f"{self.kv_pool.pages_for(pl + req.max_new_tokens - 1)} "
                f"pages needed > {self.kv_pool.n_pages} total")
            return
        req.t_submit = self.clock
        self.pending.append(req)
        self.requests[req.rid] = req

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _pad_len(self, n: int) -> int:
        p = self.prompt_pad
        return min(self.max_len, -(-n // p) * p)

    def step(self) -> int:
        """One scheduler tick.  Storm (legacy closed-loop) mode: admit one
        pending request (prefill+insert) OR run one batched decode step —
        the committed golden traces.  Continuous (open-loop) mode: admit
        as many pending requests as free slots and KV pages allow, then
        decode the whole batch, advancing the modeled clock by per-step
        costs.  Returns number of active slots."""
        n = (self._step_continuous() if self.batching == "continuous"
             else self._step_storm())
        # sample after the tick's state updates, on the front of the two
        # time domains (storm mode never advances self.clock; the DMA
        # clock still does)
        self.counters.tick(max(self.clock, self.mem.time))
        return n

    def _step_storm(self) -> int:
        slot = self._free_slot()
        if self.pending and slot is not None:
            req = self.pending.popleft()
            self._prefill_admit(slot, req)
            self.csr.hw_set("ACTIVE", self._n_active())
            return self._n_active()

        if self._n_active():
            self._decode_step()
            self.csr.hw_set("ACTIVE", self._n_active())
        return self._n_active()

    def _prefill_admit(self, slot: int, req: Request) -> None:
        """Prefill ``req`` into ``slot``: bucket-padded prefill, cache
        insert, first-token emit (shared by the storm and continuous
        schedulers; bit-exact with the legacy tick)."""
        # Left-pad to the prefill bucket.  The prefill gives the pad keys
        # position -1, so neither it nor any later decode step attends to
        # them; RoPE scores depend only on position deltas, so the
        # constant offset of the real tokens is exact for attention
        # families.  For SSM/hybrid the leading pad tokens still perturb
        # the recurrent state unless the prompt length is already a bucket
        # multiple.
        pl = self._pad_len(len(req.prompt))
        pad_n = pl - len(req.prompt)
        toks = np.zeros((1, pl), np.int32)
        toks[0, pad_n:] = req.prompt
        logits, single = self._prefill(self.params, self._batchify({
            "tokens": jnp.asarray(toks),
            "n_pad": jnp.asarray([pad_n], jnp.int32)}))
        self.cache = cache_insert(self.cache, single, slot)
        self.slots[slot] = req
        first = int(jnp.argmax(logits[0]))
        req.out_tokens.append(first)
        # the prefill itself emits one token: a max_new_tokens=1
        # request is complete right here, not after a decode step
        if len(req.out_tokens) >= req.max_new_tokens:
            self._retire(slot)

    def _decode_step(self) -> None:
        """One batched decode step over all occupied slots (shared by the
        storm and continuous schedulers; bit-exact with the legacy tick)."""
        toks = np.zeros((self.max_slots,), np.int32)
        for i, s in enumerate(self.slots):
            if s is not None:
                toks[i] = s.out_tokens[-1] % self.cfg.vocab_size
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(toks))
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            s.out_tokens.append(int(nxt[i]))
            if len(s.out_tokens) >= s.max_new_tokens:
                self._retire(i)

    def _step_continuous(self) -> int:
        """Continuous-batching tick: FIFO admission (no head-of-line
        bypass — the admitted set stays a pure function of arrival order
        and pool geometry) up to slot/page limits, then one batched decode
        over everything resident.  The modeled clock pays
        ``step_cycles`` per prompt bucket of prefill and per decode step."""
        admitted = 0
        while self.pending:
            slot = self._free_slot()
            if slot is None:
                break
            req = self.pending[0]
            pl = self._pad_len(len(req.prompt))
            if self.kv_pool is not None and not self.kv_pool.reserve(
                    req.rid, pl + req.max_new_tokens - 1):
                break       # FIFO: deferred head blocks the queue
            self.pending.popleft()
            req.t_admit = self.clock
            self.clock += self.step_cycles * max(1, pl // self.prompt_pad)
            req.t_first = self.clock
            self._prefill_admit(slot, req)
            admitted += 1
        if self._n_active():
            self.clock += self.step_cycles
            self._decode_step()
        elif not admitted and self.pending:
            # nothing runnable (pages short of the FIFO head — only
            # reachable under an injected leak): modeled time must still
            # progress so the open-loop driver's max_ticks bound fires
            # instead of freezing the clock
            self.clock += self.step_cycles
        self.csr.hw_set("ACTIVE", self._n_active())
        return self._n_active()

    def advance_clock(self, t: float) -> None:
        """Fast-forward the modeled clock to ``t`` (idle-gap skip by the
        open-loop driver; never moves time backwards)."""
        self.clock = max(self.clock, float(t))
        self.counters.tick(max(self.clock, self.mem.time))

    def _retire(self, i: int) -> None:
        """Complete slot i: tokens_out DMA writeback, slot free,
        COMPLETED CSR update (shared by the prefill and decode paths)."""
        s = self.slots[i]
        s.done = True
        s.t_done = self.clock
        self.counters.inc("requests_retired")
        self.counters.inc("tokens_retired", len(s.out_tokens))
        if self.kv_pool is not None:
            self.kv_pool.release(s.rid)
        # row-sized DMA writeback: only slot i's tokens move
        buf = self.mem.buffers["tokens_out"]
        buf.array[i, :len(s.out_tokens)] = s.out_tokens
        row = buf.array[i]
        if self.batching == "continuous":
            # writeback is issued at the engine clock; the clock then
            # absorbs the link's makespan (congestion/faults show up as
            # inter-token latency, not just log entries)
            self.mem.log_burst_list(
                [("serve_dma", "write",
                  buf.addr + i * row.nbytes, row.nbytes)],
                base_time=max(self.mem.time, self.clock))
            self.clock = max(self.clock, self.mem.time)
        else:
            self.mem.log_burst_list(
                [("serve_dma", "write",
                  buf.addr + i * row.nbytes, row.nbytes)])
        self.slots[i] = None
        self.completed += 1
        self.csr.hw_set("COMPLETED", self.completed)

    def _batchify(self, batch):
        if self.cfg.frontend == "tokens+patches":
            B, M = 1, self.cfg.n_media_tokens
            batch["patches"] = jnp.zeros((B, M, self.cfg.d_model), jnp.float32)
        return batch

    def _n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def _n_pending(self) -> int:
        return len(self.pending)

    # ------------------------------------------- target contract members
    def logs(self):
        return [self.mem.log]

    def counter_banks(self):
        """The serving-lifecycle bank, then the DMA bridge's bank."""
        return [self.counters, self.mem.counters]

    @property
    def modeled_time(self) -> float:
        return float(self.clock)

    @property
    def violations(self) -> List[str]:
        return list(self.mem.log.violations)

    def fault_events(self) -> List:
        return self.mem.fault_events()

    def outputs(self) -> Dict[str, np.ndarray]:
        return token_outputs(self.requests)

    def congestion_stats(self) -> Optional[CongestionResult]:
        """Fig. 8 stall statistics of the serving DMA traffic (None when
        the engine runs congestion-free)."""
        return self.mem.congestion_stats()

    def state_summary(self) -> dict:
        return serving_summary(self, self.mem.time, [self], self.violations)

    def profile_view(self, prefix: str = "") -> ProfileView:
        """The DMA channel (prompt uploads and token writebacks split by
        the ``serve_dma`` read/write kind) and the CSR protocol clock,
        names under ``prefix`` (a cluster's ``e{i}/``), and the request
        lifecycles."""
        return ProfileView(self.mem.channels(prefix, self.csr),
                           requests=request_spans([(0, self)]))

    def cover(self, cov) -> None:
        """Burst sizes and congestion outcomes of the log, and the KV-pool
        admission bins."""
        cov.hit_logs(self.logs())
        cover_admission(cov, [self], self.violations)

    def profiler(self, label: str = "serving"):
        from repro.core.profiler import DataMovementProfiler
        return DataMovementProfiler(self, label=label)

    # --------------------------------------------- checkpoint/restore hooks
    def get_state(self) -> dict:
        """Engine snapshot at a scheduler-tick boundary (core/replay.py):
        KV/state cache, request table, slot map, pending queue, and the
        control plane (bridge DDR + CSR values + transaction log).  The
        jitted prefill/decode executables are structure, not state — a
        restored engine reuses the live ones, so restore is warm-jit cheap.

        Requests are copied by rid so the slots/pending/requests aliasing
        (one object, three views) survives the round-trip."""
        reqs = {rid: _copy_request(r) for rid, r in self.requests.items()}
        return {
            "cache": dict(self.cache),      # jax arrays are immutable
            "requests": reqs,
            "slots": [s.rid if s is not None else None for s in self.slots],
            "pending": [r.rid for r in self.pending],
            "completed": self.completed,
            "clock": self.clock,
            "kv_pool": (self.kv_pool.get_state()
                        if self.kv_pool is not None else None),
            "mem": self.mem.get_state(),    # includes the shared log
            "csr": self.csr.get_state(),
            "counters": self.counters.get_state(),
        }

    def set_state(self, state: dict) -> None:
        self.cache = dict(state["cache"])
        self.requests = {rid: _copy_request(r)
                         for rid, r in state["requests"].items()}
        self.slots = [self.requests[rid] if rid is not None else None
                      for rid in state["slots"]]
        self.pending = deque(self.requests[rid] for rid in state["pending"])
        self.completed = state["completed"]
        # pre-paging checkpoints (storm-mode recordings) carry neither key
        self.clock = state.get("clock", 0.0)
        pool_state = state.get("kv_pool")
        if pool_state is not None and self.kv_pool is not None:
            self.kv_pool.set_state(pool_state)
        self.mem.set_state(state["mem"])
        self.csr.set_state(state["csr"])
        cs = state.get("counters")
        if cs is not None:
            self.counters.set_state(cs)

    def run_until_done(self, max_ticks: int = 10_000) -> None:
        self.csr.hw_set("STATUS", 1)
        for _ in range(max_ticks):
            if not self.pending and self._n_active() == 0:
                break
            self.step()
        self.csr.hw_set("STATUS", 2)
