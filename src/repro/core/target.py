"""The co-verification target contract: what one sweep cell, one
recording or one profile runs against.  ``FireBridge``, ``FabricCluster``,
``ServingEngine`` and ``ClusterServingEngine`` implement
``CoVerifyTarget``.  The session (core/scheduler.py), replay
(core/replay.py), the profiler (core/profiler.py) and the counter oracle
(core/counters.py) read a target through its members alone, so a new
kind of target is a class that provides them, not an edit to those
modules.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import (Any, Dict, FrozenSet, List, NamedTuple, Optional,
                    Protocol, Tuple, runtime_checkable)

import numpy as np


class Channel(NamedTuple):
    """One labelled channel for the profiler.  ``kind`` "link": ``source``
    is a congestion-arbitrated ``LinkModel``; "clock": a fast-path
    ``MemoryBridge``, whose log may also hold the transactions of the CSR
    files named in ``exclude``; "csr": a ``RegisterFile``."""
    name: str
    kind: str
    source: Any
    exclude: FrozenSet[str] = frozenset()


@dataclasses.dataclass
class ProfileView:
    """What the profiler reads of a target: its channels in report
    order, the (log, OpMark) pairs of its op marks, and (serving) the
    lifecycles of its completed requests."""
    channels: List[Channel]
    marks: List[Tuple[Any, Any]] = dataclasses.field(default_factory=list)
    requests: List[dict] = dataclasses.field(default_factory=list)


@runtime_checkable
class CoVerifyTarget(Protocol):
    """The members every co-verification target provides."""

    #: the event vocabulary ``replay.apply_event`` runs against it:
    #: "bridge", "fabric" or "serving"
    kind: str

    def logs(self) -> List[Any]:
        """Transaction logs, in the golden traces' order (own log first)."""

    def counter_banks(self) -> List[Any]:
        """Every counter bank the target owns, in a stable order."""

    @property
    def modeled_time(self) -> float:
        """Modeled completion time so far, in cycles."""

    @property
    def violations(self) -> List[str]:
        """Protocol violations logged so far (a fresh list)."""

    def fault_events(self) -> List[Any]:
        """The ``fuzz.FaultEvent``s injected so far."""

    def outputs(self) -> Dict[str, Any]:
        """The equivalence surface the sweep diffs: the final state, lent
        read-only; valid until the target runs again, and the session's
        collect is its last reader."""

    def congestion_stats(self) -> Optional[Any]:
        """``CongestionResult`` of the memory traffic, or None."""

    def state_summary(self) -> Dict[str, Any]:
        """The device state a divergence report prints around an op."""

    def profile_view(self) -> ProfileView:
        """What the data-movement profiler attributes."""

    def cover(self, cov: Any) -> None:
        """Hit in ``cov`` the bins read off a finished run (not live)."""

    def get_state(self) -> Dict[str, Any]:
        """Deep snapshot at a transaction boundary (core/replay.py)."""

    def set_state(self, state: Dict[str, Any]) -> None:
        """Restore a snapshot into a structurally identical target."""

    def profiler(self, label: Optional[str] = None) -> Any:
        """The target's ``DataMovementProfiler``."""


def lend(array: np.ndarray) -> np.ndarray:
    """A read-only view of ``array``: how ``outputs()`` lends a buffer
    without copying it."""
    view = array.view()
    view.flags.writeable = False
    return view


def log_digest(target: CoVerifyTarget) -> str:
    """sha256 over the digests of ``target.logs()``: the same-seed
    reproducibility witness of a whole run."""
    h = hashlib.sha256()
    for log in target.logs():
        h.update(log.digest().encode())
    return h.hexdigest()
