"""Worker-process entry point (spawn context).

One worker = one process with a PRIVATE task queue; the manager assigns
units one at a time and tracks the assignment, FireSim
instance-deploy-manager style.  Private queues mean a SIGKILL'd worker
can never die holding a shared queue lock and wedge its peers — the
manager just notices the dead process, re-enqueues its assigned unit to
a fresh worker, and carries on.

Spawn (not fork) keeps workers clean of the parent's jax/session state;
``extra_sys_path`` re-creates the parent's import path (sys.path does not
propagate across spawn).  A worker restricts jax to the CPU before any
computation: an accelerator belongs to one process (the parent may hold
it), and every lane of a campaign computes on the CPU.  An environment
variable set in ``worker_main`` would come too late, since unpickling the
target already imported jax.
``kill_after`` is the crash-recovery test hook: the worker SIGKILLs
itself when it receives its (N+1)-th unit — after the assignment, before
any result — the worst-case death point.
"""
from __future__ import annotations

import os
import signal
import sys


def worker_main(worker_id: int, task_q, result_q, extra_sys_path,
                kill_after=None) -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")
    for p in reversed(list(extra_sys_path or [])):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.runfarm.builtin import execute_unit
    from repro.runfarm.units import WorkUnit

    done = 0
    while True:
        msg = task_q.get()
        if msg is None:                       # clean shutdown
            result_q.put(("bye", worker_id, None))
            return
        unit = WorkUnit.from_json(msg)
        if kill_after is not None and done >= kill_after:
            os.kill(os.getpid(), signal.SIGKILL)    # test hook: die dirty
        try:
            res = execute_unit(unit)
            res.worker = worker_id
            result_q.put(("done", worker_id,
                          res.record(unit.payload_hash())))
        except BaseException as e:            # unit execution error: the
            result_q.put(("error", worker_id,  # manager records + re-raises
                          {"uid": unit.uid,
                           "error": f"{type(e).__name__}: {e}"}))
        done += 1
