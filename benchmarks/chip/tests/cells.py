"""Tiny CPU-sized versions of the benchmark's cells, for the tests: the
same drivers, references and checks as on the chip, with the chip's
backend tables swapped for the interpret-mode ones.

What the tests need to know of a cell's driver lives in the driver's case
module, ``tests/cases/<driver>.py`` (``traffic["driver"]`` names it): the
cell cut to a CPU size (``cpu_found``), the interpret-mode tables
(``cpu_tables``), the ops a planted fault breaks (``OPS``) and the checks
it must fail (``fault_checks``), the control (``CONTROL``) and the checks
it must fail (``CONTROL_FAILS``), and a hand-made traced run for the
readers of its cells (``sample_run``, ``empty_run``).  A cell with a new
driver joins every test here by bringing that file.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

from bench import common

RUN = common.load_module(common.BENCH_DIR / "run.py")
BENCH = common.load_json(common.ROOT / "BENCHMARK.json")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
CASES_DIR = Path(__file__).resolve().parent / "cases"


def driver_of(workload: str) -> str:
    return common.find_cell(BENCH, workload)["traffic"]["driver"]


def case_path(workload: str) -> Path:
    return CASES_DIR / f"{driver_of(workload)}.py"


@functools.cache
def _case(path: Path):
    return common.load_module(path)


def case(workload: str):
    """The case module of the cell's driver."""
    path = case_path(workload)
    if not path.exists():
        raise FileNotFoundError(
            f"cell {workload!r} runs driver {driver_of(workload)!r}, which "
            f"has no CPU test case: add {path.relative_to(common.ROOT)}")
    return _case(path)


def cells_breaking(*ops: str):
    """The cells whose case lists each of ``ops`` among its faults' ops (a
    cell without a case is listed nowhere; the completeness test names
    it)."""
    return [w for w in WORKLOADS if case_path(w).exists()
            and set(ops) <= set(_case(case_path(w)).OPS)]


def found(workload: str):
    """The cell as run.py finds it, from its own files, cut to a CPU
    size by its case."""
    return case(workload).cpu_found(common.find_cell(BENCH, workload))


def run_cell(f, seed: int = 7, seconds: float = 0.5, tables=None,
             control=None):
    tables = tables or case(f["cell"]["name"]).cpu_tables
    ctx = RUN.Ctx(f, seed, seconds, False, 0.0, tables=tables)
    ctx.control = control
    return RUN.run_cell(f, ctx, peak={}), ctx


def dumps(result) -> str:
    return json.dumps(result)
