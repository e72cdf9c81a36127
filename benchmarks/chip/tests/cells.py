"""Tiny CPU-sized versions of the benchmark's cells, for the tests: the
same drivers, references and checks as on the chip, with the chip's
backend tables swapped for the interpret-mode ones."""
from __future__ import annotations

import copy
import json

from bench import common

RUN = common.load_module(common.BENCH_DIR / "run.py")
BENCH = common.load_json(common.ROOT / "BENCHMARK.json")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]

KERNELS = {"matmul": {"M": 256, "K": 384, "N": 512},
           "flash": {"B": 1, "H": 4, "KH": 2, "S": 256, "D": 128,
                     "causal": True}}


def cpu_tables(tile: int):
    from repro.kernels.flash_attention.sweep import flash_backends
    from repro.kernels.systolic_matmul.sweep import matmul_backends
    return {"matmul": matmul_backends(tile), "flash": flash_backends(tile,
                                                                     tile)}


def found(workload: str):
    """The cell as run.py finds it, from its own files, cut to a CPU
    size."""
    f = common.find_cell(BENCH, workload)
    f["config"]["kernels"] = copy.deepcopy(KERNELS)
    f["traffic"]["tile"] = 128
    return f


def run_cell(f, seed: int = 7, seconds: float = 0.5, tables=cpu_tables,
             control=None):
    ctx = RUN.Ctx(f, seed, seconds, False, 0.0, tables=tables)
    ctx.control = control
    return RUN.run_cell(f, ctx, peak={}), ctx


def dumps(result) -> str:
    return json.dumps(result)
