"""A second configuration joins the benchmark as new files and entries
only.  A copy of ``benchmarks/chip`` and ``BENCHMARK.json`` gets the
stand-in of ``tests/standin/`` laid over it: a configuration and its plain
reference, a traffic file, a driver (an elementwise kernel co-verified
through ``CoVerifySession``), its CPU case and one per-layer reader, each a
file the copy did not have, and their entries appended to BENCHMARK.json,
the cell's name to ``sweep_s``'s ``workloads``.  The benchmark's own CPU
tests then take the stand-in's cell with no edit to an existing file."""
import json
import os
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET
from pathlib import Path

import pytest

from bench import common

STANDIN = Path(__file__).resolve().parent / "standin"
CELL = "coverify-standin-axpy"
TIMEOUT = 600


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The copy with the stand-in laid over it."""
    root = tmp_path_factory.mktemp("checkout")
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(common.BENCH_DIR, bench_dir, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "standin", Path(__file__).name))
    for src in sorted(STANDIN.rglob("*")):
        if not src.is_file() or src.name == "entries.json" \
                or "__pycache__" in src.parts:
            continue
        dst = bench_dir / src.relative_to(STANDIN)
        assert not dst.exists(), f"{dst} is not a new file"
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(src, dst)
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    entries = common.load_json(STANDIN / "entries.json")
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += entries[key]
    for m in bench["end_to_end"]:
        m.get("workloads", []).extend(entries["join"].get(m["name"], []))
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    return bench_dir


def _run(args, cwd):
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST")}
    env["PYTHONPATH"] = os.pathsep.join(
        [str(common.ROOT / "src")] + ([env["PYTHONPATH"]]
                                      if env.get("PYTHONPATH") else []))
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=TIMEOUT)


def test_standin_cell_passes_the_benchmark_tests(checkout, tmp_path):
    """Its cell runs at its CPU size and is correct, its control and each
    planted fault come out not correct, and its reader reads its sample
    run and nothing on an empty one."""
    xml = tmp_path / "junit.xml"
    proc = _run(["-m", "pytest", "-q", "-p", "no:cacheprovider",
                 "-p", "no:randomly", f"--junitxml={xml}",
                 "-k", "axpy or every_",
                 "tests/test_cells_cpu.py", "tests/test_bench_controls.py",
                 "tests/test_bench_faults.py", "tests/test_bench_readers.py"],
                cwd=checkout)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    ran = {c.get("name"): [e.tag for e in c]
           for c in ET.parse(xml).getroot().iter("testcase")}
    assert ran == {n: [] for n in [
        f"test_coverify_cell_is_correct[{CELL}]",
        "test_every_cell_has_a_cpu_case",
        *(f"test_control_is_not_correct[{CELL}-{s}]" for s in (11, 12, 13)),
        *(f"test_broken_kernel_answer_is_not_correct[{CELL}-{f}]"
          for f in ("altered", "half_left_out", "unchanged")),
        "test_every_listed_metric_has_a_reader",
        "test_reader_reads_its_cells[axpy_roofline]"]}


SCRIPT = """
import json, sys
sys.path[:0] = ["tests", "."]
import cells
result, _ = cells.run_cell(cells.found(CELL))
print(json.dumps(sorted(result["metrics"])))
for m in cells.BENCH["end_to_end"]:
    if CELL in m.get("workloads", []):
        m["workloads"].remove(CELL)
try:
    cells.run_cell(cells.found(CELL))
except RuntimeError as e:
    print(e)
"""


def test_standin_result_line_and_the_metric_it_must_join(checkout):
    """The result line carries ``setup_s`` and ``sweep_s``; a cell left out
    of ``sweep_s``'s ``workloads`` is refused by an error that names the
    metric and the list to join."""
    proc = _run(["-c", f"CELL = {CELL!r}" + SCRIPT], cwd=checkout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    metrics, refusal = proc.stdout.strip().splitlines()[-2:]
    assert json.loads(metrics) == ["setup_s", "sweep_s"]
    assert "'sweep_s'" in refusal and '"workloads" list' in refusal
    assert repr(CELL) in refusal
