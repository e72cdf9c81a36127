"""Example/benchmark artifact hygiene: nothing lands at the repo root.

PR history: examples/profile_cnn.py used to default its Perfetto export
to ``profile_cnn.trace.json`` in the current directory, which left an
untracked artifact at the repo root after every docs run.  Default
output paths must land under a gitignored ``artifacts/`` directory
(``artifacts/``, ``benchmarks/artifacts/``, ``tests/artifacts/``) or an
explicit tempdir; this suite enforces that statically (argparse
defaults) and dynamically (running the one exporting example).
"""
import ast
import contextlib
import importlib.util
import io
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

sys.path.insert(0, str(ROOT / "src"))

# suffixes that mark an argparse default as a file/dir OUTPUT path
_ARTIFACT_SUFFIXES = (".json", ".jsonl", ".csv", ".txt", ".trace")
# a default path is fine if it is absolute-temp or under a gitignored
# artifacts dir
_ALLOWED_PREFIXES = ("artifacts/", "benchmarks/artifacts/",
                     "tests/artifacts/", "/tmp/")


def _argparse_string_defaults(path: Path):
    """Yield (lineno, default) for every ``add_argument(..., default=<str>)``
    in the file."""
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"):
            continue
        for kw in node.keywords:
            if (kw.arg == "default" and isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, str)):
                yield node.lineno, kw.value.value


def test_default_output_paths_are_gitignored():
    """Static scan: every examples/ and benchmarks/ argparse default that
    names an output file must land under a gitignored artifacts dir."""
    offenders = []
    for d in ("examples", "benchmarks"):
        for py in sorted((ROOT / d).glob("*.py")):
            for lineno, default in _argparse_string_defaults(py):
                if not default.endswith(_ARTIFACT_SUFFIXES):
                    continue
                if not default.startswith(_ALLOWED_PREFIXES):
                    offenders.append(
                        f"{py.relative_to(ROOT)}:{lineno}: "
                        f"default={default!r} writes outside artifacts/")
    assert not offenders, "\n".join(offenders)


def test_repo_root_has_no_stray_artifacts():
    """Only the committed benchmark baselines may sit as .json at the
    repo root (the historical offender was profile_cnn.trace.json)."""
    committed = {"BENCH_runfarm.json", "BENCH_serving.json",
                 "BENCH_simspeed.json", "BENCH_counters.json",
                 "BENCHMARK.json"}
    stray = sorted(p.name for p in ROOT.glob("*.json")
                   if p.name not in committed)
    assert not stray, f"untracked artifacts at repo root: {stray}"


def test_profile_cnn_defaults_write_under_artifacts(tmp_path, monkeypatch):
    """Dynamic check: running the exporting example with DEFAULT args
    from a scratch cwd creates artifacts/ there and touches nothing at
    the repo root."""
    before = {p.name for p in ROOT.iterdir()}
    spec = importlib.util.spec_from_file_location(
        "profile_cnn_hygiene", ROOT / "examples" / "profile_cnn.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.chdir(tmp_path)
    with contextlib.redirect_stdout(io.StringIO()):
        mod.main([])
    assert (tmp_path / "artifacts" / "profile_cnn.trace.json").exists()
    after = {p.name for p in ROOT.iterdir()}
    assert after == before, f"repo root changed: {sorted(after - before)}"
