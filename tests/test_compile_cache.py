"""use_compile_cache(): JAX_COMPILATION_CACHE_DIR wins when it is set;
otherwise the cache sits at one fixed, gitignored path in the checkout.

Each case runs in a fresh interpreter, because JAX reads the variable
when it is imported."""
import os
import subprocess
import sys
from pathlib import Path

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

ROOT = Path(__file__).resolve().parents[1]


def _cache_dir_in_fresh_process(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.launch.compile_cache import use_compile_cache; "
         "print(use_compile_cache())"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.strip().splitlines()[-1]


def test_environment_variable_wins(tmp_path):
    assert _cache_dir_in_fresh_process(tmp_path) == str(tmp_path)


def test_fixed_checkout_path_otherwise():
    assert _cache_dir_in_fresh_process(None) == str(CHECKOUT_CACHE_DIR)
    assert CHECKOUT_CACHE_DIR == ROOT / "benchmarks" / "artifacts" / \
        "jax_cache"
    assert "benchmarks/artifacts/" in \
        (ROOT / ".gitignore").read_text().splitlines()
