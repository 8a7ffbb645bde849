"""`pytest benchmark/tests`: the benchmark's own tests, on the CPU, apart from
the repository's `tests/`."""

import os
import pathlib
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def run_cell(root, *argv, timeout=600):
    """`run.py` of the checkout at `root` in a new process, on the CPU.
    -> (exit code, stdout lines, stderr)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, str(pathlib.Path(root) / "benchmark" / "run.py"),
         *argv], capture_output=True, text=True, timeout=timeout, env=env,
        cwd=root)
    return done.returncode, done.stdout.strip().splitlines(), done.stderr


@pytest.fixture(scope="session")
def root():
    return ROOT
