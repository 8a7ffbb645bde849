"""No module of the benchmark touches JAX when it is imported: a parent that
only reads the data files never takes the chip."""

import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_importing_the_benchmark_does_not_import_jax():
    files = [p for p in (ROOT / "benchmark").rglob("*.py")
             if "tests" not in p.parts and "tools" not in p.parts
             and p.name != "run.py"]
    code = """
import importlib.util, sys
sys.path.insert(0, %r)
for n, path in enumerate(%r):
    s = importlib.util.spec_from_file_location(f"m{n}", path)
    m = importlib.util.module_from_spec(s); sys.modules[f"m{n}"] = m
    s.loader.exec_module(m)
assert "jax" not in sys.modules, "jax was imported"
print("clean", n + 1)
""" % (str(ROOT), [str(p) for p in files])
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.startswith("clean")
