"""Shared plumbing for the evidence runners (tools/evidence/*.py).

Each runner proves one subsystem end-to-end and writes a timestamped,
committed log to EVIDENCE/ carrying the git SHA, host fingerprint, and
full output — the artifact class VERDICT r4 asked for ("a committed,
timestamped, reproducible artifact, not prose").  Run them all with
`make evidence`.

Runners prove their subsystem on an 8-virtual-device CPU mesh and say
so: `ensure_cpu_mesh` runs before jax is imported, requires
``JAX_PLATFORMS=cpu`` in the environment (a runner must not take a
host's chips by accident) and sets the device count in process.
"""

from __future__ import annotations

import contextlib
import io
import os
import pathlib
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent.parent
EVIDENCE = REPO / "EVIDENCE"
if str(REPO) not in sys.path:  # scripts run from tools/evidence/
    sys.path.insert(0, str(REPO))


def ensure_cpu_mesh(n_devices: int = 8) -> None:
    """An n-device virtual CPU mesh for this process, set before jax
    initialises — or exit with the command that provides one."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"{pathlib.Path(sys.argv[0]).name} runs on a virtual CPU "
            f"mesh; run it as\n  JAX_PLATFORMS=cpu python "
            f"{' '.join(sys.argv)}")
    import jax

    jax.config.update("jax_num_cpu_devices", n_devices)


def write_log(name: str, body: str) -> pathlib.Path:
    EVIDENCE.mkdir(exist_ok=True)
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=REPO, capture_output=True,
                             text=True).stdout.strip() or "unknown"
    except OSError:  # no git binary: the tree may be a bare copy
        sha = "unknown"
    stamp = time.strftime("%Y%m%d_%H%M", time.gmtime())
    path = EVIDENCE / f"{name}_{stamp}.log"
    head = (f"== {name}  {time.strftime('%a %b %d %H:%M:%S UTC %Y', time.gmtime())}"
            f"  sha={sha}\n"
            f"host: {os.cpu_count()} cpu core(s); "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')} "
            f"XLA_FLAGS={os.environ.get('XLA_FLAGS', '')}\n"
            f"command: python {' '.join(sys.argv)}\n")
    path.write_text(head + body)
    print(f"-> {path.relative_to(REPO)}")
    return path


@contextlib.contextmanager
def capture():
    """Tee stdout to both the console and the returned buffer."""
    buf = io.StringIO()
    real = sys.stdout

    class Tee(io.TextIOBase):
        def write(self, s):
            real.write(s)
            buf.write(s)
            return len(s)

        def flush(self):
            real.flush()

    sys.stdout = Tee()
    try:
        yield buf
    finally:
        sys.stdout = real
