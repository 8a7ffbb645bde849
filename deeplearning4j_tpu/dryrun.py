"""Committed-evidence multichip dryrun runner.

Runs the driver's multichip entry (``__graft_entry__.dryrun_multichip``)
— the same in-process distributed proof the reference gets from its YARN
IRUnit simulator (reference: ``IRUnitDriver.java:51``) — and writes a
timestamped evidence log (full stdout/stderr, git SHA, env fingerprint,
wall time) to ``EVIDENCE/dryrun_YYYYMMDD_HHMM.log`` at the repo root.
A green multichip run thereby becomes a committed, reproducible artifact
instead of prose in a measurement note.

Usage::

    python -m deeplearning4j_tpu.dryrun [n_devices] [--out DIR]

Runs in this one process on the devices it has: on a host with chips,
the chips (fewer than ``n_devices`` is an error naming what was found);
with ``JAX_PLATFORMS=cpu`` in the environment, ``n_devices`` virtual CPU
devices.  The log's first output line names the platform that ran.
"""

from __future__ import annotations

import argparse
import io
import os
import pathlib
import platform
import subprocess
import sys
import time
from contextlib import redirect_stderr, redirect_stdout

REPO = pathlib.Path(__file__).resolve().parent.parent


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO,
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 - evidence header is best-effort
        return "unknown"


def _git_dirty() -> str:
    try:
        out = subprocess.run(
            ["git", "status", "--porcelain"], cwd=REPO,
            capture_output=True, text=True, timeout=10)
        if out.returncode:  # a copy of the tree that is not a repository
            return "unknown"
        return "dirty" if out.stdout.strip() else "clean"
    except Exception:  # noqa: BLE001
        return "unknown"


def _env_fingerprint() -> list:
    lines = [f"python: {sys.version.split()[0]} ({platform.platform()})"]
    for k in sorted(os.environ):
        if any(t in k for t in ("JAX", "XLA", "TPU")):
            lines.append(f"{k}={os.environ[k]}")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m deeplearning4j_tpu.dryrun",
        description="Run the multichip dryrun and write an EVIDENCE log.")
    ap.add_argument("n_devices", nargs="?", type=int, default=8)
    ap.add_argument("--out", default=str(REPO / "EVIDENCE"),
                    help="evidence directory (default: <repo>/EVIDENCE)")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(REPO))
    import __graft_entry__

    sha, dirty = _git_sha(), _git_dirty()
    t0 = time.time()
    buf = io.StringIO()
    ok, err = True, None
    try:
        with redirect_stdout(buf), redirect_stderr(buf):
            __graft_entry__.dryrun_multichip(args.n_devices)
    except BaseException as e:  # noqa: BLE001 - a failed run is evidence too
        ok, err = False, f"{type(e).__name__}: {e}"
    wall = time.time() - t0

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    ts = time.strftime("%Y%m%d_%H%M", time.gmtime())
    path = out_dir / f"dryrun_{ts}.log"
    header = [
        f"# multichip dryrun evidence — {time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}",
        f"git_sha: {sha} ({dirty})",
        f"n_devices: {args.n_devices}",
        f"result: {'GREEN' if ok else f'FAILED ({err})'}",
        f"wall_time_s: {wall:.1f}",
        "command: python -m deeplearning4j_tpu.dryrun "
        f"{args.n_devices}",
        *_env_fingerprint(),
        "--- run output ---",
    ]
    path.write_text("\n".join(header) + "\n" + buf.getvalue())
    sys.stdout.write(buf.getvalue())
    print(("dryrun GREEN" if ok else f"dryrun FAILED: {err}")
          + f" in {wall:.1f} s -> {path}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
