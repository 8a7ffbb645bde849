"""The bring-up contracts: nothing hides the device, the compile cache is
placed from outside, and the chip entry points fail loudly off-chip.

All cheap (no model compiles): the chip itself is exercised by
`chip_smoke.py` through the chip tool, not by tier-1.
"""

import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.runtime import device

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture()
def cache_config():
    """Restore jax's cache-dir config: the helper mutates process state."""
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_placed_from_outside_sets_no_directory(
        monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(device.CACHE_ENV, "/some/dir")
    assert device.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    first = device.enable_compile_cache()
    assert device.enable_compile_cache() == first
    assert pathlib.Path(first) == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_device_line_names_what_the_process_got():
    line = device.device_line()
    assert f"jax {jax.__version__}" in line
    assert "platform=cpu" in line and "device_kind=" in line
    assert f"devices={len(jax.devices())}" in line


def test_warmup_raises_when_a_program_fails_to_compile():
    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.serving import ContinuousLMServer

    cfg = tfm.TransformerConfig(vocab_size=50, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=32)
    # zeros in the parameter tree's shapes: the stubbed step never reads
    # them, and real initialisation would compile a dozen small programs
    params = jax.tree_util.tree_map(
        lambda leaf: np.zeros(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    srv = ContinuousLMServer(cfg, params, slots=2)

    def refused(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    srv._step = refused            # every paged dispatch goes through it
    try:
        with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
            srv.warmup(timeout=30)
        # the worker survived the failed warm (the keep-serving arm is
        # for live dispatches) and a second warmup reports again
        with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
            srv.warmup(timeout=30)
    finally:
        srv.stop()


def test_dryrun_names_the_device_count_when_there_are_too_few():
    sys.path.insert(0, str(REPO))
    try:
        import __graft_entry__
    finally:
        sys.path.remove(str(REPO))
    have = len(jax.devices())
    with pytest.raises(RuntimeError,
                       match=rf"needs {have + 1} devices .* has {have} "
                             rf".*platform=cpu"):
        __graft_entry__.dryrun_multichip(have + 1)


def test_chip_smoke_without_tiny_fails_off_chip():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert "platform=cpu" in proc.stdout.splitlines()[0]
    assert '"ok"' not in proc.stdout          # no result line
    assert "needs a TPU" in proc.stderr


def test_flash_block_error_is_raised_at_trace_time_for_compiled_calls():
    from deeplearning4j_tpu.parallel.kernels import (
        FlashBlockError,
        _blocks,
    )

    assert _blocks(1024, 128, interpret=False)[0] == 128
    assert _blocks(1000, 128, interpret=False) == [40, 8]   # not 125
    assert _blocks(1000, 128)[0] == 125                     # interpreter: any
    for s in (4, 100, 1001):
        with pytest.raises(FlashBlockError, match=f"sequence length {s}"):
            _blocks(s, 128, interpret=False)


def test_supervised_workers_get_the_environment_their_spec_states(
        monkeypatch):
    from deeplearning4j_tpu.runtime import launcher
    from deeplearning4j_tpu.serving import FleetRouter
    from deeplearning4j_tpu.serving.procfleet import (
        FleetSupervisor,
        WorkerSpec,
    )

    seen = {}

    def fake_spawn(command, log_path=None, **kwargs):
        seen.update(kwargs)
        raise launcher.WorkerSpawnError("not spawning in a unit test")

    monkeypatch.setattr(launcher, "spawn_logged", fake_spawn)
    router = FleetRouter()
    sup = FleetSupervisor(router)
    try:
        worker = sup.manage(WorkerSpec(
            name="w", url="http://127.0.0.1:1", command=["true"],
            env={"JAX_PLATFORMS": "cpu"}))
        sup._spawn(worker)
    finally:
        router.stop()
    assert seen["env"] == {"JAX_PLATFORMS": "cpu"}


def test_serve_warmup_exits_nonzero_when_a_program_fails_to_compile(
        monkeypatch, capsys):
    from deeplearning4j_tpu import cli
    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.serving import ContinuousLMServer

    cfg = tfm.TransformerConfig(vocab_size=50, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=32)
    monkeypatch.setattr(cli, "_load_saved_lm", lambda out: (cfg, {}))

    def refused(self, timeout=None):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(ContinuousLMServer, "warmup", refused)
    with pytest.raises(SystemExit, match="-warmup failed, not serving: "
                                         "RuntimeError: Mosaic failed"):
        cli.main(["serve", "-lm", "unused", "-port", "0", "-warmup"])
    assert "serve: jax " in capsys.readouterr().err   # the start-up line
