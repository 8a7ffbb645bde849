"""Resilience subsystem: chaos-injected recovery paths, CPU-only.

The acceptance scenario (ISSUE 1): a supervised run with injected NaN
batches, an injected fetch failure, and a simulated preemption FINISHES
training, with final loss within 10% of an uninjected run from the same
seed.  Poison batches are injected as *extra* corrupt records in the
stream (a corrupt record does not erase the good one next to it), so the
supervised run's executed update sequence must reduce to the clean run's
— the 10% bound then holds with real margin instead of riding on noise.
"""

import itertools
import os
import signal
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models import MultiLayerNetwork, iris_mlp
from deeplearning4j_tpu.resilience import (
    ChaosConfig,
    ChaosDataSource,
    HealthAction,
    HealthMonitor,
    ResilienceConfig,
    RetryPolicy,
    StepTimeoutError,
    SupervisorAbort,
    TrainingSupervisor,
    backoff_delays,
    chaos_runner,
    retry_call,
)

pytestmark = pytest.mark.chaos


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 3, n)
    x = rng.normal(0, 0.3, (n, 4)).astype(np.float32) + y[:, None]
    return x, np.eye(3, dtype=np.float32)[y]


def _epoch_batches(x, y, batch=8):
    return [(x[i:i + batch], y[i:i + batch]) for i in range(0, len(x), batch)]


def _cfg(tmp_path, **overrides):
    defaults = dict(checkpoint_dir=tmp_path / "ckpts", checkpoint_every=10,
                    min_history=3,
                    fetch_retry=RetryPolicy(max_attempts=3, base_delay=0.01,
                                            max_delay=0.05))
    defaults.update(overrides)
    return ResilienceConfig(**defaults)


class TestAcceptance:
    @pytest.mark.parametrize("chunk_size", [1, 4])
    def test_chaos_run_finishes_and_matches_clean_run(self, tmp_path,
                                                      chunk_size):
        """NaN batches + fetch failure + simulated preemption: training
        finishes and final loss is within 10% of the uninjected run.
        Runs both per-step (chunk_size=1) and through the fused
        multi-step dispatch path (chunk_size=4, ISSUE 2)."""
        x, y = _data()
        clean_batches = _epoch_batches(x, y) * 15  # 120 updates

        net_clean = MultiLayerNetwork(iris_mlp()).init()
        for bx, by in clean_batches:
            net_clean.fit_batch(bx, by)
        clean_loss = net_clean.score(x, y)

        # corrupt records are EXTRA entries in the stream at fetch
        # positions 5 and 30 (ChaosDataSource NaNs their features)
        injected = list(clean_batches)
        injected.insert(5, clean_batches[0])
        injected.insert(30, clean_batches[0])
        source = ChaosDataSource(injected, ChaosConfig(
            nan_steps=(5, 30), fetch_fail_steps=(9,), preempt_at=61))

        net_b = MultiLayerNetwork(iris_mlp()).init()
        report1 = TrainingSupervisor(
            net_b, _cfg(tmp_path, chunk_size=chunk_size)).run(source)
        assert report1.preempted
        assert report1.skipped == 2          # both NaN records skipped
        assert any(f.kind == "fetch_error" and f.action == "retry"
                   for f in report1.faults)

        # "process restart": fresh net, resume from the emergency
        # checkpoint, continue from the SAME source (position survives)
        net_c = MultiLayerNetwork(iris_mlp()).init()
        sup2 = TrainingSupervisor(net_c,
                                  _cfg(tmp_path, chunk_size=chunk_size))
        assert sup2.resume()
        assert sup2.step == report1.steps
        report2 = sup2.run(source)
        assert not report2.preempted
        assert report2.steps == len(clean_batches)  # all real updates ran

        final_loss = net_c.score(x, y)
        assert np.isfinite(final_loss)
        assert abs(final_loss - clean_loss) <= 0.10 * clean_loss

    def test_supervises_data_parallel_trainer(self, tmp_path):
        """The same supervisor drives a DataParallelTrainer: NaN batch
        skipped, run completes, loss finite."""
        from deeplearning4j_tpu.parallel import DataParallelTrainer

        x, y = _data()
        batches = _epoch_batches(x, y) * 3
        net = MultiLayerNetwork(iris_mlp()).init()
        trainer = DataParallelTrainer(net)
        source = ChaosDataSource(batches, ChaosConfig(nan_steps=(2,)))
        report = TrainingSupervisor(trainer, _cfg(tmp_path)).run(source)
        assert report.skipped == 1
        assert not report.preempted
        assert np.isfinite(report.final_loss)
        assert np.isfinite(float(net.last_grad_norm))


class TestPoisonBatches:
    def test_skip_budget_exhaustion_aborts(self, tmp_path):
        x, y = _data(32)
        batches = _epoch_batches(x, y) * 2
        source = ChaosDataSource(batches, ChaosConfig(nan_steps=(0, 1, 2)))
        net = MultiLayerNetwork(iris_mlp()).init()
        sup = TrainingSupervisor(net, _cfg(tmp_path, skip_budget=2))
        with pytest.raises(SupervisorAbort, match="skip budget"):
            sup.run(source)
        assert sup.skipped == 3
        # parameters were never touched by a poison batch
        assert np.isfinite(net.params_flat()).all()

    def test_skips_do_not_consume_updates(self, tmp_path):
        x, y = _data(32)
        batches = _epoch_batches(x, y)
        source = ChaosDataSource(
            [batches[0]] + batches, ChaosConfig(nan_steps=(0,)))
        net = MultiLayerNetwork(iris_mlp()).init()
        report = TrainingSupervisor(net, _cfg(tmp_path)).run(source)
        assert report.skipped == 1
        assert report.steps == len(batches)


class TestRollback:
    def test_nonfinite_loss_rolls_back_with_lr_backoff(self, tmp_path):
        """A step whose loss is non-finite by construction (a NaN batch
        let through to the step: `check_batches=False`, so nothing skips
        it) poisons the parameters; the supervisor rolls back to the
        last anchor with a reduced lr_scale and training proceeds."""
        x, y = _data()
        batches = _epoch_batches(x, y) * 4
        net = MultiLayerNetwork(iris_mlp(updater="sgd")).init()
        sup = TrainingSupervisor(net, _cfg(
            tmp_path, check_batches=False, lr_backoff=0.5,
            max_rollbacks=4))
        report = sup.run(ChaosDataSource(batches,
                                         ChaosConfig(nan_steps=(3,))))
        assert report.rollbacks == 1
        assert report.skipped == 0
        assert report.lr_scale == 0.5
        assert np.isfinite(net.params_flat()).all()
        assert np.isfinite(report.final_loss)
        assert any(f.kind == "nonfinite_loss" and f.action == "rollback"
                   for f in report.faults)

    def test_rollback_budget_exhaustion_aborts(self, tmp_path):
        x, y = _data()
        batches = _epoch_batches(x, y) * 4
        # backoff ~1: every retry explodes again until the budget is gone
        net = MultiLayerNetwork(
            iris_mlp(updater="sgd", learning_rate=1e6)).init()
        sup = TrainingSupervisor(net, _cfg(
            tmp_path, lr_backoff=0.999, max_rollbacks=2))
        with pytest.raises(SupervisorAbort, match="rollback budget"):
            sup.run(ChaosDataSource(batches, ChaosConfig()))
        assert sup.rollbacks == 3  # the third breached the budget of 2

    def test_invalid_score_error_from_step_triggers_rollback(
            self, tmp_path):
        """The typed InvalidScoreError (what a NanGuardListener raises
        inside the step) is caught precisely and answered with a
        rollback, not a crash.  Raised one-shot from a wrapper so the
        supervisor's own grad-norm check cannot fire first."""
        from deeplearning4j_tpu.optimize import InvalidScoreError

        x, y = _data()
        batches = _epoch_batches(x, y) * 2

        class GuardRaiser:
            def __init__(self, net):
                self.net = net
                self._fired = False

            def __getattr__(self, name):
                return getattr(self.net, name)

            def fit_batch(self, bx, by, mask=None):
                if not self._fired and self.net._iteration == 2:
                    self._fired = True
                    raise InvalidScoreError(2, float("nan"))
                return self.net.fit_batch(bx, by, mask)

        net = MultiLayerNetwork(iris_mlp()).init()
        sup = TrainingSupervisor(GuardRaiser(net), _cfg(tmp_path))
        report = sup.run(ChaosDataSource(batches, ChaosConfig()))
        assert report.rollbacks == 1
        assert np.isfinite(report.final_loss)
        assert any(f.exception and "InvalidScoreError" in f.exception
                   for f in report.faults)


class TestRollbackWithoutSavedMoments:
    def test_save_updater_false_resets_moments_on_rollback(self, tmp_path):
        """With save_updater=False the checkpoint has no moments; a
        rollback must RESET the optimizer state, not keep the live
        (NaN-poisoned) momentum that would re-explode clean params.  The
        momentum is poisoned by construction: a NaN batch let through to
        the step (`check_batches=False`) gives NaN gradients."""
        x, y = _data()
        batches = _epoch_batches(x, y) * 4
        net = MultiLayerNetwork(iris_mlp(updater="nesterovs")).init()
        sup = TrainingSupervisor(net, _cfg(
            tmp_path, save_updater=False, check_batches=False,
            lr_backoff=0.5, max_rollbacks=4))
        report = sup.run(ChaosDataSource(batches,
                                         ChaosConfig(nan_steps=(3,))))
        assert report.rollbacks == 1
        assert np.isfinite(report.final_loss)
        from jax.flatten_util import ravel_pytree

        assert np.isfinite(
            np.asarray(ravel_pytree(net.updater_state)[0])).all()


class TestLocalSgdCheckpointing:
    def test_checkpoint_snapshot_does_not_perturb_training(self, tmp_path):
        """Supervised local-SGD (sync_every > 1): the per-checkpoint
        publish must be a pure snapshot — the training trajectory equals
        an unsupervised run's, with no extra sync points injected."""
        from deeplearning4j_tpu.parallel import DataParallelTrainer

        x, y = _data()
        batches = _epoch_batches(x, y) * 2  # 16 steps

        net_a = MultiLayerNetwork(iris_mlp()).init()
        plain = DataParallelTrainer(net_a, sync_every=4)
        for bx, by in batches:
            plain.fit_batch(bx, by)
        plain.finalize()

        net_b = MultiLayerNetwork(iris_mlp()).init()
        supervised = DataParallelTrainer(net_b, sync_every=4)
        sup = TrainingSupervisor(supervised, _cfg(tmp_path,
                                                  checkpoint_every=3))
        sup.run(ChaosDataSource(batches, ChaosConfig()))
        supervised.finalize()

        np.testing.assert_allclose(net_a.params_flat(),
                                   net_b.params_flat(), atol=1e-6)

    def test_mid_window_checkpoint_carries_current_params(self, tmp_path):
        """A checkpoint taken between sync points must hold the replica
        average of the CURRENT step, not the last-sync copy."""
        from deeplearning4j_tpu.parallel import DataParallelTrainer
        from deeplearning4j_tpu.runtime.checkpoint import load_checkpoint

        x, y = _data()
        batches = _epoch_batches(x, y)
        net = MultiLayerNetwork(iris_mlp()).init()
        trainer = DataParallelTrainer(net, sync_every=4)
        sup = TrainingSupervisor(trainer, _cfg(tmp_path,
                                               checkpoint_every=10**9))
        for bx, by in batches[:3]:       # stop INSIDE the sync window
            sup.supervised_step(bx, by)
        stale = net.params_flat().copy()  # last publish: initial stack
        sup.checkpoint(score=None)
        step, params, _upd, _extra = load_checkpoint(
            tmp_path / "ckpts", net.params, step=3)
        assert step == 3
        from jax.flatten_util import ravel_pytree

        ckpt_flat = np.asarray(ravel_pytree(params)[0])
        assert not np.allclose(ckpt_flat, stale)  # progress was captured


class TestPreemption:
    def test_sigterm_flushes_emergency_checkpoint(self, tmp_path):
        """The real signal path: SIGTERM mid-run -> flag -> emergency
        checkpoint at the next step boundary -> resumable stop."""
        from deeplearning4j_tpu.runtime.checkpoint import (
            latest_checkpoint,
            load_checkpoint,
        )

        x, y = _data(32)
        batches = _epoch_batches(x, y)
        net = MultiLayerNetwork(iris_mlp()).init()
        sup = TrainingSupervisor(net, _cfg(tmp_path))
        sup.install_signal_handlers()
        try:
            timer = threading.Timer(
                0.3, os.kill, (os.getpid(), signal.SIGTERM))
            timer.start()
            report = sup.run(itertools.cycle(batches), max_steps=100_000)
            timer.cancel()
        finally:
            sup.uninstall_signal_handlers()
        assert report.preempted
        assert any(f.kind == "preemption" for f in report.faults)
        ckpt = latest_checkpoint(tmp_path / "ckpts")
        assert ckpt is not None
        step, _params, _upd, extra = load_checkpoint(
            tmp_path / "ckpts", net.params, net.updater_state)
        assert step == report.steps
        assert extra.get("preempt") is True

    def test_request_preemption_is_deterministic(self, tmp_path):
        x, y = _data(32)
        batches = _epoch_batches(x, y)
        net = MultiLayerNetwork(iris_mlp()).init()
        sup = TrainingSupervisor(net, _cfg(tmp_path))
        sup.request_preemption()
        report = sup.run(ChaosDataSource(batches, ChaosConfig()))
        assert report.preempted and report.steps == 0


class TestWatchdog:
    def test_hung_step_raises_structured_fault(self, tmp_path):
        x, y = _data(32)
        batches = _epoch_batches(x, y)
        net = MultiLayerNetwork(iris_mlp()).init()
        net.fit_batch(*batches[0])  # pre-compile: the hang must be the
        # injected sleep, not XLA compilation time
        runner = chaos_runner(net, ChaosConfig(hang_steps=(1,),
                                               hang_seconds=5.0))
        sup = TrainingSupervisor(runner, _cfg(tmp_path, step_timeout=0.5))
        with pytest.raises(StepTimeoutError) as ei:
            sup.run(ChaosDataSource(batches, ChaosConfig()))
        assert ei.value.report is not None
        assert ei.value.report.kind == "hang"
        assert any(f.kind == "hang" for f in sup.faults)


class TestLayerStateCheckpointing:
    def test_resume_restores_batchnorm_running_stats(self, tmp_path):
        """Checkpoints carry non-parameter layer state: poisoned
        batch-norm running stats must not survive a resume (an exploding
        step writes inf into them BEFORE the loss reaches the host, so
        restoring params alone would keep the poison)."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.conf import (
            BatchNormConf,
            DenseLayerConf,
            MultiLayerConfiguration,
            NeuralNetConfiguration,
            OutputLayerConf,
        )

        conf = MultiLayerConfiguration(
            conf=NeuralNetConfiguration(seed=3, learning_rate=0.05),
            layers=(DenseLayerConf(n_in=4, n_out=8, activation="relu"),
                    BatchNormConf(n_in=8),
                    OutputLayerConf(n_in=8, n_out=3)))
        x, y = _data(32)
        batches = _epoch_batches(x, y)
        net = MultiLayerNetwork(conf).init()
        sup = TrainingSupervisor(net, _cfg(tmp_path, checkpoint_every=2))
        sup.run(ChaosDataSource(batches, ChaosConfig()))
        from jax.flatten_util import ravel_pytree

        good_state = np.asarray(ravel_pytree(net.state)[0])
        assert np.isfinite(good_state).all() and good_state.size > 0
        # poison the running stats the way an exploded step would
        net.state = jax.tree_util.tree_map(
            lambda a: jnp.full_like(a, jnp.inf), net.state)

        sup2 = TrainingSupervisor(net, _cfg(tmp_path, checkpoint_every=2))
        assert sup2.resume()
        restored = np.asarray(ravel_pytree(net.state)[0])
        np.testing.assert_allclose(restored, good_state, atol=0)
        assert np.isfinite(np.asarray(net.output(x))).all()


class TestFetchFaults:
    def test_generator_death_surfaces_fetch_error_not_clean_end(
            self, tmp_path):
        """A generator source that raises is CLOSED — the retry sees
        StopIteration.  That must surface the original fetch error, not
        end the run 'completed' half-trained."""
        x, y = _data(32)
        batches = _epoch_batches(x, y)

        def gen():
            yield batches[0]
            raise OSError("boom: dataset file vanished")

        net = MultiLayerNetwork(iris_mlp()).init()
        sup = TrainingSupervisor(net, _cfg(tmp_path))
        with pytest.raises(OSError, match="boom"):
            sup.run(gen())
        assert any(f.kind == "fetch_error" and "source died" in f.detail
                   for f in sup.faults)

    def test_fetch_failure_exhausting_retries_propagates(self, tmp_path):
        x, y = _data(32)
        batches = _epoch_batches(x, y)
        source = ChaosDataSource(batches, ChaosConfig(fetch_fail_steps=(1,)))
        net = MultiLayerNetwork(iris_mlp()).init()
        sup = TrainingSupervisor(net, _cfg(
            tmp_path,
            fetch_retry=RetryPolicy(max_attempts=1, base_delay=0.01)))
        with pytest.raises(OSError, match="injected fetch failure"):
            sup.run(source)
        assert any(f.kind == "fetch_error" and f.action == "abort"
                   for f in sup.faults)


class TestHealthMonitor:
    def test_divergence_needs_patience(self):
        mon = HealthMonitor(divergence_factor=5.0, patience=2, window=8,
                            min_history=3)
        for i in range(4):
            action, _ = mon.observe(i, 1.0)
            assert action is HealthAction.OK
        action, report = mon.observe(4, 100.0)   # suspect #1
        assert action is HealthAction.OK
        action, report = mon.observe(5, 100.0)   # suspect #2 -> rollback
        assert action is HealthAction.ROLLBACK
        assert report.kind == "divergence"

    def test_suspect_losses_do_not_poison_the_median(self):
        mon = HealthMonitor(divergence_factor=5.0, patience=3, window=8,
                            min_history=3)
        for i in range(4):
            mon.observe(i, 1.0)
        mon.observe(4, 100.0)
        assert mon.suspect  # checkpoints must not snapshot this regime
        mon.observe(5, 1.0)  # healthy step resets the streak
        assert not mon.suspect
        assert mon.rolling_median == pytest.approx(1.0)

    def test_nonfinite_is_immediate(self):
        mon = HealthMonitor()
        action, report = mon.observe(0, float("nan"))
        assert action is HealthAction.ROLLBACK
        assert report.kind == "nonfinite_loss"
        action, report = mon.observe(1, 1.0, grad_norm=float("inf"))
        assert action is HealthAction.ROLLBACK


class TestRetry:
    def test_exponential_backoff_with_jitter_bounds(self):
        import random

        policy = RetryPolicy(max_attempts=5, base_delay=1.0, multiplier=2.0,
                             max_delay=5.0, jitter=0.1)
        delays = list(backoff_delays(policy, random.Random(0)))
        assert len(delays) == 4
        for d, nominal in zip(delays, (1.0, 2.0, 4.0, 5.0)):
            assert abs(d - nominal) <= 0.1 * nominal + 1e-9

    def test_retry_call_retries_then_succeeds(self):
        calls = []
        sleeps = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        policy = RetryPolicy(max_attempts=3, base_delay=0.5, jitter=0.0)
        out = retry_call(flaky, policy=policy, sleep=sleeps.append)
        assert out == "ok" and len(calls) == 3
        assert sleeps == [0.5, 1.0]

    def test_non_retryable_raises_immediately(self):
        calls = []

        def buggy():
            calls.append(1)
            raise TypeError("a real bug")

        with pytest.raises(TypeError):
            retry_call(buggy, policy=RetryPolicy(max_attempts=5),
                       sleep=lambda _: None)
        assert len(calls) == 1

    def test_budget_exhaustion_reraises_last(self):
        def always():
            raise OSError("down")

        with pytest.raises(OSError, match="down"):
            retry_call(always, policy=RetryPolicy(max_attempts=2,
                                                  base_delay=0.0),
                       sleep=lambda _: None)


class TestHookPoints:
    def test_lr_scale_scales_the_applied_update(self):
        x, y = _data(32)
        a = MultiLayerNetwork(iris_mlp(updater="sgd")).init()
        b = MultiLayerNetwork(iris_mlp(updater="sgd")).init()
        p0 = a.params_flat()
        a.fit_batch(x, y)
        b.set_lr_scale(0.5)
        b.fit_batch(x, y)
        full = a.params_flat() - p0
        half = b.params_flat() - p0
        np.testing.assert_allclose(half, 0.5 * full, rtol=1e-4, atol=1e-7)
        # and it never recompiles: the jitted step cache has ONE entry
        assert len(a._jit_train_step) == 1

    def test_grad_norm_surfaced_per_step(self):
        x, y = _data(32)
        net = MultiLayerNetwork(iris_mlp()).init()
        assert net.last_grad_norm is None
        net.fit_batch(x, y)
        g = float(net.last_grad_norm)
        assert np.isfinite(g) and g > 0

    def test_restore_train_state_replays_exactly(self, tmp_path):
        from deeplearning4j_tpu.runtime.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )

        x, y = _data(32)
        net = MultiLayerNetwork(iris_mlp()).init()
        for _ in range(3):
            net.fit_batch(x, y)
        save_checkpoint(tmp_path, 3, net.params,
                        updater_state=net.updater_state)
        l_ref = net.fit_batch(x, y)

        net2 = MultiLayerNetwork(iris_mlp(seed=99)).init()
        step, params, upd, _ = load_checkpoint(tmp_path, net2.params,
                                               net2.updater_state)
        net2.restore_train_state(step, params, upd)
        assert net2._iteration == 3
        l_resumed = net2.fit_batch(x, y)
        assert abs(l_ref - l_resumed) < 1e-6


class TestChaosDeterminism:
    def test_fault_schedule_is_deterministic(self):
        x, y = _data(32)
        batches = _epoch_batches(x, y)

        def consume():
            src = ChaosDataSource(batches, ChaosConfig(
                nan_steps=(1,), fetch_fail_steps=(2,)))
            events = []
            while True:
                try:
                    bx, _by, _m = next(src)
                    events.append("nan" if np.isnan(bx).any() else "ok")
                except OSError:
                    events.append("fail")
                except StopIteration:
                    break
            return events

        assert consume() == consume()

    def test_slow_fetch_delays(self):
        x, y = _data(16)
        src = ChaosDataSource(_epoch_batches(x, y), ChaosConfig(
            slow_fetch_steps=(0,), slow_seconds=0.05))
        t0 = time.monotonic()
        next(src)
        assert time.monotonic() - t0 >= 0.05
