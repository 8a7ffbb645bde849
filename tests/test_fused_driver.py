"""Fused multi-step training driver (runtime/fused.py).

Covers the ISSUE-2 acceptance surface: chunk assembly with tail-batch
padding + example masks, bitwise chunked-vs-unchunked equivalence
(including ragged tails, single-device and data-parallel), the
constant-compile-count guard over mixed-size epochs, listener
sync-interval gating, the batched-eval fast path, and chunked
supervision (per-step fault granularity with chunk replay).
"""

import numpy as np
import pytest

from deeplearning4j_tpu.models import MultiLayerNetwork, iris_mlp
from deeplearning4j_tpu.runtime.fused import (
    FusedTrainingDriver,
    assemble_chunks,
    stack_batches,
)


def _data(n=37, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 3, n)
    x = rng.normal(0, 0.3, (n, 4)).astype(np.float32) + y[:, None]
    return x, np.eye(3, dtype=np.float32)[y]


def _batches(x, y, batch=8):
    """Mini-batches WITH a ragged tail (37 examples / 8 -> tail of 5)."""
    return [(x[i:i + batch], y[i:i + batch]) for i in range(0, len(x), batch)]


class TestAssembler:
    def test_pads_ragged_tail_with_zero_weights(self):
        x, y = _data(21)
        chunks = list(assemble_chunks(iter(_batches(x, y)), 3))
        assert len(chunks) == 1
        c = chunks[0]
        assert c.xs.shape == (3, 8, 4) and c.weights.shape == (3, 8)
        np.testing.assert_array_equal(c.weights[:2], 1.0)
        np.testing.assert_array_equal(c.weights[2], [1, 1, 1, 1, 1, 0, 0, 0])
        np.testing.assert_array_equal(c.xs[2, 5:], 0.0)

    def test_short_group_emits_length_one_chunks(self):
        """A group shorter than chunk_size becomes [1, ...] chunks: only
        two programs per shape ever exist ([K] and [1])."""
        x, y = _data(48)
        chunks = list(assemble_chunks(iter(_batches(x, y, 8)), 4))
        assert [c.steps for c in chunks] == [4, 1, 1]

    def test_feature_shape_change_flushes_group(self):
        x, y = _data(32)
        stream = _batches(x, y, 8) + [(np.zeros((8, 6), np.float32),
                                       np.zeros((8, 3), np.float32))]
        chunks = list(assemble_chunks(iter(stream), 4))
        assert [c.steps for c in chunks] == [4, 1]
        assert chunks[1].xs.shape[-1] == 6

    def test_stack_batches_pads_to_largest(self):
        x, y = _data(13)
        c = stack_batches([(x[:8], y[:8], None), (x[8:], y[8:], None)])
        assert c.xs.shape == (2, 8, 4)
        assert c.weights[1].sum() == 5

    def test_accepts_dataset_objects(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet

        x, y = _data(16)
        chunks = list(assemble_chunks(
            iter([DataSet(x[:8], y[:8]), DataSet(x[8:], y[8:])]), 2))
        assert chunks[0].steps == 2


class TestChunkEquivalence:
    """ISSUE-2 acceptance: same seed, chunk_size in {1, 4, 7} (ragged
    tail included) -> bitwise-identical parameters on CPU."""

    def _run(self, chunk, epochs=2, prefetch=2):
        x, y = _data()
        net = MultiLayerNetwork(iris_mlp()).init()
        net.fit(_batches(x, y), epochs=epochs, chunk_size=chunk,
                prefetch=prefetch)
        return net

    @pytest.mark.parametrize("chunk", [4, 7])
    def test_bitwise_identical_params(self, chunk):
        ref = self._run(1).params_flat()
        out = self._run(chunk).params_flat()
        np.testing.assert_array_equal(ref, out)  # bitwise, not allclose

    def test_prefetch_does_not_change_results(self):
        a = self._run(4, prefetch=2).params_flat()
        b = self._run(4, prefetch=0).params_flat()
        np.testing.assert_array_equal(a, b)

    def test_iteration_count_and_grad_norm(self):
        net = self._run(4, epochs=1)
        x, y = _data()
        assert net._iteration == len(_batches(x, y))
        assert np.isfinite(float(net.last_grad_norm))

    def test_per_step_losses_match_across_chunkings(self):
        x, y = _data(32)
        b = _batches(x, y)

        def losses(k):
            net = MultiLayerNetwork(iris_mlp()).init()
            out = []
            for c in assemble_chunks(iter(b), k):
                ls, _ = net.fit_chunk_async(c.xs, c.ys, c.masks, c.weights)
                out.extend(np.asarray(ls).tolist())
            return out

        np.testing.assert_array_equal(losses(1), losses(4))

    def test_chunked_matches_legacy_fit_to_tolerance(self):
        """The weighted objective (sum/N) is mathematically the legacy
        mean loss; chunked training tracks the legacy per-batch path to
        float tolerance (bit-exactness is guaranteed across CHUNKINGS,
        not against the differently-fused legacy program)."""
        x, y = _data()
        net = MultiLayerNetwork(iris_mlp()).init()
        net.fit(_batches(x, y), epochs=2)
        ref = net.params_flat()
        out = self._run(4).params_flat()
        np.testing.assert_allclose(ref, out, atol=1e-5)


class TestDataParallelChunkEquivalence:
    def _run(self, chunk):
        from deeplearning4j_tpu.parallel import DataParallelTrainer

        x, y = _data()  # 37 examples: 2 x 16 + ragged tail of 5
        net = MultiLayerNetwork(iris_mlp()).init()
        trainer = DataParallelTrainer(net)
        trainer.fit(_batches(x, y, 16), epochs=2, chunk_size=chunk)
        return net.params_flat()

    def test_dp_bitwise_identical_including_padded_tail(self):
        """Chunked DP pads the ragged tail to the group batch size, so a
        tail the per-batch DP path REJECTS (5 % 8 devices != 0) trains
        fine — and chunk sizes agree bitwise."""
        np.testing.assert_array_equal(self._run(1), self._run(4))

    def test_dp_padded_tail_matches_single_device_weighting(self):
        """The DP chunk step psums weighted-loss numerator/denominator
        and gradients SEPARATELY before normalizing: a tail batch whose
        padded rows leave some shards with zero real examples must
        produce the same global weighted update as one device."""
        x, y = _data()  # tail of 5 padded to 16 -> shards 3..7 all-pad
        net = MultiLayerNetwork(iris_mlp()).init()
        net.fit(_batches(x, y, 16), epochs=2, chunk_size=4)
        single = net.params_flat()
        np.testing.assert_allclose(self._run(4), single, atol=1e-6)


class TestRecompileGuard:
    """CI guard: two epochs over mixed-size tail batches compile a
    CONSTANT number of XLA programs — the padded chunk program and the
    length-1 remainder program — and epoch 2 compiles nothing new."""

    def test_compile_count_constant_across_epochs(self):
        import jax
        import jax.monitoring

        x, y = _data()  # 5 batches/epoch: chunk [4] + remainder [1]
        net = MultiLayerNetwork(iris_mlp()).init()
        driver = FusedTrainingDriver(net, chunk_size=4, prefetch=0)
        driver.fit(_batches(x, y), epochs=1)
        chunk_fn = net._jit_train_chunk[(False, 1, False)]
        assert chunk_fn._cache_size() == 2  # [4,...] + [1,...] programs

        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            driver.fit(_batches(x, y), epochs=2)
        finally:
            jax.monitoring.clear_event_listeners()
        assert compiles == []  # warm cache: zero XLA compiles
        assert chunk_fn._cache_size() == 2


class TestListenerSyncInterval:
    def test_score_listener_fires_only_on_interval(self):
        from deeplearning4j_tpu.optimize import ScoreIterationListener

        x, y = _data(32)
        seen = []
        net = MultiLayerNetwork(iris_mlp()).init()
        net.add_listener(ScoreIterationListener(
            print_iterations=3, out=seen.append))
        for _ in range(7):
            net.fit_batch_async(x, y)
        assert len(seen) == 2  # iterations 3 and 6 only
        # and off-interval steps did not even reach the listener bridge:
        # the net's due-listener gate is empty for iteration 7
        assert net._due_listeners(7) == []
        assert len(net._due_listeners(9)) == 1

    def test_plain_listener_still_fires_every_step(self):
        x, y = _data(32)
        calls = []
        net = MultiLayerNetwork(iris_mlp()).init()
        net.add_listener(lambda it, score: calls.append((it, score)))
        for _ in range(3):
            net.fit_batch_async(x, y)
        assert [it for it, _ in calls] == [1, 2, 3]
        assert all(np.isfinite(s) for _, s in calls)

    def test_chunked_path_fires_due_listeners_in_order(self):
        from deeplearning4j_tpu.optimize import ScoreIterationListener

        x, y = _data(32)
        seen = []
        net = MultiLayerNetwork(iris_mlp()).init()
        net.add_listener(ScoreIterationListener(print_iterations=2,
                                                out=seen.append))
        net.fit(_batches(x, y, 8), epochs=2, chunk_size=4)
        assert len(seen) == 4  # iterations 2, 4, 6, 8

    def test_model_reading_listeners_fire_only_at_chunk_boundaries(self):
        """A model-reading listener (score_only=False) fired mid-chunk
        would label end-of-chunk params with an earlier step; the chunked
        path defers it to the chunk's final iteration."""
        from deeplearning4j_tpu.optimize import IterationListener

        calls = []

        class Snapshotter(IterationListener):  # score_only=False default
            def iteration_done(self, model, iteration, score):
                calls.append(iteration)

        x, y = _data(32)
        net = MultiLayerNetwork(iris_mlp()).init()
        net.add_listener(Snapshotter())
        net.fit(_batches(x, y, 8), epochs=2, chunk_size=4)  # 8 batches
        assert calls == [4, 8]  # chunk-final iterations only


class TestEvalFastPath:
    def test_batched_eval_matches_single_shot(self):
        x, y = _data(37)
        net = MultiLayerNetwork(iris_mlp()).init()
        net.fit(_batches(x, y), epochs=1, chunk_size=4)
        whole = net.evaluate(x, y)
        batched = net.evaluate(x, y, batch_size=8)  # ragged final slice
        assert whole.stats() == batched.stats()
        assert float(whole.f1()) == float(batched.f1())


class TestChunkedSupervision:
    """Chunked resilience: per-step health granularity, chunk replay on
    rollback (the full chaos acceptance scenario runs chunked in
    tests/test_resilience.py)."""

    def _cfg(self, tmp_path, **kw):
        from deeplearning4j_tpu.resilience import (
            ResilienceConfig,
            RetryPolicy,
        )

        defaults = dict(checkpoint_dir=tmp_path / "ckpts",
                        checkpoint_every=10, min_history=3, chunk_size=4,
                        fetch_retry=RetryPolicy(max_attempts=3,
                                                base_delay=0.01,
                                                max_delay=0.05))
        defaults.update(kw)
        return ResilienceConfig(**defaults)

    def test_chunked_run_matches_unchunked_supervision(self, tmp_path):
        from deeplearning4j_tpu.resilience import TrainingSupervisor

        x, y = _data(64)
        batches = _batches(x, y, 8)[:8] * 3  # 24 full batches

        # legacy per-step supervision (different compiled program:
        # float-tolerance match)
        net_a = MultiLayerNetwork(iris_mlp()).init()
        TrainingSupervisor(net_a, self._cfg(
            tmp_path / "a", chunk_size=1)).run(list(batches))
        # chunked supervision vs the unsupervised fused driver at
        # chunk_size=1: same per-step program -> BITWISE match
        net_b = MultiLayerNetwork(iris_mlp()).init()
        TrainingSupervisor(net_b, self._cfg(
            tmp_path / "b", chunk_size=4)).run(list(batches))
        net_c = MultiLayerNetwork(iris_mlp()).init()
        net_c.fit(list(batches), chunk_size=1)
        np.testing.assert_array_equal(net_b.params_flat(),
                                      net_c.params_flat())
        np.testing.assert_allclose(net_a.params_flat(),
                                   net_b.params_flat(), atol=1e-5)

    def test_in_chunk_divergence_replays_and_rolls_back(self, tmp_path):
        from deeplearning4j_tpu.resilience import (
            ChaosConfig,
            ChaosDataSource,
            TrainingSupervisor,
        )

        x, y = _data(64)
        batches = _batches(x, y, 8)[:8] * 4
        net = MultiLayerNetwork(iris_mlp(updater="sgd")).init()
        # a divergence by construction: a NaN batch let through into
        # the middle of a chunk (nothing checks batches at assembly)
        sup = TrainingSupervisor(net, self._cfg(
            tmp_path, check_batches=False, lr_backoff=0.5,
            max_rollbacks=4))
        report = sup.run(ChaosDataSource(batches,
                                         ChaosConfig(nan_steps=(5,))))
        assert report.rollbacks == 1
        assert report.lr_scale == 0.5
        assert np.isfinite(report.final_loss)
        assert any(f.action == "replay" for f in report.faults)

    def test_poison_batches_skipped_at_assembly(self, tmp_path):
        from deeplearning4j_tpu.resilience import (
            ChaosConfig,
            ChaosDataSource,
            TrainingSupervisor,
        )

        x, y = _data(32)
        batches = _batches(x, y, 8)[:4] * 2
        source = ChaosDataSource([batches[0]] + batches,
                                 ChaosConfig(nan_steps=(0,)))
        net = MultiLayerNetwork(iris_mlp()).init()
        report = TrainingSupervisor(net, self._cfg(tmp_path)).run(source)
        assert report.skipped == 1
        assert report.steps == len(batches)  # skips consume no updates
        assert np.isfinite(net.params_flat()).all()

    def test_mixed_shape_stream_flushes_groups(self, tmp_path):
        """Bucketed sequence batches (different T, [B, T] masks) through
        one supervised chunked run: a sequence-length change mid-buffer
        must flush the open chunk — mis-stacking would raise a broadcast
        error (or silently drop masks when the first buffered batch has
        none)."""
        from deeplearning4j_tpu.nn.conf import (
            GravesLSTMConf,
            MultiLayerConfiguration,
            NeuralNetConfiguration,
            RnnOutputLayerConf,
        )
        from deeplearning4j_tpu.resilience import TrainingSupervisor

        rng = np.random.default_rng(0)

        def seq_batch(t):
            xb = rng.normal(size=(4, t, 3)).astype(np.float32)
            yb = np.eye(2, dtype=np.float32)[
                rng.integers(0, 2, (4, t))]
            m = np.ones((4, t), np.float32)
            return xb, yb, m

        stream = [seq_batch(6), seq_batch(6), seq_batch(10), seq_batch(10),
                  seq_batch(6), seq_batch(10)]
        conf = MultiLayerConfiguration(
            conf=NeuralNetConfiguration(seed=1, learning_rate=0.05),
            layers=(GravesLSTMConf(n_in=3, n_out=8),
                    RnnOutputLayerConf(n_in=8, n_out=2)))
        net = MultiLayerNetwork(conf).init()
        report = TrainingSupervisor(net, self._cfg(tmp_path)).run(stream)
        assert report.steps == len(stream)
        assert np.isfinite(report.final_loss)

    def test_unsupported_dp_modes_fall_back_to_per_step(self, tmp_path):
        """A local-SGD trainer exposes fit_chunk_async but raises in it;
        the supervisor must detect that and supervise per-step instead of
        crashing mid-run."""
        from deeplearning4j_tpu.parallel import DataParallelTrainer
        from deeplearning4j_tpu.resilience import TrainingSupervisor

        x, y = _data(64)
        batches = _batches(x, y, 16)[:2] * 2
        net = MultiLayerNetwork(iris_mlp()).init()
        trainer = DataParallelTrainer(net, sync_every=4)
        report = TrainingSupervisor(trainer, self._cfg(tmp_path)).run(
            list(batches))
        assert report.steps == len(batches)
        assert np.isfinite(report.final_loss)

    def test_max_steps_respected_mid_chunk(self, tmp_path):
        from deeplearning4j_tpu.resilience import TrainingSupervisor

        x, y = _data(64)
        batches = _batches(x, y, 8)[:8] * 2
        net = MultiLayerNetwork(iris_mlp()).init()
        report = TrainingSupervisor(net, self._cfg(tmp_path)).run(
            list(batches), max_steps=6)
        assert report.steps == 6
