"""ZeRO-1 weight-update sharding plane (ISSUE-17): the sharded update is
the DEFAULT data-parallel path and must be indistinguishable from the
replicated one it replaced.

The load-bearing identity: `psum_scatter(flat, tiled=True) / n` followed
by `all_gather(tiled=True)` runs the SAME reduction tree as `pmean`, so
the fp32 sharded update is pinned BITWISE against the replicated update
— parameters AND optimizer moments.  Everything the precision plane and
the training loop compose with the update — dynamic loss scaling,
chunked fit, local-SGD, global-norm clipping, per-layer lr multipliers,
the hybrid/pipeline trainers' DP axes, elastic N→M checkpoint resume,
supervisor rollback — is exercised here with `shard_update=True`.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import (
    DenseLayerConf,
    MultiLayerConfiguration,
    NeuralNetConfiguration,
    OutputLayerConf,
)
from deeplearning4j_tpu.parallel import DataParallelTrainer, make_mesh

pytestmark = pytest.mark.zero

if len(jax.devices()) < 8:
    pytest.skip("needs the 8-device virtual mesh", allow_module_level=True)


def _mlp(seed=5, lr=0.02, mults=(1.0, 1.0), updater="adam", **kw):
    return MultiLayerConfiguration(
        conf=NeuralNetConfiguration(learning_rate=lr, updater=updater,
                                    seed=seed, **kw),
        layers=(DenseLayerConf(n_in=4, n_out=16, activation="relu",
                               lr_multiplier=mults[0]),
                OutputLayerConf(n_in=16, n_out=3,
                                lr_multiplier=mults[1])))


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 3, n)
    x = rng.normal(0, 0.3, (n, 4)).astype(np.float32) + y[:, None]
    return x, np.eye(3, dtype=np.float32)[y]


def _flat(tree):
    return np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree_util.tree_leaves(tree)])


def _run(shard, steps=5, conf_kw=None, precision=None, sync_every=1):
    net = MultiLayerNetwork(_mlp(**(conf_kw or {}))).init()
    if precision:
        net.set_precision(precision)
    tr = DataParallelTrainer(net, sync_every=sync_every, shard_update=shard)
    x, y = _data()
    for _ in range(steps):
        tr.fit_batch(x, y)
    tr.finalize()
    return net


class TestShardedReplicatedParity:
    def test_default_is_sharded(self):
        net = MultiLayerNetwork(_mlp()).init()
        tr = DataParallelTrainer(net)
        assert tr.shard_update
        assert "zero-1" in tr.scaling_report()["collective"]

    def test_fp32_params_and_moments_bitwise(self):
        """The tentpole pin: fp32 sharded vs replicated, 5 adam steps,
        params AND updater moments bitwise identical (same reduction
        tree; see docs/performance.md)."""
        a, b = _run(True), _run(False)
        assert np.array_equal(_flat(a.params), _flat(b.params))
        assert np.array_equal(_flat(a.updater_state),
                              _flat(b.updater_state))

    def test_elementwise_regularizers_stay_bitwise(self):
        """l2/l1/clip_value re-applied on the gradient shard are
        elementwise — still bitwise."""
        kw = dict(conf_kw=dict(l2=1e-3))
        a, b = _run(True, **kw), _run(False, **kw)
        assert np.array_equal(_flat(a.params), _flat(b.params))

    def test_clip_norm_global_norm_equivalence(self):
        """Global-norm clip under sharding: shard-local partial square
        norms psum'd — equal to the replicated global norm to float
        tolerance."""
        kw = dict(conf_kw=dict(clip_norm=0.5))
        a, b = _run(True, **kw), _run(False, **kw)
        np.testing.assert_allclose(_flat(a.params), _flat(b.params),
                                   rtol=0, atol=1e-6)

    def test_lr_multiplier_vector_bitwise(self):
        """Per-layer lr_multiplier rides the flat plane as a per-element
        vector — bitwise vs the per-layer python-float multiply."""
        kw = dict(conf_kw=dict(mults=(0.5, 2.0)))
        a, b = _run(True, **kw), _run(False, **kw)
        assert np.array_equal(_flat(a.params), _flat(b.params))

    def test_unit_norm_shards_by_leaf_segments(self):
        """unit_norm needs per-LEAF norms from the flat shard: segment
        square-sums psum'd across replicas.  (unit_norm only exists on
        UpdaterConfig — patched into the conf mapping here.)"""
        from deeplearning4j_tpu.nn.conf.config import (
            NeuralNetConfiguration as NNC,
        )

        orig = NNC.updater_config
        NNC.updater_config = lambda self: dataclasses.replace(
            orig(self), unit_norm=True)
        try:
            kw = dict(conf_kw=dict(updater="sgd"), steps=3)
            a, b = _run(True, **kw), _run(False, **kw)
        finally:
            NNC.updater_config = orig
        np.testing.assert_allclose(_flat(a.params), _flat(b.params),
                                   rtol=0, atol=1e-5)


class TestPrecisionComposition:
    def test_mixed_precision_parity(self):
        a = _run(True, precision="mixed")
        b = _run(False, precision="mixed")
        np.testing.assert_allclose(_flat(a.params), _flat(b.params),
                                   rtol=0, atol=1e-5)
        assert a.scaler_stats()["overflow_count"] == 0

    def test_loss_scale_overflow_skip_is_lockstep(self):
        """An inf batch under the sharded step: every replica reaches
        the same verdict (psum'd nonfinite count on the unscaled shard),
        the step is skipped in the SHARD domain, and the gather returns
        the old params exactly."""
        net = MultiLayerNetwork(_mlp()).init()
        net.set_precision("mixed")
        tr = DataParallelTrainer(net)
        x, y = _data()
        tr.fit_batch(x, y)
        tr.publish_train_state()
        before = _flat(net.params)
        xbad = x.copy()
        xbad[3, 1] = np.inf
        tr.fit_batch(xbad, y)
        tr.publish_train_state()
        assert np.array_equal(before, _flat(net.params))
        assert net.scaler_stats()["overflow_count"] == 1
        assert np.isfinite(tr.fit_batch(x, y))


class TestChunkedFit:
    def test_chunk_parity_1_vs_k(self):
        """fit(chunk_size=K) scans with the shard-local optimizer state
        in the carry: chunk 1 vs chunk 4 bitwise (unroll=1 path)."""

        def run(chunk):
            net = MultiLayerNetwork(_mlp()).init()
            tr = DataParallelTrainer(net)
            x, y = _data()
            tr.fit([(x, y)] * 8, chunk_size=chunk)
            return net

        a, b = run(1), run(4)
        assert np.array_equal(_flat(a.params), _flat(b.params))

    def test_mixed_chunked_fit_threads_scaler(self):
        net = MultiLayerNetwork(_mlp()).init()
        net.set_precision("mixed")
        tr = DataParallelTrainer(net)
        x, y = _data()
        tr.fit([(x, y)] * 6, chunk_size=3)
        assert np.isfinite(_flat(net.params)).all()
        assert net.scaler_stats()["good_steps"] == 6


class TestLocalSGD:
    def test_sync_round_parity(self):
        """sync_every>1 keeps local replicated moments; the sync round
        runs the SHARDED param average — bitwise vs the replicated
        pmean average."""
        kw = dict(steps=9, sync_every=3)
        a, b = _run(True, **kw), _run(False, **kw)
        assert np.array_equal(_flat(a.params), _flat(b.params))

    def test_local_sgd_converges_under_default(self):
        net = MultiLayerNetwork(_mlp()).init()
        tr = DataParallelTrainer(net, sync_every=4)
        x, y = _data()
        for _ in range(40):
            tr.fit_batch(x, y)
        tr.finalize()
        assert net.evaluate(x, y).accuracy() > 0.6


class TestMeshTrainersDPAxis:
    def test_hybrid_moments_shard_over_data(self):
        from deeplearning4j_tpu.parallel import transformer as tfm
        from deeplearning4j_tpu.parallel.hybrid import HybridParallelTrainer

        cfg = tfm.TransformerConfig(vocab_size=41, d_model=16, n_heads=4,
                                    n_layers=1, d_ff=32, max_len=16)
        mesh = make_mesh((2, 2, 2), ("data", "seq", "model"),
                         devices=jax.devices()[:8])
        rng = np.random.default_rng(5)
        tok = rng.integers(0, cfg.vocab_size, (4, 8))
        tgt = rng.integers(0, cfg.vocab_size, (4, 8))

        def run(shard):
            tr = HybridParallelTrainer(cfg, mesh, lr=0.01, seed=3,
                                       updater="adam", shard_update=shard)
            for _ in range(3):
                tr.fit_batch(tok, tgt)
            return tr

        a, b = run(True), run(False)
        assert a.shard_update and not b.shard_update
        np.testing.assert_allclose(_flat(a.params), _flat(b.params),
                                   rtol=0, atol=1e-5)
        m_leaf = jax.tree_util.tree_leaves(a.opt_state["m"])[0]
        assert "data" in str(m_leaf.sharding.spec)
        per = {s.data.size for s in m_leaf.addressable_shards}
        assert per == {m_leaf.size // 2}

    def test_pipeline_flat_zero_bitwise(self):
        """The flat sharded update against the replicated one.  The two
        layouts are different XLA programs whose gradients agree to an
        ULP, not to the bit (XLA on a CPU never promised that).  The
        first Adam update is `lr * g / (|g| + eps)` and does not see an
        ULP of `g`: bitwise there.  The moments carry it forward, so the
        third step is held to 1e-6 absolute, as the hybrid trainer's
        test above holds its own."""
        from deeplearning4j_tpu.parallel import transformer as tfm
        from deeplearning4j_tpu.parallel.hybrid import (
            PipelineParallelTrainer,
        )

        cfg = tfm.TransformerConfig(vocab_size=41, d_model=16, n_heads=4,
                                    n_layers=4, d_ff=32, max_len=16)
        mesh = make_mesh((2, 4), ("data", "stage"),
                         devices=jax.devices()[:8])
        rng = np.random.default_rng(6)
        tok = rng.integers(0, cfg.vocab_size, (8, 8))
        tgt = rng.integers(0, cfg.vocab_size, (8, 8))

        def run(shard):
            tr = PipelineParallelTrainer(cfg, mesh, n_microbatches=2,
                                         lr=0.01, seed=4, updater="adam",
                                         shard_update=shard)
            tr.fit_batch(tok, tgt)
            first = (_flat(tr.stage_params), _flat(tr.io_params))
            for _ in range(2):
                tr.fit_batch(tok, tgt)
            return tr, first

        (a, a1), (b, b1) = run(True), run(False)
        assert np.array_equal(a1[0], b1[0])
        assert np.array_equal(a1[1], b1[1])
        np.testing.assert_allclose(_flat(a.stage_params),
                                   _flat(b.stage_params), rtol=0, atol=1e-6)
        np.testing.assert_allclose(_flat(a.io_params), _flat(b.io_params),
                                   rtol=0, atol=1e-6)
        from jax.sharding import PartitionSpec as P

        m = jax.tree_util.tree_leaves(a.stage_opt["m"])[0]
        assert m.sharding.spec == P("stage", "data")
        mio = jax.tree_util.tree_leaves(a.io_opt["m"])[0]
        assert mio.sharding.spec == P("data")


class TestElasticResume:
    def test_save_n2_resume_m1_and_m4_bitwise(self, tmp_path):
        """Save a sharded N=2 run, resume on M=1 and M=4: the adopted
        train state round-trips BITWISE (the flat layout re-pads per
        mesh; values never change), and training continues."""
        from deeplearning4j_tpu.runtime.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )

        x, y = _data()
        net = MultiLayerNetwork(_mlp()).init()
        big = DataParallelTrainer(net, mesh=make_mesh(
            (2,), ("data",), devices=jax.devices()[:2]))
        for _ in range(5):
            big.fit_batch(x, y)
        big.publish_train_state()
        save_checkpoint(tmp_path, step=5, params=net.params,
                        updater_state=net.updater_state)
        saved_p, saved_u = _flat(net.params), _flat(net.updater_state)

        for m in (1, 4):
            net2 = MultiLayerNetwork(_mlp()).init()
            step, params, upd, _ = load_checkpoint(
                tmp_path, net2.params, updater_like=net2.updater_state)
            assert step == 5
            net2.params, net2.updater_state = params, upd
            tr = DataParallelTrainer(net2, mesh=make_mesh(
                (m,), ("data",), devices=jax.devices()[:m]))
            tr.publish_train_state()
            assert np.array_equal(saved_p, _flat(net2.params)), m
            assert np.array_equal(saved_u, _flat(net2.updater_state)), m
            assert np.isfinite(tr.fit_batch(x, y))


class TestSupervisorComposition:
    def test_divergence_rollback_repartitions_shards(self, tmp_path):
        """A run that diverges (a NaN batch let through to the step:
        non-finite by construction) under the sharded default: the
        supervisor rolls back by restoring the checkpoint INTO the shard layout
        (restore_train_state repartitions, it does not install
        replicated moments), and training then completes finite."""
        from deeplearning4j_tpu.models import iris_mlp
        from deeplearning4j_tpu.resilience import (
            ChaosConfig,
            ChaosDataSource,
            ResilienceConfig,
            TrainingSupervisor,
        )

        x, y = _data()
        batches = [(x[i:i + 8], y[i:i + 8]) for i in range(0, 64, 8)] * 4
        net = MultiLayerNetwork(iris_mlp(updater="sgd")).init()
        tr = DataParallelTrainer(net)
        assert tr.shard_update
        sup = TrainingSupervisor(tr, ResilienceConfig(
            checkpoint_dir=tmp_path / "ckpts", checkpoint_every=10,
            min_history=3, check_batches=False, lr_backoff=0.5,
            max_rollbacks=4))
        report = sup.run(ChaosDataSource(batches,
                                         ChaosConfig(nan_steps=(3,))))
        assert report.rollbacks == 1
        assert np.isfinite(report.final_loss)
        # the trainer still owns a SHARDED opt state after the rollback
        assert getattr(tr, "_opt_shard", None) is not None


class TestNoRecompile:
    def test_steady_state_zero_compiles(self):
        """After warmup, repeated sharded steps hit the jit cache: zero
        new XLA compiles (jax.monitoring)."""
        import jax.monitoring

        net = MultiLayerNetwork(_mlp()).init()
        tr = DataParallelTrainer(net)
        x, y = _data()
        tr.fit_batch(x, y)     # compiles the sharded step
        tr.fit_batch(x, y)     # one-time host-side scalar programs
        events = []

        def listener(event, *a, **kw):
            if "compile" in event and "backend" in event:
                events.append(event)

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            for _ in range(5):
                tr.fit_batch(x, y)
        finally:
            jax.monitoring.clear_event_listeners()
        assert events == []
