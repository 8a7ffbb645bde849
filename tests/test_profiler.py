"""Profiler tests: trace files written, StepTimer stats coherent."""

import os

import numpy as np

from deeplearning4j_tpu.obs.trace import annotate
from deeplearning4j_tpu.runtime.profiler import (
    StepTimer,
    device_memory_stats,
    trace,
)


def _tiny_net():
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import (
        DenseLayerConf,
        MultiLayerConfiguration,
        NeuralNetConfiguration,
        OutputLayerConf,
    )

    conf = MultiLayerConfiguration(
        conf=NeuralNetConfiguration(learning_rate=0.1),
        layers=(DenseLayerConf(n_in=4, n_out=8),
                OutputLayerConf(n_in=8, n_out=3)))
    return MultiLayerNetwork(conf).init()


def test_trace_writes_profile(tmp_path):
    import jax.numpy as jnp

    logdir = str(tmp_path / "prof")
    with trace(logdir):
        with annotate("matmul-span"):
            (jnp.ones((32, 32)) @ jnp.ones((32, 32))).block_until_ready()
    found = []
    for root, _dirs, files in os.walk(logdir):
        found.extend(files)
    assert found, "no trace files written"


def test_step_timer_on_training():
    net = _tiny_net()
    timer = StepTimer(batch_size=16, skip=1)
    net.add_listener(timer)
    rng = np.random.default_rng(0)
    x = rng.random((16, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    for _ in range(6):
        net.fit_batch(x, y)
    s = timer.summary()
    assert s["steps"] == 4  # 6 iterations - first interval skip - 1
    assert s["mean_s"] > 0
    assert s["examples_per_sec"] > 0
    timer.reset()
    assert timer.summary() == {"steps": 0}


def test_device_memory_stats_shape():
    stats = device_memory_stats()
    assert isinstance(stats, list) and stats
    assert "device" in stats[0]
