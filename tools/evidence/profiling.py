"""Profiling evidence: a real XLA trace captured around training steps,
StepTimer throughput stats, and device memory stats — the §5 profiling
subsystem (beyond the 2015 reference, which had no profiler)."""

from _common import capture, ensure_cpu_mesh, write_log

ensure_cpu_mesh(8)

import pathlib  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

from deeplearning4j_tpu.models import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.nn.conf import (  # noqa: E402
    DenseLayerConf,
    MultiLayerConfiguration,
    NeuralNetConfiguration,
    OutputLayerConf,
)
from deeplearning4j_tpu.obs.trace import annotate  # noqa: E402
from deeplearning4j_tpu.runtime.profiler import (  # noqa: E402
    StepTimer,
    device_memory_stats,
    trace,
)


def main() -> None:
    conf = MultiLayerConfiguration(
        conf=NeuralNetConfiguration(learning_rate=0.05, updater="adam"),
        layers=(DenseLayerConf(n_in=32, n_out=64, activation="relu"),
                OutputLayerConf(n_in=64, n_out=4)))
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    X = rng.standard_normal((128, 32)).astype(np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 128)]

    logdir = tempfile.mkdtemp()
    timer = StepTimer(batch_size=128, skip=1)  # iteration listener
    with trace(logdir):
        for i in range(6):
            with annotate(f"step{i}"):
                net.fit_batch(X, Y)
            timer(i, 0.0)
    files = list(pathlib.Path(logdir).rglob("*"))
    traced = [f for f in files if f.is_file()]
    print(f"trace artifacts written: {len(traced)} files "
          f"(e.g. {traced[0].name if traced else 'none'})")
    assert traced, "no trace files written"
    stats = timer.summary()
    print("StepTimer:", {k: round(v, 2) if isinstance(v, float) else v
                         for k, v in stats.items()})
    assert stats["steps"] == 4 and stats["examples_per_sec"] > 0
    mem = device_memory_stats()
    print(f"device_memory_stats: {len(mem)} device entries "
          f"(keys: {sorted(mem[0])[:4] if mem else '-'})")
    print("GREEN: profiling subsystem (trace, StepTimer, memory stats)")


if __name__ == "__main__":
    with capture() as buf:
        main()
    write_log("profiling", buf.getvalue())
