"""CLI tests — reference `cli/subcommands/TrainTest.java` trained against
irisSvmLight.txt + a JSON model config; same flow here."""

import json
import re

import numpy as np
import pytest

from deeplearning4j_tpu.cli import main
from deeplearning4j_tpu.datasets.fetchers import iris_dataset
from deeplearning4j_tpu.nn.conf import (
    DenseLayerConf,
    MultiLayerConfiguration,
    NeuralNetConfiguration,
    OutputLayerConf,
)


@pytest.fixture(scope="module")
def iris_svmlight(tmp_path_factory):
    """Write iris as an SVMLight file (the reference CLI's default format)."""
    path = tmp_path_factory.mktemp("data") / "iris.svmlight"
    ds = iris_dataset()
    labels = ds.labels.argmax(1)
    with open(path, "w") as f:
        for xi, yi in zip(ds.features, labels):
            feats = " ".join(f"{j + 1}:{v:.6f}" for j, v in enumerate(xi))
            f.write(f"{yi} {feats}\n")
    return path


@pytest.fixture(scope="module")
def model_json(tmp_path_factory):
    conf = MultiLayerConfiguration(
        conf=NeuralNetConfiguration(seed=12, learning_rate=0.05,
                                    updater="adam"),
        layers=(DenseLayerConf(n_in=4, n_out=16, activation="relu"),
                OutputLayerConf(n_in=16, n_out=3)))
    path = tmp_path_factory.mktemp("model") / "model.json"
    path.write_text(conf.to_json())
    return path


def test_train_test_predict_round_trip(iris_svmlight, model_json, tmp_path,
                                       capsys):
    out = tmp_path / "out"
    rc = main(["train", "-input", str(iris_svmlight), "-model",
               str(model_json), "-output", str(out), "-epochs", "60",
               "-savemode", "txt"])
    assert rc == 0
    stdout = capsys.readouterr().out
    assert "examples/sec" in stdout
    assert (out / "model" / "conf.json").exists()
    assert (out / "params.txt").exists()

    rc = main(["test", "-input", str(iris_svmlight), "-model",
               str(out / "model")])
    assert rc == 0
    stats = capsys.readouterr().out
    assert "Accuracy" in stats or "accuracy" in stats

    preds_file = tmp_path / "preds.txt"
    rc = main(["predict", "-input", str(iris_svmlight), "-model",
               str(out / "model"), "-output", str(preds_file)])
    assert rc == 0
    preds = np.loadtxt(preds_file)
    assert preds.shape == (150,)
    # Model trained 60 epochs on iris must beat random guessing handily.
    truth = iris_dataset().labels.argmax(1)
    assert (preds == truth).mean() > 0.9


def test_properties_file_overrides(iris_svmlight, model_json, tmp_path,
                                   capsys):
    props = tmp_path / "train.props"
    props.write_text("input.format=svmlight\n"
                     "input.num.features=4\n"
                     "input.num.classes=3\n"
                     "train.epochs=2\n"
                     "train.batch.size=50\n")
    out = tmp_path / "out"
    rc = main(["train", "-input", str(iris_svmlight), "-model",
               str(model_json), "-output", str(out), "-conf", str(props)])
    assert rc == 0
    assert "Trained 2 epochs" in capsys.readouterr().out


def test_spmd_runtime_handles_remainder_batches(iris_svmlight, model_json,
                                                tmp_path, capsys):
    # 150 examples / batch 32 → final batch of 22, not divisible by the
    # 8-device test mesh; the CLI must pad it rather than crash.
    out = tmp_path / "out"
    rc = main(["train", "-input", str(iris_svmlight), "-model",
               str(model_json), "-output", str(out), "-epochs", "2",
               "-batch", "32", "-runtime", "spmd"])
    assert rc == 0
    assert "examples/sec" in capsys.readouterr().out


def test_spmd_pad_longer_than_tail(iris_svmlight, model_json, tmp_path,
                                   capsys):
    # 150 % 148 → tail batch of 2 on an 8-device mesh needs 6 pad rows,
    # MORE than the tail itself — padding must wrap modulo the batch.
    out = tmp_path / "out"
    rc = main(["train", "-input", str(iris_svmlight), "-model",
               str(model_json), "-output", str(out), "-epochs", "1",
               "-batch", "148", "-runtime", "spmd"])
    assert rc == 0
    assert "examples/sec" in capsys.readouterr().out


def test_csv_input(model_json, tmp_path, capsys):
    ds = iris_dataset()
    csv = tmp_path / "iris.csv"
    rows = np.concatenate([ds.features, ds.labels.argmax(1)[:, None]], axis=1)
    np.savetxt(csv, rows, delimiter=",", fmt="%.6f")
    rc = main(["train", "-input", str(csv), "-model", str(model_json),
               "-output", str(tmp_path / "o"), "-epochs", "2"])
    assert rc == 0


def test_lm_train_save_generate(tmp_path, capsys):
    """`dl4j lm`: byte-level TransformerLM trains on raw text, saves, and
    a second invocation generates from the saved model."""
    text = tmp_path / "corpus.txt"
    text.write_text("the quick brown fox jumps over the lazy dog. " * 40)
    out = tmp_path / "lm"
    rc = main(["lm", "-input", str(text), "-output", str(out),
               "-epochs", "2", "-batch", "4", "-seq", "32",
               "-d-model", "32", "-layers", "1", "-heads", "2"])
    assert rc == 0
    assert (out / "lm_config.json").exists()
    assert (out / "lm_params.npz").exists()
    assert "tokens/sec" in capsys.readouterr().out
    rc = main(["lm", "-output", str(out), "-generate", "the quick",
               "-max-new", "8", "-temperature", "0"])
    assert rc == 0
    sampled = capsys.readouterr().out
    assert sampled.startswith("the quick") and len(sampled) > len("the quick")


def test_lm_accum_trains_and_generates(tmp_path, capsys):
    """`dl4j lm -accum k`: gradient accumulation through
    make_accum_train_step; training completes, saves, generates."""
    text = tmp_path / "corpus.txt"
    text.write_text("to be or not to be that is the question. " * 40)
    out = tmp_path / "lm"
    rc = main(["lm", "-input", str(text), "-output", str(out),
               "-epochs", "2", "-batch", "4", "-seq", "32", "-accum", "2",
               "-d-model", "32", "-layers", "1", "-heads", "2"])
    assert rc == 0
    rc = main(["lm", "-output", str(out), "-generate", "to be",
               "-max-new", "6", "-temperature", "0"])
    assert rc == 0
    # indivisible accum fails fast with a clear message
    with pytest.raises(SystemExit, match="divisible"):
        main(["lm", "-input", str(text), "-output", str(out),
              "-epochs", "1", "-batch", "4", "-seq", "32", "-accum", "3",
              "-d-model", "32", "-layers", "1", "-heads", "2"])


@pytest.mark.slow  # ~9s; beam/eval semantics are pinned in
# tests/test_generation.py — this adds only the CLI plumbing
def test_lm_eval_perplexity_and_beam_generate(tmp_path, capsys):
    """`dl4j lm -eval`: held-out byte perplexity; `-beam k`: beam-search
    decoding from the saved model."""
    text = tmp_path / "corpus.txt"
    text.write_text("all work and no play makes jack a dull boy. " * 40)
    held = tmp_path / "held.txt"  # same distribution: ppl well below uniform
    held.write_text("all work and no play makes jack a dull boy. " * 20)
    out = tmp_path / "lm"
    rc = main(["lm", "-input", str(text), "-output", str(out),
               "-epochs", "20", "-batch", "8", "-seq", "32", "-lr", "0.01",
               "-d-model", "32", "-layers", "1", "-heads", "2"])
    assert rc == 0
    capsys.readouterr()
    rc = main(["lm", "-output", str(out), "-eval", str(held)])
    assert rc == 0
    stdout = capsys.readouterr().out
    m = re.search(r"perplexity (\d+\.?\d*)", stdout)
    # trained model: far below the uniform-byte 256 (measured ~18)
    assert m and 1.0 < float(m.group(1)) < 100.0
    rc = main(["lm", "-output", str(out), "-generate", "all work",
               "-max-new", "6", "-beam", "2"])
    assert rc == 0
    assert capsys.readouterr().out.startswith("all work")


def test_lm_spmd_runtime_trains_data_parallel(tmp_path, capsys):
    """`dl4j lm -runtime spmd`: the batch shards over the 8-device mesh
    (GSPMD inserts the gradient allreduce); training completes and the
    saved LM generates."""
    text = tmp_path / "c.txt"
    text.write_text("abcdefgh " * 300)
    out = tmp_path / "lm"
    rc = main(["lm", "-input", str(text), "-output", str(out),
               "-epochs", "1", "-batch", "8", "-seq", "16",
               "-d-model", "16", "-layers", "1", "-heads", "2",
               "-runtime", "spmd"])
    assert rc == 0
    captured = capsys.readouterr()
    # observed from the arrays' shardings after the first step, not
    # claimed from the mesh size
    assert ("spmd: placement batch on 8 devices, params on 8 devices"
            in captured.out)
    assert captured.err.startswith("lm: jax ")     # start-up device line
    rc = main(["lm", "-output", str(out), "-generate", "abc",
               "-max-new", "4", "-temperature", "0"])
    assert rc == 0


def test_train_runs_greedy_pretraining_for_dbn(tmp_path, capsys,
                                               monkeypatch):
    """A pretrain=True config (zoo:dbn-mnist) must actually pretrain from
    the CLI — the loop previously called fit_batch directly and silently
    skipped it."""
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork,
    )

    calls = []
    orig = MultiLayerNetwork.pretrain

    def spy(self, *a, **k):
        calls.append(1)
        return orig(self, *a, **k)

    monkeypatch.setattr(MultiLayerNetwork, "pretrain", spy)
    rng = np.random.default_rng(0)
    x = rng.random((64, 16)).astype(np.float32)
    labels = rng.integers(0, 3, 64)
    csv = tmp_path / "d.csv"
    np.savetxt(csv, np.concatenate([x, labels[:, None]], axis=1),
               delimiter=",", fmt="%.5f")
    conf_json = tmp_path / "dbn.json"
    from deeplearning4j_tpu.models import get_model

    conf_json.write_text(get_model(
        "dbn-mnist", layer_sizes=(16, 8), n_out=3).to_json())
    rc = main(["train", "-input", str(csv), "-model", str(conf_json),
               "-output", str(tmp_path / "o"), "-epochs", "2",
               "-batch", "32"])
    assert rc == 0
    assert calls, "CLI train must run greedy pretraining for pretrain confs"


@pytest.mark.slow  # ~35s: two full CLI mesh trainings back to back
def test_lm_mesh_runtimes_match_each_other(tmp_path, capsys):
    """`-runtime hybrid` (dp/sp/tp) and `-runtime pipeline` (dp/pp) both
    train end-to-end through the CLI on the 8-device mesh, save in the
    standard layout, and — same seed, same data order — land on the
    same final loss.  The single-runtime boot/train paths stay in tier-1
    via `test_lm_mesh_runtime_single_device` and the runtime-specific
    trainer equivalence tests; this pairwise A/B is the long gate."""
    text = tmp_path / "corpus.txt"
    text.write_text("the quick brown fox jumps over the lazy dog. " * 60)
    finals = {}
    for runtime in ("hybrid", "pipeline"):
        out = tmp_path / f"lm_{runtime}"
        rc = main(["lm", "-input", str(text), "-output", str(out),
                   "-epochs", "1", "-batch", "8", "-seq", "16",
                   "-d-model", "32", "-layers", "4", "-heads", "4",
                   "-lr", "3e-3", "-runtime", runtime,
                   "-generate", "the", "-max-new", "4",
                   "-temperature", "0"])
        assert rc == 0
        assert (out / "lm_params.npz").exists()
        got = capsys.readouterr().out
        assert f"{runtime}: training on mesh" in got
        finals[runtime] = float(
            got.split("final loss ")[1].split(",")[0])
    assert finals["hybrid"] == pytest.approx(finals["pipeline"],
                                             abs=1e-3)


@pytest.mark.slow  # ~8s; MoE dispatch semantics are pinned by
# TestMoEDispatch in tier-1 — this adds only the CLI flag plumbing
def test_lm_moe_experts_flag(tmp_path, capsys):
    """-experts trains a Switch-MoE byte LM end-to-end (train -> save ->
    generate), and the pipeline runtime rejects it with the documented
    boundary message."""
    text = tmp_path / "corpus.txt"
    text.write_text("the quick brown fox jumps over the lazy dog. " * 40)
    out = tmp_path / "lm_moe"
    rc = main(["lm", "-input", str(text), "-output", str(out),
               "-epochs", "1", "-batch", "4", "-seq", "16",
               "-d-model", "32", "-layers", "2", "-heads", "4",
               "-experts", "2", "-generate", "the", "-max-new", "4",
               "-temperature", "0"])
    assert rc == 0
    assert (out / "lm_params.npz").exists()
    cfg = json.loads((out / "lm_config.json").read_text())
    assert cfg["n_experts"] == 2
    capsys.readouterr()
    with pytest.raises(SystemExit, match="pipeline"):
        main(["lm", "-input", str(text), "-output", str(out),
              "-experts", "2", "-runtime", "pipeline"])


def test_lm_mesh_layout_factorization():
    """The layout chooser must produce a valid mesh for ANY device count
    — in particular n=1 (the single real TPU chip) must degrade both
    runtimes to a trivial mesh instead of erroring."""
    from deeplearning4j_tpu.cli import _lm_mesh_layout

    for n in (1, 2, 3, 4, 6, 8, 16):
        shape, B, _ = _lm_mesh_layout("hybrid", n, S=16, n_heads=4,
                                      n_layers=4, B=8)
        dp, sp, tp = shape
        assert dp * sp * tp <= n and B % dp == 0
        assert 16 % (2 * sp) == 0 and 4 % tp == 0
        shape, B, mb = _lm_mesh_layout("pipeline", n, S=16, n_heads=4,
                                       n_layers=4, B=8)
        dp, stages = shape
        assert dp * stages <= n and 4 % stages == 0
        assert B % dp == 0 and (B // dp) % mb == 0
    # a length the zigzag ring cannot deal (2 * sp chunks) keeps sp at 1
    assert _lm_mesh_layout("hybrid", 4, 18, 4, 4, 8)[0] == (2, 1, 2)
    # n=1 degrades to the trivial mesh for both
    assert _lm_mesh_layout("hybrid", 1, 16, 4, 4, 8)[0] == (1, 1, 1)
    assert _lm_mesh_layout("pipeline", 1, 16, 4, 4, 8)[0] == (1, 1)
    # odd layer counts still find a stage split (or degrade to 1)
    assert _lm_mesh_layout("pipeline", 8, 16, 4, 3, 8)[0] == (8, 1)


@pytest.mark.slow  # ~18s CLI mesh training; the spmd-runtime CLI
# train stays in tier-1 (tier-1 870s budget)
def test_lm_mesh_runtime_single_device(tmp_path, monkeypatch):
    """-runtime pipeline on ONE visible device (the real-chip case) must
    train rather than error."""
    import jax

    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a: real(*a)[:1])
    text = tmp_path / "c.txt"
    text.write_text("abcd " * 200)
    rc = main(["lm", "-input", str(text), "-output",
               str(tmp_path / "lm1"), "-epochs", "1", "-batch", "4",
               "-seq", "16", "-d-model", "32", "-layers", "4",
               "-heads", "4", "-runtime", "pipeline"])
    assert rc == 0


def test_train_spmd_sync_every(tmp_path, iris_svmlight, model_json,
                               capsys):
    """-sync-every N on the spmd runtime trains in local-SGD mode
    (replica averaging every N steps) and still converges on Iris."""
    rc = main(["train", "-input", str(iris_svmlight), "-model",
               str(model_json), "-output", str(tmp_path / "m"),
               "-epochs", "30", "-batch", "32", "-runtime", "spmd",
               "-sync-every", "4"])
    assert rc == 0
    got = capsys.readouterr().out
    assert "local-SGD mode, averaging every 4 steps" in got
    acc = float(re.search(r"Accuracy:\s+([0-9.]+)", got).group(1))
    assert acc >= 0.85, got


@pytest.mark.chaos
def test_train_resilience_checkpoints_and_resumes(tmp_path, iris_svmlight,
                                                  model_json, capsys):
    """-resilience supervises training (periodic checkpoints + manifest)
    and a second invocation resumes from the newest checkpoint."""
    args = ["train", "-input", str(iris_svmlight), "-model",
            str(model_json), "-output", str(tmp_path / "m"),
            "-epochs", "4", "-batch", "32", "-resilience",
            "-ckpt-every", "5"]
    assert main(args) == 0
    got = capsys.readouterr().out
    assert "resilience: completed" in got
    ckpts = tmp_path / "m" / "ckpts"
    assert (ckpts / "manifest.json").exists()
    assert any(p.name.startswith("ckpt-") for p in ckpts.iterdir())

    assert main(args) == 0
    got = capsys.readouterr().out
    assert "resilience: resumed from checkpoint step" in got
