"""Config serde round-trips — parity with reference
MultiLayerNeuralNetConfigurationTest / NeuralNetConfigurationTest (SURVEY §4)."""

import pytest

from deeplearning4j_tpu.nn.conf import (
    ConvolutionLayerConf,
    DenseLayerConf,
    GravesLSTMConf,
    MultiLayerConfiguration,
    NeuralNetConfiguration,
    OutputLayerConf,
    RBMConf,
    SubsamplingLayerConf,
    layer_conf_from_dict,
)
from deeplearning4j_tpu.nn.conf.config import Builder


def _sample_conf() -> MultiLayerConfiguration:
    return MultiLayerConfiguration(
        conf=NeuralNetConfiguration(
            learning_rate=0.05, updater="adam", seed=42, l2=1e-4),
        layers=(
            ConvolutionLayerConf(n_in=1, n_out=6, kernel_size=(5, 5)),
            SubsamplingLayerConf(pooling_type="max"),
            DenseLayerConf(n_in=864, n_out=120, activation="relu"),
            OutputLayerConf(n_in=120, n_out=10),
        ),
        input_preprocessors={"2": {"type": "cnn_to_ffn"}},
    )


class TestJsonRoundTrip:
    def test_json_round_trip_preserves_everything(self):
        c = _sample_conf()
        c2 = MultiLayerConfiguration.from_json(c.to_json())
        assert c2 == c

    def test_yaml_round_trip(self):
        c = _sample_conf()
        assert MultiLayerConfiguration.from_yaml(c.to_yaml()) == c

    def test_layer_types_preserved(self):
        c = _sample_conf()
        c2 = MultiLayerConfiguration.from_json(c.to_json())
        assert isinstance(c2.layers[0], ConvolutionLayerConf)
        assert c2.layers[0].kernel_size == (5, 5)
        assert isinstance(c2.layers[3], OutputLayerConf)
        assert c2.layers[3].loss == "mcxent"

    def test_rbm_units_round_trip(self):
        d = RBMConf(n_in=10, n_out=5, visible_unit="gaussian",
                    hidden_unit="rectified", k=3).to_dict()
        r = layer_conf_from_dict(d)
        assert isinstance(r, RBMConf)
        assert r.visible_unit == "gaussian" and r.k == 3

    def test_lstm_round_trip(self):
        d = GravesLSTMConf(n_in=16, n_out=32, forget_gate_bias_init=5.0).to_dict()
        r = layer_conf_from_dict(d)
        assert isinstance(r, GravesLSTMConf)
        assert r.forget_gate_bias_init == 5.0


    @pytest.mark.parametrize("tag", ["graveslstm", "lstm"])
    def test_saved_conf_with_the_retired_fused_key_still_loads(self, tag):
        """Confs saved while the LSTM layers had a `fused` field carry
        the key: it is ignored on read."""
        r = layer_conf_from_dict({"type": tag, "n_in": 4, "n_out": 8,
                                  "fused": True})
        assert (r.n_in, r.n_out) == (4, 8)
        assert "fused" not in r.to_dict()


class TestOverridesAndBuilder:
    def test_per_layer_override(self):
        base = DenseLayerConf(n_in=4, n_out=8)
        over = base.with_overrides(activation="relu", dropout=0.5)
        assert over.activation == "relu" and over.dropout == 0.5
        assert base.activation == "sigmoid"  # frozen original untouched

    def test_builder_fluent(self):
        conf = (Builder()
                .learning_rate(0.01)
                .updater("rmsprop")
                .seed(7)
                .layer(DenseLayerConf(n_in=4, n_out=8))
                .layer(OutputLayerConf(n_in=8, n_out=3))
                .build())
        assert conf.conf.learning_rate == 0.01
        assert conf.conf.updater == "rmsprop"
        assert len(conf.layers) == 2

    def test_updater_config_derivation(self):
        conf = NeuralNetConfiguration(updater="adam", learning_rate=0.003,
                                      l2=0.01, clip_norm=5.0)
        uc = conf.updater_config()
        assert uc.learning_rate == 0.003
        assert uc.l2 == 0.01 and uc.clip_norm == 5.0
