"""The `deepseek-v2-serve-ep4` configuration and its cell: a `--tiny`
rehearsal of the whole run; the file against the catalog's published keys;
the new readers on a tiny run's counters, on a made-up trace whose sums are
known, and on runs that have nothing for them."""

import argparse
import ast
import json
import pathlib
import time
import types

import pytest

from benchmark import flops_latent, spec, trace_reduce
from benchmark.observe import Run
from benchmark.trace_reduce import DeviceTrace, Event
from conftest import run_cell

CELL = "deepseek-v2.docqa-sat"
FILE = spec.ROOT / "benchmark" / "configs" / "deepseek-v2-serve-ep4.json"
BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
NEW = [m["name"] for m in BENCH["per_layer"] if m["workloads"] == [CELL]]
# `config.json` of deepseek-ai/DeepSeek-V2, the keys that give its shape
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400}


def read(metric, run):
    return spec.reader("layer_metrics", metric).read(run)


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_rehearsal_of_the_cell(root, trace):
    code, out, err = run_cell(root, "--workload", CELL, "--seed",
                              "3000000019", "--seconds", "3", "--trace",
                              str(trace), "--tiny")
    assert code == 0, err[-3000:]
    last = json.loads(out[-1])
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0


def test_the_file_keeps_every_published_key_but_the_four_reduced():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "deepseek-v2-serve-ep4")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "max_position_embeddings"]
    config = json.loads(FILE.read_text())
    differs = [k for k, v in PUBLISHED.items() if config.get(k) != v]
    assert sorted(differs) == sorted(entry["reduced"])
    assert config["n_routed_experts_published"] == 160
    assert config["experts_held"] == [0, 40] and "deployment" in config
    assert config["layer_norm_epsilon"] == config["rms_norm_eps"]
    # the floors: 4 expert layers after the dense one, 8 experts, 1/8 vocab
    assert config["num_hidden_layers"] >= 5
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_adapter_builds_what_the_programs_constructor_builds():
    from deeplearning4j_tpu.parallel import transformer as tfm

    config = spec._with_tiny(json.loads(FILE.read_text()), False)
    cfg = spec.adapter(config).program_config(config, "bfloat16", False)
    assert cfg == tfm.deepseek_v2(layers=5, experts_held=(0, 40),
                                  vocab=25600, max_len=16384)
    assert cfg.experts.published == 160 and cfg.latent.row_values == 576


def test_the_reference_imports_nothing_of_the_program():
    path = spec.ROOT / "benchmark" / "reference" / "deepseek_v2.py"
    source = path.read_text()
    names = {n.module if isinstance(n, ast.ImportFrom) else a.name
             for n in ast.walk(ast.parse(source))
             if isinstance(n, (ast.Import, ast.ImportFrom))
             for a in n.names}
    assert not {n for n in names if n and n.startswith(
        ("deeplearning4j_tpu", "benchmark"))}
    assert 'default_matmul_precision("highest")' in source


@pytest.mark.parametrize("fault, kept", [("no_routed", 0.0),
                                         ("routed_unscaled", 1 / 16)])
def test_a_planted_fault_is_what_it_says(fault, kept):
    """`tools/control.py <cell> <s> +no_routed,routed_unscaled <seed>`: the
    reference with the routed experts' sum left out, or combined without
    the factor 16, and nothing else touched."""
    import jax
    import numpy as np

    from benchmark.reference import deepseek_v2 as reference

    config = spec.load_cell(CELL, tiny=True).config
    adapter = spec.adapter(config)
    cfg = adapter.program_config(config, "float32", remat=False)
    p = adapter.make_params(cfg, 5, "float32")["layers"][1]["experts"]
    x = jax.random.normal(jax.random.PRNGKey(1), (12, cfg.d_model))
    whole = reference.expert_layer(p, x)
    routed = reference.expert_layer(p, x, shared=False)
    assert float(np.abs(routed).max()) > 1e-3
    np.testing.assert_allclose(reference.expert_layer(p, x, quant=fault),
                               whole - (1 - kept) * routed, atol=1e-5)


def test_roofline_arithmetic():
    # a cached token: 1,152 bytes against 128 heads x 2 x 1,088 operations
    assert flops_latent.latent_attention_bytes(1, 576) == 1152.0
    assert flops_latent.latent_attention_flops(1, 128, 576, 512) == 278528.0
    assert 278528 / 1152 == pytest.approx(241.8, abs=0.1)   # the v5e's ridge


@pytest.fixture(scope="module")
def tiny_docqa():
    import jax

    cell = spec.load_cell(CELL, tiny=True)
    args = argparse.Namespace(seed=11, seconds=2.0, trace=0, tiny=True)
    run, checks, attempted, failed, _ = spec.driver(cell.config).run(
        cell, args, time.perf_counter(), jax.devices()[:1])
    assert attempted > 0 and failed == 0
    assert all(value <= limit for _, value, limit in checks)
    return run


@pytest.mark.parametrize("metric", [
    "expert_load_peak.docqa", "feed_fill.sat", "lane_occupancy.sat",
    "prefix_saved_share", "round_host_ms.sat", "warmup_s"])
def test_counter_and_span_readers_find_their_numbers(tiny_docqa, metric):
    value = read(metric, tiny_docqa)
    assert value is not None and value > 0.0
    if "%" == spec.reader("layer_metrics", metric).UNIT:
        assert value <= 100.0
    if metric.startswith("expert_load_peak"):
        assert value >= 1.0                     # 1 is an even load


def test_the_programs_new_counters_add_up(tiny_docqa):
    after = tiny_docqa.counters["after"]
    pairs = after["experts"]["pairs"]
    cfg = tiny_docqa.model
    expert_layers = cfg.n_layers - cfg.dense_layers
    fed = sum(after["rounds"]["fed_tokens"].values())
    assert pairs["held"] + pairs["absent"] == (
        fed * cfg.experts.per_token * expert_layers)
    assert after["rounds"]["attn_pairs"]["w1"] >= (
        after["rounds"]["attn_rows"]["w1"])


def made_up_trace(grouped_calls=12):
    """Two width-1 rounds of 10 ms: five latent kernel calls of 1 ms and
    twelve ragged-dot calls of 0.25 ms in each."""
    ms, ops, modules = 1e-3, [], []
    for r in range(2):
        t0 = r * 20 * ms
        modules.append(Event("jit_step(77)", t0, 10 * ms))
        for i in range(5):
            ops.append(Event(
                f"%latent_paged_attention.{i} = bf16[16,1,128,512]{{3,2,1,0}}"
                " custom-call(s32[16,128]{1,0} %add.1, s32[16]{0} %p.2)",
                t0 + i * ms, 1 * ms))
        for i in range(grouped_calls):
            ops.append(Event(
                f"%ragged-dot-none.{i} = bf16[96,1536]{{1,0}} custom-call("
                "%get-tuple-element.1)", t0 + (5 + i * 0.25) * ms,
                0.25 * ms))
    return trace_reduce.reduce(
        [DeviceTrace("/device:TPU:0", sorted(ops, key=lambda e: e.start),
                     modules)],
        [Event(trace_reduce.WINDOW_SPAN, 0.0, 40 * ms)])


def test_trace_readers_on_a_made_up_trace():
    model = types.SimpleNamespace(
        n_layers=5, n_heads=128, dtype="bfloat16", experts=object(),
        latent=types.SimpleNamespace(row_values=576, kv_rank=512))

    def at(n, rows, pairs):
        return {"rounds": {"by_width": {"1": n}, "count": n,
                           "attn_rows": {"w1": rows, "wide": 0},
                           "attn_pairs": {"w1": pairs, "wide": 0}}}

    run = Run(cell=None, chips=1, model=model, device_trace=made_up_trace(),
              peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
              counters={"before": at(100, 0, 0),
                        "after": at(200, 100 * 64000, 100 * 64000)})
    assert read("step_ms.sat", run) == pytest.approx(10.0)
    assert read("latent_kernel_ms_per_step", run) == pytest.approx(5.0)
    assert read("expert_ms_per_step", run) == pytest.approx(3.0)
    assert read("idle_share.sat", run) == pytest.approx(60.0)   # ops: 16 of 40 ms
    # 64,000 rows a round: the operations bound by a hair (241.8 flop/B
    # against the chip's 240.5)
    least = 5 * max(64000 * 1152 / 819e9, 64000 * 278528 / 197e12)
    assert read("latent_kernel_roofline", run) == pytest.approx(
        100 * least / 5e-3)
    assert 0 < read("latent_kernel_roofline", run) < 100


def test_a_traced_window_of_width_1_rounds_reads_no_grouped_matmul():
    """Order 31's traced 4 s hold no wide round (call E, PR 26): the
    compiler's fusions at 36 rows are not `ragged-dot` calls, and the
    reader still gives the line a number."""
    model = types.SimpleNamespace(n_layers=5, n_heads=128, experts=object())
    run = Run(cell=None, chips=1, model=model, peaks={},
              device_trace=made_up_trace(grouped_calls=0),
              counters={"before": {}, "after": {}})
    assert read("expert_ms_per_step", run) == 0.0


@pytest.mark.parametrize("metric", NEW)
def test_a_run_of_another_family_or_of_the_parent_gives_nothing(metric):
    """GPT-2's configuration has no latent rows and no experts (and the
    parent's has not even the fields); its `stats()` has no expert counts;
    no trace was taken."""
    assert len(NEW) == 4
    for model in (types.SimpleNamespace(n_layers=36, n_heads=20,
                                        latent=None, experts=None),
                  types.SimpleNamespace(n_layers=36, n_heads=20)):
        run = Run(cell=None, chips=1, model=model,
                  peaks={"hbm_bytes_per_s": 819e9,
                         "bf16_flops_per_s": 197e12},
                  counters={"before": {}, "after": {"slots": 16}},
                  traces=[{"spans": [{"name": "queue_wait", "dur_s": 0.1}]}])
        assert read(metric, run) is None
