"""The `solar-open2-serve-ep8` configuration and its cell: a `--tiny`
rehearsal of the whole run; the file against the catalog's published keys;
the new readers on a tiny run's counters, on a made-up trace whose sums are
known, and on runs that have nothing for them."""

import argparse
import ast
import json
import time
import types

import pytest

from benchmark import flops_kda, spec, trace_reduce
from benchmark.observe import Run
from benchmark.trace_reduce import DeviceTrace, Event
from conftest import run_cell

CELL = "solar-open2.longdoc-sat"
FILE = spec.ROOT / "benchmark" / "configs" / "solar-open2-serve-ep8.json"
BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
NEW = [m["name"] for m in BENCH["per_layer"] if m["workloads"] == [CELL]]
# `config.json` of upstage/Solar-Open2-250B, the keys that give its shape
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48,
    "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}


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
                 if c["name"] == "solar-open2-serve-ep8")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "max_position_embeddings"]
    config = json.loads(FILE.read_text())
    differs = [k for k, v in PUBLISHED.items() if config.get(k) != v]
    assert sorted(differs) == sorted(entry["reduced"])
    assert config["n_routed_experts_published"] == 320
    assert config["experts_held"] == [0, 40] and "deployment" in config
    assert config["layer_norm_epsilon"] == config["rms_norm_eps"]
    assert config["n_positions"] == config["max_position_embeddings"]
    # the floors: a whole period of four, 8 experts, 1/8 vocabulary
    assert config["num_hidden_layers"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # every inference the layer equations rest on is written down
    assert {"use_gqa_gate", "kda_gate_rank", "scoring_func",
            "shared_expert_width", "kda_layer", "weights"} <= set(
                config["assumed"])
    serve = config["serve"]
    assert serve["snapshot_every"] % serve["page_size"] == 0
    assert serve["snapshot_every"] % serve["prefill_chunk"] == 0
    assert serve["state_rows"] >= 2 * serve["slots"]


def test_the_adapter_builds_what_the_programs_constructor_builds():
    from deeplearning4j_tpu.parallel import generation as gen
    from deeplearning4j_tpu.parallel import transformer as tfm

    config = spec._with_tiny(json.loads(FILE.read_text()), False)
    cfg = spec.adapter(config).program_config(config, "bfloat16", False)
    assert cfg == tfm.solar_open2(layers=4, experts_held=(0, 40),
                                  vocab=24576, max_len=24576)
    assert cfg.mixer_kinds() == ("full", "kda", "kda", "kda")
    # the program's sizes are the yardstick's
    la = cfg.linear
    assert gen.state_row_bytes(cfg) == flops_kda.state_row_bytes(
        3, la.heads, la.k_dim, la.v_dim, la.conv_taps)
    assert gen.pool_token_bytes(cfg) == flops_kda.gqa_paged_bytes(
        1, 1, cfg.n_kv_heads, cfg.head_dim)


def test_the_reference_imports_nothing_of_the_program():
    path = spec.ROOT / "benchmark" / "reference" / "solar_open2.py"
    source = path.read_text()
    names = {n.module if isinstance(n, ast.ImportFrom) else a.name
             for n in ast.walk(ast.parse(source))
             if isinstance(n, (ast.Import, ast.ImportFrom))
             for a in n.names}
    assert not {n for n in names if n and n.startswith(
        ("deeplearning4j_tpu", "benchmark"))}
    assert 'default_matmul_precision("highest")' in source


def test_the_control_moves_the_references_logits():
    """`tools/control.py <cell> <s> +fp8 <seed>`: the reference with every
    linear layer's operands in float8 is another function (the cell's limit
    is set from chip readings, PERF.md section 2)."""
    import jax
    import numpy as np

    from benchmark.reference import solar_open2 as reference

    config = spec.load_cell(CELL, tiny=True).config
    adapter = spec.adapter(config)
    cfg = adapter.program_config(config, "float32", remat=False)
    params = adapter.make_params(cfg, 5, "float32")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                                cfg.vocab_size)
    sound = reference.logits(params, tokens, 1e-5)
    low = reference.logits(params, tokens, 1e-5, "fp8")
    assert float(np.abs(np.asarray(sound - low)).max()) > 1e-3
    with pytest.raises(ValueError, match="unknown control"):
        reference.logits(params, tokens, 1e-5, "fp4")


def test_roofline_arithmetic():
    # a sequence's state: 3 layers x (4 MB of float32 + 147 KB of tails)
    assert flops_kda.state_row_bytes(3, 64, 128, 128, 4) == 13025280
    assert flops_kda.kda_step_bytes(8, 3, 64, 128, 128, 4) == 2 * 8 * 13025280
    # a cached token of the one grouped-query layer: 4 KB; a page 512 KB
    assert flops_kda.gqa_paged_bytes(1, 128, 8, 128) == 524288.0
    assert 13025280 // 4096 == 3180         # a state is worth 3,180 tokens
    # the chunked form: 163,840 float32 operations a token and head
    per = flops_kda.kda_chunk_flops(64, 1, 1, 128, 128, chunk=64) / 64
    assert per == 64 * (4 * 128 + 256 + 256) + 6 * 128 * 128 == 163840
    # a wide round's bytes: the lanes' rows twice and 2,560 B a token-head
    assert flops_kda.kda_chunk_bytes(2048, 8, 3, 64, 128, 128, 4) == (
        2 * 8 * 13025280 + 2048 * 3 * 64 * 640 * 4)


@pytest.fixture(scope="module")
def tiny_longdoc():
    import jax

    cell = spec.load_cell(CELL, tiny=True)
    args = argparse.Namespace(seed=11, seconds=2.0, trace=0, tiny=True)
    run, checks, attempted, failed, _ = spec.driver(cell.config).run(
        cell, args, time.perf_counter(), jax.devices()[:1])
    assert attempted > 0 and failed == 0
    assert all(value <= limit for _, value, limit in checks)
    return run


@pytest.mark.parametrize("metric", [
    "feed_fill.sat", "lane_occupancy.sat", "prefix_saved_share",
    "round_host_ms.sat", "warmup_s", "expert_load_peak.longdoc"])
def test_counter_and_span_readers_find_their_numbers(tiny_longdoc, metric):
    value = read(metric, tiny_longdoc)
    assert value is not None and value > 0.0
    if "%" == spec.reader("layer_metrics", metric).UNIT:
        assert value <= 100.0


def test_the_programs_new_counters_add_up(tiny_longdoc):
    after = tiny_longdoc.counters["after"]
    state, rounds = after["state"], after["rounds"]
    # a state row a lane a round: the rows are the dispatched lanes
    assert sum(state["kda_rows"].values()) == after["rows"]
    assert state["kda_rows"]["w1"] <= rounds["by_width"]["1"] * after["slots"]
    snaps = state["snapshots"]
    assert snaps["taken"] >= snaps["hit"] > 0
    assert state["snapshots_held"] == snaps["taken"] - snaps["evicted"]
    assert state["rows_in_use"] >= state["snapshots_held"]
    assert state["copy_rows"] >= snaps["taken"] + snaps["hit"]
    spans = [s["attrs"] for t in tiny_longdoc.traces for s in t["spans"]
             if s["name"] == "decode"]
    matched = [a["snapshot_matched"] for a in spans
               if a.get("snapshot_matched")]
    assert matched and all(m % after["kv"]["page_size"] == 0
                           for m in matched)
    assert all(a.get("prefix_matched", 0) == a.get("snapshot_matched", 0)
               for a in spans)
    pairs = after["experts"]["pairs"]
    cfg = tiny_longdoc.model
    fed = sum(rounds["fed_tokens"].values())
    assert pairs["held"] + pairs["absent"] == (
        fed * cfg.experts.per_token * cfg.n_layers)


# ---- the trace readers on a made-up trace ------------------------------------

def made_up_trace():
    """Two width-1 rounds of 10 ms and one wide round of 40 ms, operation
    names as the compiled step programs have them (PR 38).  A width-1 round:
    the grouped kernel 2 ms, three state updates of 0.5 ms, a state gather
    loop of 0.4 ms whose body's 0.3 ms is nested in it; the wide round: the
    kernel 4 ms, a triangular solve of 3 ms, the scan's `while` of 9 ms with
    8 ms of matmuls nested, a projection of 5 ms that is NOT the rule's, a
    grouped matmul of the expert layer of 6 ms.
    After the first round the row-copy program runs 0.2 ms."""
    ms, ops, modules = 1e-3, [], []

    def op(text, t, dur):
        ops.append(Event(text, t, dur))

    for r in range(2):
        t0 = r * 20 * ms
        modules.append(Event("jit_step(11)", t0, 10 * ms))
        op("%grouped_paged_attention.1 = bf16[8,1,64,128]{3,2,1,0} "
           "custom-call(s32[8,192]{1,0} %add.1, s32[8]{0} %p.2)", t0, 2 * ms)
        for i in range(3):
            op(f"%multiply_add_fusion.{i} = f32[8,64,128,128]{{3,2,1,0:"
               "T(8,128)S(1)} fusion(%custom-call.9, %copy-done.2)",
               t0 + (2 + 0.5 * i) * ms, 0.5 * ms)
        op("%while.9 = (s32[]{:T(128)}, f32[99,64,128,128]{3,2,1,0}, "
           "f32[8,64,128,128]{3,2,1,0}) while(%tuple.2), condition=%c",
           t0 + 4 * ms, 0.4 * ms)
        op("%dynamic-update-slice_fusion.3 = f32[8,64,128,128]{3,2,1,0} "
           "fusion(%p.1, %p.2)", t0 + 4.05 * ms, 0.3 * ms)
        op("%fusion.77 = bf16[8,1,8192]{2,1,0} fusion(%p.3)", t0 + 5 * ms,
           1 * ms)
    modules.append(Event("jit_state_copy(5)", 11 * ms, 0.2 * ms))
    op("%fusion.1 = f32[3,33,64,128,128]{4,3,2,1,0} fusion(%p.0)", 11 * ms,
       0.2 * ms)
    t0 = 40 * ms
    modules.append(Event("jit_step(22)", t0, 40 * ms))
    op("%grouped_paged_attention.2 = bf16[8,256,64,128]{3,2,1,0} "
       "custom-call(s32[8,192]{1,0} %add.1, s32[8]{0} %p.2)", t0, 4 * ms)
    op("%custom-call.46 = f32[8,64,4,1,64,64]{1,4,5,3,2,0:T(8,128)} "
       "custom-call(%add_bitcast_fusion.2), custom_call_target=\"X\"",
       t0 + 4 * ms, 3 * ms)
    op("%while.6 = (s32[]{:T(128)}, f32[8,64,128,128]{3,2,1,0}, "
       "f32[4,8,64,64,128]{4,3,2,1,0}) while(%tuple.9), condition=%c",
       t0 + 8 * ms, 9 * ms)
    op("%fusion.88 = f32[8,64,64,128]{3,2,1,0} fusion(%p.4, %p.5)",
       t0 + 8.5 * ms, 8 * ms)
    op("%fusion.99 = f32[8,256,64,128]{3,2,1,0} fusion(%p.6)", t0 + 20 * ms,
       5 * ms)
    op("%ragged-dot.3 = bf16[16384,1280]{1,0} custom-call(%p.7, %p.8, %p.9)",
       t0 + 26 * ms, 6 * ms)
    return trace_reduce.reduce(
        [DeviceTrace("/device:TPU:0", sorted(ops, key=lambda e: e.start),
                     modules)],
        [Event(trace_reduce.WINDOW_SPAN, 0.0, 100 * ms)])


def solar_model():
    return types.SimpleNamespace(
        n_layers=4, n_heads=64, head_dim=128, kv_heads=8, dtype="bfloat16",
        experts=object(), latent=None,
        linear=types.SimpleNamespace(heads=64, k_dim=128, v_dim=128,
                                     conv_taps=4),
        mixer_kinds=lambda: ("full", "kda", "kda", "kda"))


def counters(n1, nw, lanes1, lanesw, fed, pages):
    return {"slots": 8, "kv": {"page_size": 128},
            "rounds": {"by_width": {"1": n1, "256": nw}, "count": n1 + nw,
                       "live_pages": pages,
                       "fed_tokens": {"prefill": fed, "decode": lanes1,
                                      "draft": 0}},
            "state": {"kda_rows": {"w1": lanes1, "wide": lanesw}}}


def test_trace_readers_on_a_made_up_trace():
    after = counters(100, 10, 600, 50, 10 * 1024, 110 * 400)
    run = Run(cell=None, chips=1, model=solar_model(),
              device_trace=made_up_trace(),
              peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
              counters={"before": counters(0, 0, 0, 0, 0, 0), "after": after})
    assert read("step_ms.sat", run) == pytest.approx(20.0)    # 10, 10, 40
    # width 1: 3 x 0.5 + the loop 0.4 (its body counted once, inside it)
    # = 1.9 a round; wide: 3 + 9 = 12
    assert read("kda_ms_per_step", run) == pytest.approx((2 * 1.9 + 12) / 3)
    assert read("state_copy_ms_per_round", run) == pytest.approx(0.2 / 3)
    # 6 active lanes a width-1 round: 6 x 2 x 13,025,280 B at 819 GB/s
    least = 2 * 6 * 13025280 / 819e9
    assert read("kda_step_roofline", run) == pytest.approx(
        100 * least / 1.9e-3)
    # 1,024 fed tokens and 5 lanes a wide round
    flops = 1024 * 3 * 64 * 163840 / 197e12
    moved = (2 * 5 * 13025280 + 1024 * 3 * 64 * 640 * 4) / 819e9
    assert moved > flops        # at this fill the rule is bound by bytes
    assert read("kda_chunk_roofline", run) == pytest.approx(
        100 * moved / 12e-3)
    # 400 live pages a round, 512 KB a page, against (2 + 2 + 4) / 3 ms
    assert read("gqa_kernel_roofline", run) == pytest.approx(
        100 * (400 * 524288 / 819e9) / (8e-3 / 3))
    for metric in ("kda_step_roofline", "kda_chunk_roofline",
                   "gqa_kernel_roofline"):
        assert 0 < read(metric, run) < 100
    # the wide round's one grouped matmul over the three launches
    assert read("expert_ms_per_step.longdoc", run) == pytest.approx(6 / 3)
    assert read("expert_ms_per_step.longdoc", run) == read(
        "expert_ms_per_step", run)


def test_a_wide_launch_cut_by_the_traces_start_still_gives_its_width():
    """The trace begins inside a wide launch, after its one grouped-query
    layer: that launch's first event has no kernel to read the width off.
    The accepted reader drops the program; the new readers read the width
    off the next launch and leave the cut one out."""
    from benchmark import readings, readings_kda

    whole = made_up_trace().devices[0]
    cut = Event("jit_step(22)", 30e-3, 5e-3)
    ops = sorted(whole.ops + [Event(
        "%fusion.99 = f32[8,256,64,128]{3,2,1,0} fusion(%p.6)", 31e-3,
        2e-3)], key=lambda e: e.start)
    modules = sorted(whole.modules + [cut], key=lambda e: e.start)
    run = Run(cell=None, chips=1, model=solar_model(),
              device_trace=trace_reduce.reduce(
                  [DeviceTrace("/device:TPU:0", ops, modules)],
                  [Event(trace_reduce.WINDOW_SPAN, 0.0, 100e-3)]),
              peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
              counters={"before": counters(0, 0, 0, 0, 0, 0),
                        "after": counters(100, 10, 600, 50, 10 * 1024,
                                          110 * 400)})
    assert sorted(readings.paged_programs(run)) == [1]
    found = readings_kda.paged_programs(run)
    assert sorted(found) == [1, 256]
    assert [e.start for e in found[256]] == [pytest.approx(40e-3)]
    assert read("kda_chunk_roofline", run) is not None
    assert read("expert_ms_per_step", run) == 0.0
    assert read("expert_ms_per_step.longdoc", run) == pytest.approx(6 / 3)


def test_the_rules_operations_are_told_from_the_projections():
    from benchmark import readings_kda

    found = readings_kda.pattern(8, 64, 128, 128)
    for result in ("f32[8,64,128,128]{3,2,1,0}", "f32[771,64,128,128]",
                   "f32[3,257,64,128,128]", "f32[8,64,4,64,128]{4,3,2,1,0}",
                   "f32[8,64,4,1,64,64]", "f32[4,8,64,64,128]",
                   "(s32[], f32[4,8,64,1,128]{4,3,2,1,0})",
                   "f32[8,64,64,128]"):
        assert found.search(result), result
    for result in ("f32[8,256,64,128]", "bf16[8,64,128,128]", "f32[8,64]",
                   "f32[8,256,24576]", "f32[8,4,64,64]", "f32[2048,320]",
                   "bf16[8,256,64,128]", "f32[8,64,128]"):
        assert not found.search(result), result


# `kda_chunk_roofline` has a reader and no entry: the traced 4 s of the cell's
# order hold no wide round, so no cell lists it (its docstring)
@pytest.mark.parametrize("metric", [*NEW, "kda_chunk_roofline"])
def test_a_run_of_another_family_or_of_the_parent_gives_nothing(metric):
    """GPT-2's and DeepSeek-V2's configurations have no recurrent layer and
    no grouped heads (and the parent's have not even the fields); their
    `stats()` have no state counts; no trace was taken, or one was."""
    assert len(NEW) == 6 and "kda_chunk_roofline" not in NEW
    for model in (types.SimpleNamespace(n_layers=36, n_heads=20,
                                        head_dim=64, latent=None,
                                        experts=None, linear=None,
                                        kv_heads=None),
                  types.SimpleNamespace(n_layers=36, n_heads=20)):
        for trace in (None, made_up_trace()):
            run = Run(cell=None, chips=1, model=model, device_trace=trace,
                      peaks={"hbm_bytes_per_s": 819e9,
                             "bf16_flops_per_s": 197e12},
                      counters={"before": {}, "after": {"slots": 16}},
                      traces=[{"spans": [{"name": "queue_wait",
                                          "dur_s": 0.1}]}])
            assert read(metric, run) is None
