"""The `sdar-30b-a3b-serve-6l` configuration and its cell: a `--tiny`
rehearsal of the whole run; the file against the catalog's published keys;
`correct` against three planted faults and the float8 control; the new
readers on a tiny run's counters, on a made-up trace whose sums are known, and
on runs that have nothing for them."""

import argparse
import ast
import copy
import dataclasses
import json
import time
import types

import numpy as np
import pytest

from benchmark import flops_sdar, spec, trace_reduce
from benchmark.observe import Run
from benchmark.trace_reduce import DeviceTrace, Event
from conftest import run_cell

CELL = "sdar-30b-a3b.blockgen-sat"
NAME = "sdar-30b-a3b-serve-6l"
FILE = spec.ROOT / "benchmark" / "configs" / f"{NAME}.json"
BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
NEW = [m["name"] for m in BENCH["per_layer"] if m["workloads"] == [CELL]]
# `config.json` of JetLM/SDAR-30B-A3B-Chat, the keys that give its shape
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


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


def test_the_file_keeps_every_published_key_but_the_two_reduced():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers",
                                "max_position_embeddings"]
    config = json.loads(FILE.read_text())
    differs = [k for k, v in PUBLISHED.items() if config.get(k) != v]
    assert sorted(differs) == sorted(entry["reduced"])
    assert config["num_hidden_layers"] >= 4 and "deployment" in config
    assert config["n_positions"] == config["max_position_embeddings"]
    # every inference the equations rest on is written down
    assert {"qk_norm", "rotary", "block_length", "mask_token_id", "logits",
            "generation", "schedule", "weights"} <= set(config["assumed"])
    serve = config["serve"]
    block = config["block_length"]
    assert serve["page_size"] % block == 0
    assert serve["prefill_chunk"] % block == 0
    assert block % serve["denoise_steps"] == 0
    assert serve["unmask"] == "static"
    assert config["mask_token_id"] < config["vocab_size"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "blockgen-sat", 1)


def test_the_mix_is_the_issues_to_the_letter():
    mix = json.loads((spec.ROOT / "benchmark" / "traffic"
                      / "blockgen-sat.json").read_text())
    assert mix["kind"] == "open_loop_requests"
    assert mix["prompt_tokens"] == {"median": 192, "sigma": 0.8, "min": 32,
                                    "max": 1024}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0, "min": 256,
                                    "max": 256}
    assert (mix["block_s"], mix["preroll_s"], mix["drain"]) == (
        10.0, 10.0, "cancel")
    arrivals = mix["rate_per_s"] * mix["block_s"]
    assert arrivals == int(arrivals)


def test_the_new_entries_list_what_the_issue_names():
    assert sorted(NEW) == sorted([
        "tokens_per_forward", "unmask_ms_per_step",
        "gqa_block_kernel_roofline", "expert_ms_per_step.blockgen",
        "expert_load_peak.blockgen"])
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == set(NEW) | {
        "serve_tokens_per_s", "idle_share.sat", "round_host_ms.sat",
        "lane_occupancy.sat", "feed_fill.sat", "step_ms.sat", "warmup_s",
        "build_trace_lower_s", "build_compile_s", "build_cache_misses"}
    layers = {m["name"]: m["layer"] for m in BENCH["per_layer"]}
    assert layers["tokens_per_forward"] == "LM scheduler"
    assert layers["gqa_block_kernel_roofline"] == "Paged attention kernel"
    assert spec.load_cell(CELL).end_to_end == ("serve_tokens_per_s",
                                               "setup_s")


def test_the_adapter_builds_what_the_programs_constructor_builds():
    from deeplearning4j_tpu.parallel import generation as gen
    from deeplearning4j_tpu.parallel import transformer as tfm

    config = spec._with_tiny(json.loads(FILE.read_text()), False)
    cfg = spec.adapter(config).program_config(config, "bfloat16", False)
    assert cfg == tfm.sdar_30b_a3b(layers=6, max_len=2048)
    assert cfg.experts.held == (0, 128) and cfg.block_length == 4
    assert cfg.mask_token == 151669 and cfg.grouped and not cfg.classic
    # the program's sizes are the yardstick's: a cached token 12,288 B
    assert gen.pool_token_bytes(cfg) == 12288 == flops_sdar.block_paged_bytes(
        1, 1, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers)


def test_the_reference_imports_nothing_of_the_program():
    path = spec.ROOT / "benchmark" / "reference" / "sdar.py"
    source = path.read_text()
    names = {n.module if isinstance(n, ast.ImportFrom) else a.name
             for n in ast.walk(ast.parse(source))
             if isinstance(n, (ast.Import, ast.ImportFrom))
             for a in n.names}
    assert not {n for n in names if n and n.startswith(
        ("deeplearning4j_tpu", "benchmark"))}
    assert 'default_matmul_precision("highest")' in source


def test_a_pages_bytes():
    # a page of 16 rows, both pools, six layers: 196,608 B
    assert flops_sdar.block_paged_bytes(1, 16, 4, 128, 6) == 196608.0


# ---- `correct`: a sound run, three planted faults, the control --------------

def tiny_run(plant=None):
    """The cell's tiny rehearsal through its driver, in process."""
    import jax

    cell = spec.load_cell(CELL, tiny=True)
    args = argparse.Namespace(seed=11, seconds=2.0, trace=0, tiny=True)
    run, checks, attempted, failed, _ = spec.driver(cell.config).run(
        cell, args, time.perf_counter(), jax.devices()[:1])
    assert attempted > 0 and failed == 0
    return run, checks


def over(checks):
    return sorted(name for name, value, limit in checks if value > limit)


@pytest.fixture(scope="module")
def tiny_blockgen():
    run, checks = tiny_run()
    assert over(checks) == []
    return run


def test_the_commit_pass_left_out_reads_false(monkeypatch):
    """Later blocks read K/V made from mask ids: a lane whose block has no
    masked column left feeds nothing, and the host commits all the same."""
    from deeplearning4j_tpu.parallel.generation import pool_names
    from deeplearning4j_tpu.serving.lm import ContinuousLMServer

    start = ContinuousLMServer._start_locked

    def start_and_plant(self):
        start(self)
        step, at = self._step, len(pool_names(self.cfg)) + 2

        def skipping(params, *args):
            n_feed = np.array(args[at])
            for i, s in enumerate(self._slots):
                # a decoding lane that feeds with no block of its own left:
                # this dispatch is its commit pass
                if (s.active and s.fed >= s.req.prefill_len
                        and s.block is None):
                    n_feed[i] = 0
            return step(params, *args[:at], n_feed, *args[at + 1:])

        self._step = skipping

    monkeypatch.setattr(ContinuousLMServer, "_start_locked", start_and_plant)
    _, checks = tiny_run()
    assert "served_logit_gap_max" in over(checks)


def test_the_mask_rule_left_causal_reads_false(monkeypatch):
    from deeplearning4j_tpu.parallel import generation as gen

    attend = gen._grouped_paged_attn

    def causal(*args, cfg=None, **kw):
        return attend(*args, cfg=dataclasses.replace(cfg, block_length=1),
                      **kw)

    gen._compiled_block_step.cache_clear()
    monkeypatch.setattr(gen, "_grouped_paged_attn", causal)
    try:
        _, checks = tiny_run()
    finally:
        gen._compiled_block_step.cache_clear()
    assert "served_logit_gap_max" in over(checks)


def test_a_wrong_unmasking_rule_reads_false(monkeypatch):
    """The program unmasks by position, the two lowest masked columns a
    step, where the schedule wants the two most confident: every token is
    still the best at its own state, so the first number holds, and the
    share of steps off the reference's choice does not."""
    from deeplearning4j_tpu.parallel import generation as gen

    import jax.numpy as jnp

    unmask = gen.block_unmask

    def by_position(logits, tokens, known, quota, tau, mask_token):
        width = tokens.shape[1]
        best, _ = unmask(logits, tokens, jnp.zeros_like(known),
                         jnp.full_like(quota, width), tau, mask_token)
        nth = jnp.cumsum(~known, axis=1)    # a masked column's place
        take = ~known & (nth <= quota[:, None])
        return jnp.where(take, best, tokens), known | take

    gen._compiled_block_step.cache_clear()
    monkeypatch.setattr(gen, "block_unmask", by_position)
    try:
        _, checks = tiny_run()
    finally:
        gen._compiled_block_step.cache_clear()
    assert over(checks) == ["unmask_steps_off_share"]


def _checked(run, requests, control=None):
    """The driver's comparison on `requests` of the tiny run."""
    cell = spec.load_cell(CELL, tiny=True)
    adapter = spec.adapter(cell.config)
    cfg = adapter.program_config(cell.config, "float32", remat=False)
    params = adapter.make_params(cfg, 11, "float32")
    driver = spec.driver(cell.config)
    return cfg, params, driver, driver.check_against_reference(
        cell.config, cfg, params, requests, 11, 10, control)


def test_one_unmask_step_off_by_one_reads_false(tiny_blockgen):
    requests = copy.deepcopy([r for r in tiny_blockgen.requests
                              if r.status == "ok"])
    assert over(_checked(tiny_blockgen, requests)[3]) == []
    for r in requests:
        steps = r.decode["unmask_steps"]
        k = steps.index(1)
        steps[k] = 0            # a token said to be unmasked a step early
    found = over(_checked(tiny_blockgen, requests)[3])
    assert found and set(found) <= {"served_logit_gap_max",
                                    "unmask_steps_off_share"}
    for r in requests:          # and one whose steps are missing
        r.decode = None
    assert over(_checked(tiny_blockgen, requests)[3]) == [
        "answers_without_their_steps"]


def test_the_float8_control_fails_a_limit_at_toy_size(tiny_blockgen):
    cell = spec.load_cell(CELL, tiny=True)
    requests = [r for r in tiny_blockgen.requests if r.status == "ok"]
    cfg, params, driver, _ = _checked(tiny_blockgen, requests[:1])
    limit = cell.config["check"]
    tokens, choices = [], []
    for r in requests:
        sound = driver.replayed_gaps(cell.config, cfg, params, r, 12, 176)
        assert max(sound[0]) == 0.0 == max(sound[1])
        low = driver.replayed_gaps(cell.config, cfg, params, r, 12, 176,
                                   "fp8")
        tokens += low[0]
        choices += low[1]
    # over the run's answers (a short one alone may keep every choice)
    assert (max(tokens) > limit["served_logit_gap_max"]
            or np.mean(np.asarray(choices) > 0)
            > limit["unmask_steps_off_share"])
    # the configuration's own precision costs less than the one below it
    same = driver.replayed_gaps(cell.config, cfg, params, requests[0], 12,
                                176, "bf16")
    assert max(same[0]) < max(tokens)
    with pytest.raises(ValueError, match="unknown control"):
        driver.replayed_gaps(cell.config, cfg, params, requests[0], 12, 176,
                             "fp4")


# ---- the readers -------------------------------------------------------------

@pytest.mark.parametrize("metric", [
    "feed_fill.sat", "lane_occupancy.sat", "round_host_ms.sat", "warmup_s",
    "tokens_per_forward", "expert_load_peak.blockgen"])
def test_counter_readers_find_their_numbers(tiny_blockgen, metric):
    value = read(metric, tiny_blockgen)
    assert value is not None and value > 0.0
    if "%" == spec.reader("layer_metrics", metric).UNIT:
        assert value <= 100.0


def test_the_programs_new_counters_add_up(tiny_blockgen):
    after, before = (tiny_blockgen.counters[k] for k in ("after", "before"))
    blocks = after["blocks"]
    assert blocks["block_length"] == 4 and blocks["denoise_steps"] == 2
    rounds = blocks["rounds"]
    # every block: at most two denoise rounds, then one commit pass
    assert rounds["commit"] <= rounds["denoise"] <= 2 * rounds["commit"] + 8
    assert blocks["committed"] == rounds["commit"]
    fed = blocks["positions"]
    assert fed["masked"] + fed["known"] == 4 * (rounds["denoise"]
                                                + rounds["commit"])
    assert fed["masked"] >= blocks["unmasked"] > 0
    assert after["rounds"]["fed_tokens"]["decode"] == (fed["masked"]
                                                       + fed["known"])
    # ten tokens an answer in three or four blocks: between 10/12 and 10/9
    assert 0.8 < read("tokens_per_forward", tiny_blockgen) < 4 / 3
    spans = [s["attrs"] for t in tiny_blockgen.traces for s in t["spans"]
             if s["name"] == "decode" and s["attrs"].get("generated")]
    assert spans
    for a in spans:
        assert len(a["unmask_steps"]) == a["generated"] == 10
        assert set(a["unmask_steps"]) <= {0, 1}
        assert a["commit_rounds"] == a["blocks"] <= a["denoise_rounds"]
        assert (a["prompt_tokens"] + 10 + len(a["surplus"])) % 4 == 0
    assert set(after["rounds"]["by_width"]) <= {"4", "16"}
    assert "w4" in after["kv"]["write_path"]


def made_up_trace():
    """Two narrow rounds of 12 ms and one wide round of 20 ms, operation
    names as the compiled step programs have them (compile-only for v5e,
    PR 42).  A narrow round: six grouped kernels of 0.5 ms, a grouped matmul
    of the expert layer of 1 ms, the head 0.8 ms, the float32 softmax 0.3 ms
    with 0.1 ms nested in it, an embedding gather of 0.2 ms that is NOT the
    tail's; the wide round: six kernels of 1 ms, grouped matmuls 4 ms, the
    head 0.8 ms."""
    ms, ops, modules = 1e-3, [], []

    def op(text, t, dur):
        ops.append(Event(text, t, dur))

    def kernels(t0, width, each):
        for i in range(6):
            op(f"%grouped_paged_attention.{i} = bf16[32,{width},32,128]"
               "{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[32,128]{1,0} "
               "%add.1, s32[32]{0} %p.2)", t0 + i * each, each)

    for r in range(2):
        t0 = r * 30 * ms
        modules.append(Event("jit_step(11)", t0, 12 * ms))
        kernels(t0, 4, 0.5 * ms)
        op("%ragged-dot.3 = bf16[1024,768]{1,0} custom-call(%p.7, %p.8)",
           t0 + 4 * ms, 1 * ms)
        op("%gather.1 = bf16[32,4,2048]{2,1,0} gather(bf16[151936,2048]{1,0}"
           " %p.0, s32[32,4]{1,0} %p.1)", t0 + 5 * ms, 0.2 * ms)
        op("%fusion.5 = bf16[32,4,151936]{2,0,1} fusion(bf16[32,4,2048]{2,1,"
           "0} %p.3, bf16[2048,151936]{1,0} %p.4)", t0 + 6 * ms, 0.8 * ms)
        op("%fusion.6 = f32[32,4]{1,0} fusion(bf16[32,4,151936]{2,0,1} "
           "%fusion.5)", t0 + 7 * ms, 0.3 * ms)
        op("%reduce.2 = f32[32,4]{1,0} reduce(f32[32,4,151936]{2,0,1} %c.1)",
           t0 + 7.1 * ms, 0.1 * ms)
    t0 = 70 * ms
    modules.append(Event("jit_step(22)", t0, 20 * ms))
    kernels(t0, 64, 1 * ms)
    op("%ragged-dot.4 = bf16[16384,768]{1,0} custom-call(%p.7, %p.8)",
       t0 + 7 * ms, 4 * ms)
    op("%fusion.9 = bf16[32,4,151936]{2,0,1} fusion(bf16[32,4,2048]{2,1,0} "
       "%p.3, bf16[2048,151936]{1,0} %p.4)", t0 + 12 * ms, 0.8 * ms)
    return trace_reduce.reduce(
        [DeviceTrace("/device:TPU:0", sorted(ops, key=lambda e: e.start),
                     modules)],
        [Event(trace_reduce.WINDOW_SPAN, 0.0, 100 * ms)])


def sdar_model():
    return types.SimpleNamespace(
        n_layers=6, n_heads=32, head_dim=128, kv_heads=4, n_kv_heads=4,
        dtype="bfloat16", experts=object(), latent=None, linear=None,
        block_length=4, vocab_size=151936)


def counters(rounds, pages):
    return {"slots": 32, "kv": {"page_size": 16},
            "rounds": {"count": rounds, "live_pages": pages}}


def test_trace_readers_on_a_made_up_trace():
    run = Run(cell=None, chips=1, model=sdar_model(),
              device_trace=made_up_trace(),
              peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
              counters={"before": counters(0, 0),
                        "after": counters(100, 100 * 1000)})
    assert read("step_ms.sat", run) == pytest.approx((12 + 12 + 20) / 3)
    # the head and the softmax (its nested reduce counted once), not the
    # embedding's gather: 0.8 + 0.3 a narrow round, 0.8 the wide one
    assert read("unmask_ms_per_step", run) == pytest.approx(
        (2 * 1.1 + 0.8) / 3)
    assert read("expert_ms_per_step.blockgen", run) == pytest.approx(
        (1 + 1 + 4) / 3)
    # 1,000 live pages a round, 196,608 B a page, against (3 + 3 + 6) / 3 ms
    share = read("gqa_block_kernel_roofline", run)
    assert share == pytest.approx(
        100 * (1000 * 196608 / 819e9) / (12e-3 / 3))
    assert 0 < share < 100


def test_the_tails_operations_are_told_from_the_embeddings():
    found = spec.reader("layer_metrics", "unmask_ms_per_step").pattern(151936)
    for name in ("%f = f32[32,4,151936]{2,0,1} convert(%p)",
                 "%r = f32[32,4]{1,0} reduce(f32[32,4,151936]{2,0,1} %c)",
                 "%d = bf16[32,4,151936] fusion(%a, bf16[2048,151936]{1,0})",
                 "%i = pred[151936]{0} compare(%iota, %b)"):
        assert found.search(name), name
    for name in ("%g = bf16[32,4,2048]{2,1,0} gather(bf16[151936,2048]{1,0})",
                 "%x = bf16[1519360,768] fusion(%p)",
                 "%y = f32[32,4]{1,0} fusion(f32[32,4,2048]{2,1,0} %q)"):
        assert not found.search(name), name


@pytest.mark.parametrize("metric", NEW)
def test_a_run_of_another_family_or_of_the_parent_gives_nothing(metric):
    """GPT-2's, DeepSeek-V2's and Solar-Open2's configurations have no block
    length over 1 (and the parent's have not even the field); their
    `stats()` have no block counts; no trace was taken, or one was."""
    assert len(NEW) == 5
    for model in (types.SimpleNamespace(n_layers=36, n_heads=20, head_dim=64,
                                        latent=None, experts=None,
                                        linear=None, kv_heads=None,
                                        block_length=1, vocab_size=50304),
                  types.SimpleNamespace(n_layers=4, n_heads=64, kv_heads=8,
                                        experts=object(), vocab_size=24576)):
        for trace in (None, made_up_trace()):
            run = Run(cell=None, chips=1, model=model, device_trace=trace,
                      peaks={"hbm_bytes_per_s": 819e9,
                             "bf16_flops_per_s": 197e12},
                      counters={"before": {"tokens": 0},
                                "after": {"slots": 16, "tokens": 9}},
                      traces=[{"spans": [{"name": "queue_wait",
                                          "dur_s": 0.1}]}])
            assert read(metric, run) is None
