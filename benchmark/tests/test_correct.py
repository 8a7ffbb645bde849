"""`correct` comes out false when it must: with the timed path broken
underneath (the look for a chip skipped, the rest of a run driven), and for
the control, the plain reference computed in 8 bits in the program's place,
at a size a test run can hold."""

import argparse
import time

import pytest

from benchmark import spec


def drive(cell_name, seed=3, seconds=1.5, control=None):
    """The rest of a run after the look for a chip, on the CPU's device.
    -> {number: (value, limit)}"""
    import jax

    cell = spec.load_cell(cell_name, tiny=True)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, tiny=True,
                              control=control)
    _, checks, attempted, _, _ = spec.driver(cell.config).run(
        cell, args, time.perf_counter(), jax.devices()[:cell.chips])
    assert attempted > 0
    return {name: (value, limit) for name, value, limit in checks}


def wrong(checks):
    return sorted(n for n, (value, limit) in checks.items()
                  if not value <= limit)


@pytest.mark.parametrize("cell", ["gpt2-large.chat", "gpt2-medium.train"])
def test_sound_runs_are_correct(cell):
    assert wrong(drive(cell)) == []


def test_a_token_altered_where_it_is_produced_is_seen(monkeypatch):
    from deeplearning4j_tpu.serving import lm

    commit = lm.ContinuousLMServer._commit_tokens

    def altered(self, slot, toks):
        # every fifth committed token of a lane is another token
        if len(slot.generated) % 5 == 4:
            toks = [(toks[0] + 1) % self.cfg.vocab_size, *toks[1:]]
        return commit(self, slot, toks)

    monkeypatch.setattr(lm.ContinuousLMServer, "_commit_tokens", altered)
    assert "served_logit_gap_max" in wrong(drive("gpt2-large.chat"))


def test_a_step_that_returns_its_state_unchanged_is_seen(monkeypatch):
    import jax

    from deeplearning4j_tpu.parallel import hybrid

    make = hybrid.make_accum_train_step

    def frozen(*a, **kw):
        step, init = make(*a, **kw)

        def same(params, opt, tokens, targets):
            # the real step donates its arguments: give it copies
            copy = jax.tree_util.tree_map(lambda x: x + 0, (params, opt))
            _, new_opt, loss = step(*copy, tokens, targets)
            return params, new_opt, loss

        return same, init

    monkeypatch.setattr(hybrid, "make_accum_train_step", frozen)
    assert "change_norm_gap_worst_leaf" in wrong(drive("gpt2-medium.train"))


def test_a_part_of_the_batch_left_out_is_seen(monkeypatch):
    from deeplearning4j_tpu.parallel import hybrid

    make = hybrid.make_accum_train_step

    def partial(*a, **kw):
        step, init = make(*a, **kw)

        def half(params, opt, tokens, targets):
            n = tokens.shape[0] // 2
            return step(params, opt, tokens.at[n:].set(tokens[:n]),
                        targets.at[n:].set(targets[:n]))

        return half, init

    monkeypatch.setattr(hybrid, "make_accum_train_step", partial)
    # at the toy size the loss sees it; at the cell's size, where the
    # program's bfloat16 loss is coarse, the gradient norms do (PERF.md)
    found = wrong(drive("gpt2-medium.train"))
    assert "loss_gap_max" in found
    assert "first_grad_norm_gap_worst_leaf" in found


@pytest.mark.parametrize("cell", ["gpt2-large.chat", "gpt2-medium.train"])
def test_the_control_is_not_correct(capsys, cell):
    """The reference in float8, in the program's place over the program's own
    inputs: its numbers pass the limits a sound run is held to.  (In 8-bit
    integers with a scale a row it is as close to float32 as the bfloat16
    program is, on the chip too: PERF.md section 2.)"""
    import json

    limits = drive(cell, seconds=3.0, control="fp8")
    lines = [json.loads(line.split(": ", 1)[1])
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("benchmark control: ")]
    assert lines and lines[-1]["precision"] == "fp8"
    over = [n for n, (_, limit) in limits.items()
            if n in lines[-1] and lines[-1][n] > limit]
    assert over, (cell, lines[-1])
