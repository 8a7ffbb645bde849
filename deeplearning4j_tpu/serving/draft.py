"""Drafter plane for speculative multi-token decoding (ISSUE-13).

The LM pool's verify side (`parallel.generation.make_spec_step`) scores
a lane's drafted chunk in one wide dispatch and accepts/rolls back
IN-JIT; this module owns the other half — where the drafts come from.
A `Drafter` proposes up to `budget` continuation tokens per lane per
round from the lane's committed history (prompt + generated so far).
Draft QUALITY only moves throughput: the verify step's accept rule
guarantees greedy output is byte-identical to 1-token decode whatever
the drafter proposes, so a drafter can be wrong, cheap, and simple.

Two stdlib-cheap implementations:

- `NgramDrafter` — n-gram / prompt-lookup drafting: suffix-match the
  lane's recent tokens against its OWN earlier history and propose the
  continuation that followed the most recent prior occurrence.  Pure
  host Python, ZERO extra device programs — the free drafter, and
  strong on exactly the traffic continuous batching concentrates
  (shared system prompts, template continuations, greedy decode loops).
- `ModelDrafter` — a small zoo model (a tiny transformer config, or the
  target model itself for self-speculation tests) decoding greedily
  over a page pool of its OWN through the `make_paged_step` program the
  target serves from, each lane on a fixed run of pages.
  Costs ~(catch_up + budget) 1-wide draft-model dispatches per round —
  worth it only when the draft model is much smaller than the target
  (docs/performance.md "The speculative decode cost model").

Threading: a drafter instance is owned by the LM pool's WORKER THREAD
(the single mutator, same contract as `serving/paged.py`); `propose`
is called from the worker's lock-free dispatch path only.
"""

from __future__ import annotations

from typing import List, Optional, Protocol, Sequence, runtime_checkable


# tokens a page of the draft model's own pool (the serving pool's default)
_PAGE_SIZE = 16


@runtime_checkable
class Drafter(Protocol):
    """One round of proposals for the whole slot pool.

    `histories[i]` is lane i's committed tokens (prompt + generated), or
    None for lanes that must not be drafted for (inactive, sampling, or
    out of budget); `budgets[i]` caps lane i's proposal length.  Returns
    one proposal list per lane — possibly empty, never longer than the
    budget, and None-lanes always get [].
    """

    name: str

    def propose(self, histories: Sequence[Optional[Sequence[int]]],
                budgets: Sequence[int]) -> List[List[int]]:
        ...  # pragma: no cover — protocol signature only

    def reset(self) -> None:
        """Forget all lane state (the pool was rebuilt)."""
        ...  # pragma: no cover — protocol signature only

    def compiled_programs(self) -> int:
        """Device programs this drafter adds to the serving ladder."""
        ...  # pragma: no cover — protocol signature only


class NgramDrafter:
    """Prompt-lookup / n-gram drafting over each lane's own history.

    For the longest n in [min_ngram, max_ngram] whose history suffix
    re-occurs EARLIER in the history, propose the tokens that followed
    the most recent prior occurrence, up to the budget.  Degenerate
    inputs (empty history, history shorter than min_ngram, no prior
    occurrence, nothing after the occurrence) propose zero tokens —
    the lane falls back to plain 1-token decode for that round.
    """

    name = "ngram"

    def __init__(self, max_ngram: int = 4, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(f"need 1 <= min_ngram <= max_ngram, got "
                             f"{min_ngram}..{max_ngram}")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)

    def _propose_one(self, hist: Sequence[int], budget: int) -> List[int]:
        h = list(hist)
        n_hist = len(h)
        for n in range(min(self.max_ngram, n_hist - 1),
                       self.min_ngram - 1, -1):
            suffix = h[n_hist - n:]
            # most recent PRIOR occurrence whose continuation fills the
            # budget; an occurrence too close to the end only yields a
            # truncated continuation (for a periodic tail — greedy
            # decode loops, templated text — the nearest match is
            # always the overlapping one), so keep scanning and fall
            # back to the longest continuation seen
            best: List[int] = []
            for i in range(n_hist - n - 1, -1, -1):
                if h[i] == suffix[0] and h[i:i + n] == suffix:
                    cont = h[i + n:i + n + budget]
                    if len(cont) == budget:
                        return cont
                    if len(cont) > len(best):
                        best = cont
            if best:
                return best
        return []

    def propose(self, histories, budgets) -> List[List[int]]:
        out: List[List[int]] = []
        for hist, budget in zip(histories, budgets):
            if hist is None or budget < 1:
                out.append([])
            else:
                out.append(self._propose_one(hist, int(budget)))
        return out

    def reset(self) -> None:
        pass                        # stateless — history rides each call

    def compiled_programs(self) -> int:
        return 0


class ModelDrafter:
    """Small-model drafting: a draft LM greedily rolls out `budget`
    tokens per lane over a page pool of its OWN (one `make_paged_step`
    program, 1-wide dispatches).  The block table is the identity: lane
    `i` owns pages `1 + i*mp .. (i+1)*mp` for good (page 0 is the null
    page), so there is no allocator and no radix tree here.

    Lane state self-heals from the histories handed to `propose`: each
    call rewinds a lane to the longest common prefix of what was fed
    and the new committed history (rejected drafts and freed/reused
    slots fall out naturally — the causal mask hides everything past
    `pos`, so rewinding is a host-side counter move),
    teacher-forces the missing suffix, then rolls out proposals.  Lanes
    mid-teacher-forcing idle by RE-FEEDING their last token at its own
    position — k/v at a position are a pure function of (token,
    position, earlier history), so the re-write is byte-idempotent.
    """

    name = "model"

    def __init__(self, cfg, params, slots: int, target_vocab: int = 0,
                 target_max_len: int = 0):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if target_vocab and cfg.vocab_size < target_vocab:
            raise ValueError(
                f"draft model vocab ({cfg.vocab_size}) smaller than the "
                f"target's ({target_vocab}): drafts could never cover "
                f"the target's tokens")
        if target_max_len and cfg.max_len < target_max_len:
            raise ValueError(
                f"draft model max_len ({cfg.max_len}) smaller than the "
                f"target's ({target_max_len}): a lane's history would "
                f"outgrow the draft cache mid-request")
        self.cfg = cfg
        self.params = params
        self.n_slots = int(slots)
        self._step = None
        self._cache = None          # the donated pool buffers
        self._table = None          # [slots, mp] identity block table
        self._fed: List[List[int]] = [[] for _ in range(self.n_slots)]

    # ---- device plumbing --------------------------------------------------

    def _ensure_started(self) -> None:
        if self._step is not None:
            return
        import numpy as np

        from deeplearning4j_tpu.parallel.generation import (
            init_paged_cache,
            make_paged_step,
            pages_per_seq,
        )

        mp = pages_per_seq(self.cfg, _PAGE_SIZE)
        total = self.n_slots * mp + 1
        self._step = make_paged_step(self.cfg, total, _PAGE_SIZE, 1)
        self._cache = tuple(
            init_paged_cache(self.cfg, total, _PAGE_SIZE).values())
        self._table = 1 + np.arange(self.n_slots * mp, dtype=np.int32
                                    ).reshape(self.n_slots, mp)

    def warmup(self) -> None:
        """Compile the draft-model program before traffic (the LM
        pool's `warmup()` calls this so the zero-compile-after-warmup
        contract covers the drafter too)."""
        import numpy as np

        self._ensure_started()
        zi = np.zeros((self.n_slots,), np.int32)
        # nothing fed: only the null page is written, live lanes keep theirs
        self._dispatch(zi, zi, zi)

    def _dispatch(self, tokens, pos, n_feed):
        """One 1-wide draft-model step; returns [B] greedy next tokens.
        Lanes with `n_feed` 0 write the null page.  Sampling inputs are
        all-zero: temperature 0 = argmax rows."""
        import numpy as np

        from deeplearning4j_tpu.obs.compilewatch import compile_scope

        zi = np.zeros((self.n_slots,), np.int32)
        zf = np.zeros((self.n_slots,), np.float32)
        with compile_scope("lm:draft"):
            nxt, *pools = self._step(
                self.params, *self._cache, self._table, pos, n_feed,
                tokens[:, None], zf, zi, zi)
        self._cache = tuple(pools)
        return np.asarray(nxt)[:self.n_slots]

    # ---- drafting ---------------------------------------------------------

    def propose(self, histories, budgets) -> List[List[int]]:
        import numpy as np

        if len(histories) != self.n_slots:
            raise ValueError(f"expected {self.n_slots} lane histories, "
                             f"got {len(histories)}")
        budgets = [int(b) for b in budgets]
        if not any(b > 0 and h is not None
                   for h, b in zip(histories, budgets)):
            return [[] for _ in histories]
        self._ensure_started()
        pending: List[List[int]] = []
        for i, hist in enumerate(histories):
            if hist is None:
                pending.append([])
                continue
            h = [int(t) for t in hist]
            cp = 0
            fed = self._fed[i]
            for a, b in zip(fed, h):
                if a != b:
                    break
                cp += 1
            self._fed[i] = fed[:cp]        # rewind = pointer move
            pending.append(h[cp:])
        # a history the draft cache cannot hold (custom construction
        # bypassing the factory's max_len validation) must not scatter
        # at clamped positions and silently corrupt the cache: the lane
        # simply sits this round out (no proposal is always safe)
        for i in range(self.n_slots):
            if (histories[i] is not None
                    and len(self._fed[i]) + len(pending[i])
                    > self.cfg.max_len):
                pending[i] = []
                budgets[i] = 0
        if not any(b > 0 and h is not None
                   for h, b in zip(histories, budgets)):
            return [[] for _ in histories]
        # teacher-force the missing suffixes in lockstep; at least one
        # round always runs so every drafted lane's last committed
        # token has been (re-)fed and its next-token prediction is live
        rounds = max(1, max(len(p) for p in pending))
        pred = None
        for _ in range(rounds):
            tokens = np.zeros((self.n_slots,), np.int32)
            pos = np.zeros((self.n_slots,), np.int32)
            for i in range(self.n_slots):
                if pending[i]:
                    tokens[i] = pending[i].pop(0)
                    pos[i] = len(self._fed[i])
                    self._fed[i].append(int(tokens[i]))
                elif self._fed[i]:             # idle: byte-idempotent re-feed
                    tokens[i] = self._fed[i][-1]
                    pos[i] = len(self._fed[i]) - 1
            pred = self._dispatch(tokens, pos, self._feeding())
        # greedy rollout: feed each round's prediction back in
        out: List[List[int]] = [[] for _ in range(self.n_slots)]
        k_max = max(budgets)
        for t in range(k_max):
            for i in range(self.n_slots):
                if (histories[i] is not None and self._fed[i]
                        and t < budgets[i]):
                    out[i].append(int(pred[i]))
            if t + 1 >= k_max:
                break
            tokens = np.zeros((self.n_slots,), np.int32)
            pos = np.zeros((self.n_slots,), np.int32)
            for i in range(self.n_slots):
                if (histories[i] is not None and self._fed[i]
                        and t + 1 < budgets[i]
                        and len(self._fed[i]) < self.cfg.max_len):
                    tokens[i] = pred[i]
                    pos[i] = len(self._fed[i])
                    self._fed[i].append(int(pred[i]))
                elif self._fed[i]:
                    tokens[i] = self._fed[i][-1]
                    pos[i] = len(self._fed[i]) - 1
            pred = self._dispatch(tokens, pos, self._feeding())
        return [p[:b] for p, b in zip(out, budgets)]

    def _feeding(self):
        """n_feed of a dispatch: every lane with a history feeds one
        token (its next, or its last again), the rest none."""
        import numpy as np

        return np.asarray([1 if f else 0 for f in self._fed], np.int32)

    def reset(self) -> None:
        self._fed = [[] for _ in range(self.n_slots)]

    def compiled_programs(self) -> int:
        return 1


def make_drafter(mode: str, cfg, params, slots: int,
                 draft_model=None) -> Optional[Drafter]:
    """The LM pool's drafter factory: `mode` in {"off", "ngram",
    "model"}.  For "model", `draft_model` is an optional (cfg, params)
    pair — default is SELF-speculation against the target's own
    weights (100% greedy accept; useful for parity tests and wiring
    validation, not a throughput win — see docs/performance.md)."""
    if mode == "off":
        return None
    if mode == "ngram":
        return NgramDrafter()
    if mode == "model":
        d_cfg, d_params = (draft_model if draft_model is not None
                           else (cfg, params))
        return ModelDrafter(d_cfg, d_params, slots,
                            target_vocab=cfg.vocab_size,
                            target_max_len=cfg.max_len)
    raise ValueError(
        f"speculate must be 'off', 'ngram' or 'model', got {mode!r}")


__all__ = ["Drafter", "ModelDrafter", "NgramDrafter", "make_drafter"]
