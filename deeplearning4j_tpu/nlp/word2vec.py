"""Word2Vec: skip-gram with hierarchical softmax and/or negative sampling,
dense-batched for TPU.

Parity: reference `models/word2vec/Word2Vec.java:59` (fit():103 — vocab
build → Huffman → training loop; `skipGram():319`; `iterate():342`) and the
HS/NEG inner loop `InMemoryLookupTable.iterateSample:192` with its expTable
sigmoid LUT, unigram^0.75 negative table, and linear learning-rate decay
floored at minLearningRate.

TPU-first re-design (SURVEY §7 hard part #1): the reference trains via
sparse per-pair saxpy updates, racy across a thread pool (Hogwild). Here:

- the host encodes sentences to int32 arrays once, then per epoch emits
  skip-gram (input, target) pairs with the word2vec dynamic-window trick,
  packed into fixed-size batches (static shapes → one XLA program);
- ONE jitted step evaluates the whole batch: embedding gathers, a [B,L]
  batched dot against the Huffman path rows (HS) and/or [B,K] negatives
  gathered from the unigram table, exact `log_sigmoid` instead of the
  1000-entry LUT, masked sum;
- gradients for syn0/syn1 are hand-derived for the TOUCHED rows only
  (the reference's per-pair saxpy math, batched) and applied as
  scatter-adds: O(B·D) work per step, never a dense O(V·D) gradient
  table, so vocabulary size costs memory, not step time;
- Hogwild's lock-free parallelism (`Word2Vec.java:145-258` thread pool
  over shared syn0, `InMemoryLookupTable.java:192`) maps to data-parallel
  batch sharding: pass ``mesh=`` and each step shard_maps the pair batch
  over the mesh's data axis, all_gathers the sparse (row, delta) pairs
  over ICI (O(B·D) comms, not a dense psum), and applies one identical
  scatter per replica — *more* synchronous than the reference's racy
  updates, not less, and bit-stable across device counts up to float
  reduction order.  ``mesh=None`` is the single-device case with
  identical numerics.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.parallel.mesh import (
    round_batch_to_mesh,
    sparse_allgather_step,
)

from deeplearning4j_tpu.nlp.tokenization import (
    DefaultTokenizerFactory,
    TokenizerFactory,
)
from deeplearning4j_tpu.nlp.vocab import (
    Huffman,
    VocabCache,
    build_negative_table,
)
from deeplearning4j_tpu.nlp.word_vectors import WordVectors


def _log_sigmoid(x):
    # Stable log sigmoid; replaces the reference's clipped expTable LUT
    # (InMemoryLookupTable.java:173-177, MAX_EXP=6).
    return -jax.nn.softplus(-x)


# Reference MAX_EXP (InMemoryLookupTable.java): in the HIERARCHICAL
# SOFTMAX loop, pairs whose dot saturates (|dot| >= 6) contribute NO
# update — `iterateSample:214` skips them ("continue").  Besides parity,
# this is load-bearing for stability: a batched step accumulates
# hundreds of same-row contributions (e.g. doc labels in
# ParagraphVectors), and without the skip a badly-placed high-norm row
# feeds back |g|~1 updates and diverges geometrically; the skip freezes
# saturated pairs exactly as the reference does.  (The NEG loop is
# different — see _build_neg_step.)
MAX_EXP = 6.0

# Pairs staged on device per chunk during fit() (see the fit loop): the
# bound keeps device memory O(chunk) on huge corpora while still moving
# data to the device outside the hot loop.
STAGE_PAIRS = 1_048_576


class Word2Vec(WordVectors):
    """Skip-gram word embeddings (reference Word2Vec.java defaults:
    layerSize 100, window 5, alpha .025, minLearningRate 1e-2*alpha,
    negative sampling off → hierarchical softmax on)."""

    def __init__(self,
                 vector_length: int = 100,
                 window: int = 5,
                 min_word_frequency: int = 1,
                 learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4,
                 negative: int = 0,
                 subsample: float = 0.0,
                 batch_size: int = 2048,
                 epochs: int = 1,
                 seed: int = 42,
                 tokenizer_factory: Optional[TokenizerFactory] = None,
                 mesh=None):
        self.vector_length = vector_length
        self.window = window
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = negative
        self.subsample = subsample
        self.mesh = mesh  # jax.sharding.Mesh: shard pairs over its 1st axis
        if mesh is not None:
            batch_size = round_batch_to_mesh(batch_size, mesh)
        self.batch_size = batch_size
        self.epochs = epochs
        self.seed = seed
        self.tokenizer = tokenizer_factory or DefaultTokenizerFactory()
        vocab = VocabCache(min_word_frequency=min_word_frequency)
        super().__init__(vocab, np.zeros((0, vector_length), np.float32))
        self.syn1: Optional[np.ndarray] = None      # HS inner nodes
        self.syn1neg: Optional[np.ndarray] = None   # NEG output vectors
        self._hs = None  # (points, codes, lengths) device arrays
        self._neg_table = None
        self._step = None  # jitted train step, built in reset_weights

    # ------------------------------------------------------------------
    # vocab + weights

    def _sentences_to_tokens(self, sentences) -> List[List[str]]:
        out = []
        for s in sentences:
            out.append(self.tokenizer.tokenize(s) if isinstance(s, str)
                       else list(s))
        return out

    def build_vocab(self, token_lists: Sequence[Sequence[str]]) -> None:
        self.vocab.fit(token_lists)
        if len(self.vocab) == 0:
            raise ValueError("empty vocabulary — corpus too small or "
                             "min_word_frequency too high")
        Huffman(self.vocab).build()

    def reset_weights(self) -> None:
        """syn0 uniform in [-.5,.5]/D, syn1 zeros — reference
        `InMemoryLookupTable.resetWeights():94-100`."""
        rng = np.random.default_rng(self.seed)
        V, D = len(self.vocab), self.vector_length
        self.syn0 = ((rng.random((V, D)) - 0.5) / D).astype(np.float32)
        self.syn1 = np.zeros((max(V - 1, 1), D), np.float32)
        if self.negative > 0:
            self.syn1neg = np.zeros((V, D), np.float32)
            self._neg_table = jnp.asarray(build_negative_table(self.vocab))
        points, codes, lengths = self.vocab.hs_arrays()
        self._hs = (jnp.asarray(points), jnp.asarray(codes),
                    jnp.asarray(lengths))
        self._norms = None
        self._step = (self._build_neg_step() if self.negative > 0
                      else self._build_hs_step())

    # ------------------------------------------------------------------
    # pair generation (host side; reference skipGram():319)

    def _make_pairs(self, encoded: List[np.ndarray], rng: np.random.Generator
                    ) -> np.ndarray:
        """All (input=context, target=center) pairs for one epoch with the
        word2vec reduced-window trick; subsampling of frequent words if
        configured. Returns int32 [N, 2]."""
        total = self.vocab.total_word_count()
        keep_prob = None
        if self.subsample > 0:
            freq = np.array([self.vocab.word_frequency(self.vocab.word_at(i))
                             for i in range(len(self.vocab))], np.float64)
            ratio = freq / (self.subsample * total)
            keep_prob = np.minimum((np.sqrt(ratio) + 1) / ratio, 1.0)
        # Vectorized windowing: flatten the corpus with sentence ids, then
        # one numpy pass per offset d in [1, window] instead of a Python
        # loop per (token, offset) — ~20x faster host prep, same pair set
        # (context j for center i iff |j-i| <= window - b[i] within the
        # sentence, the word2vec reduced-window trick).
        sents = [s for s in encoded if len(s)]
        if not sents:
            return np.zeros((0, 2), np.int32)
        flat = np.concatenate(sents).astype(np.int32)
        sid = np.repeat(np.arange(len(sents)), [len(s) for s in sents])
        if keep_prob is not None and len(flat):
            keep = rng.random(len(flat)) < keep_prob[flat]
            flat, sid = flat[keep], sid[keep]
        n = len(flat)
        if n < 2:
            return np.zeros((0, 2), np.int32)
        win = self.window - rng.integers(0, self.window, n)  # in [1, window]
        chunks = []
        for d in range(1, self.window + 1):
            left = np.arange(n - d)
            same = sid[left] == sid[left + d]
            # center=left, context=left+d — gated by LEFT's reduced window
            c = left[same & (d <= win[left])]
            chunks.append(np.stack([flat[c + d], flat[c]], axis=1))
            # center=left+d, context=left — gated by RIGHT's reduced window
            c = left[same & (d <= win[left + d])]
            chunks.append(np.stack([flat[c], flat[c + d]], axis=1))
        arr = np.concatenate(chunks, axis=0).astype(np.int32)
        if not len(arr):
            return np.zeros((0, 2), np.int32)
        # permutation-gather, NOT rng.shuffle: numpy shuffles 2-D arrays
        # with per-row swaps (~40x slower; it dominated pair-gen time,
        # which is the host-side floor on TPU words/sec).
        return arr[rng.permutation(len(arr))]

    # ------------------------------------------------------------------
    # jitted training steps

    def _build_hs_step(self):
        """Sparse-update HS step: gradients are hand-derived for the
        TOUCHED rows only (the reference's `iterateSample:192` math,
        batched), applied as `.at[].add` scatters — O(B·L·D) work and
        memory instead of autodiff's dense O(V·D) gradient tables, which
        is the difference between toy and real vocabularies on TPU."""
        points, codes, lengths = self._hs
        L = points.shape[1]

        def deltas(syn0, syn1, inputs, targets, valid):
            """-> loss, (syn0 rows, syn0 deltas), (syn1 rows, syn1 deltas);
            deltas are DESCENT directions already scaled by -1 (add
            lr * delta to apply)."""
            h = syn0[inputs]                     # [B, D] input vectors
            p = points[targets]                  # [B, L] inner-node path
            c = codes[targets]                   # [B, L] branch bits
            mask = (jnp.arange(L)[None, :]
                    < lengths[targets][:, None]).astype(h.dtype)
            mask = mask * valid[:, None].astype(h.dtype)      # pad rows off
            w = syn1[p]                          # [B, L, D]
            dots = jnp.einsum("bd,bld->bl", h, w)
            # label 1 for code 0 (sign trick: s = 1 - 2*code)
            sign = 1.0 - 2.0 * c.astype(h.dtype)
            loss = -jnp.sum(_log_sigmoid(sign * dots) * mask)
            # d(-loss)/d(dots) = sign * sigmoid(-sign*dots), masked; the
            # reference's MAX_EXP skip zeroes saturated pairs.
            g = sign * jax.nn.sigmoid(-sign * dots) * mask    # [B, L]
            g = jnp.where(jnp.abs(dots) < MAX_EXP, g, 0.0)
            dh = jnp.einsum("bl,bld->bd", g, w)               # [B, D]
            dw = jnp.einsum("bl,bd->bld", g, h)               # [B, L, D]
            return loss, (inputs, dh), (p.reshape(-1),
                                        dw.reshape(-1, h.shape[-1]))

        step_core = self._sparse_step(deltas, with_key=False)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def hs_step(syn0, syn1, inputs, targets, lr, key, valid):
            return step_core(syn0, syn1, lr, inputs, targets, valid)

        return hs_step

    def _build_neg_step(self):
        """Sparse-update negative-sampling step; see _build_hs_step."""
        K = self.negative
        table = self._neg_table
        T = table.shape[0]

        def deltas(syn0, syn1neg, inputs, targets, valid, key):
            idx = jax.random.randint(key, (inputs.shape[0], K), 0, T)
            negs = table[idx]                    # [B, K]
            h = syn0[inputs]                     # [B, D]
            pos = syn1neg[targets]               # [B, D]
            neg = syn1neg[negs]                  # [B, K, D]
            pos_dot = jnp.sum(h * pos, axis=1)
            neg_dot = jnp.einsum("bd,bkd->bk", h, neg)
            # Collisions with the true target get masked out.
            collide = negs == targets[:, None]
            v = valid.astype(h.dtype)            # pad rows contribute zero
            neg_mask = jnp.where(collide, 0.0, v[:, None])
            loss = -(jnp.sum(_log_sigmoid(pos_dot) * v)
                     + jnp.sum(_log_sigmoid(-neg_dot) * neg_mask))
            # descent deltas (add lr * delta).  NOTE the asymmetry with
            # the HS step: the reference's negative-sampling loop does
            # NOT skip saturated pairs — it clamps the sigmoid to {0,1}
            # (InMemoryLookupTable.java:271-276), which the exact sigmoid
            # matches asymptotically, so no clip belongs here.
            g_pos = jax.nn.sigmoid(-pos_dot) * v              # [B]
            g_neg = -jax.nn.sigmoid(neg_dot) * neg_mask       # [B, K]
            dh = (g_pos[:, None] * pos
                  + jnp.einsum("bk,bkd->bd", g_neg, neg))     # [B, D]
            dpos = g_pos[:, None] * h                         # [B, D]
            dneg = jnp.einsum("bk,bd->bkd", g_neg, h)         # [B, K, D]
            out_rows = jnp.concatenate([targets, negs.reshape(-1)])
            out_deltas = jnp.concatenate(
                [dpos, dneg.reshape(-1, h.shape[-1])])
            return loss, (inputs, dh), (out_rows, out_deltas)

        step_core = self._sparse_step(deltas, with_key=True)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def neg_step(syn0, syn1neg, inputs, targets, lr, key, valid):
            return step_core(syn0, syn1neg, lr, inputs, targets, valid,
                             key)

        return neg_step

    def _sparse_step(self, deltas_fn, with_key: bool):
        """Turn a sparse-delta fn into the full table-update step via the
        shared `sparse_allgather_step` harness: single device scatter-adds
        `lr * delta` into the touched rows; with a mesh, the pair batch
        shards over the first axis (the documented TPU-native Hogwild,
        `Word2Vec.java:145-258`), the (rows, deltas) pairs are
        all_gathered — O(B·D) over ICI instead of a dense O(V·D) psum —
        and every replica applies the identical scatter."""

        def deltas(syn0, syn1, lr, inputs, targets, valid, *key):
            loss, p0, p1 = deltas_fn(syn0, syn1, inputs, targets, valid,
                                     *key)
            return loss, (p0, p1)

        def apply(syn0, syn1, lr, aux):
            (r0, d0), (r1, d1) = aux
            return (syn0.at[r0].add(lr * d0), syn1.at[r1].add(lr * d1))

        return sparse_allgather_step(self.mesh, deltas, apply, n_state=2,
                                     n_scalar=1, n_sharded=3,
                                     with_key=with_key)

    # ------------------------------------------------------------------
    # fit (reference Word2Vec.fit():103)

    def _pair_producer(self, encoded, out_q) -> None:
        """Background pair-chunk producer (reference parity: the
        Word2Vec.java:145-258 thread pool existed to overlap exactly this
        host work with training).  Epoch pair arrays are generated on a
        worker thread — numpy releases the GIL for the heavy ops — while
        the main thread keeps the device busy dispatching steps; the
        1-deep queue bounds host memory to one epoch ahead."""
        rng = np.random.default_rng(self.seed)
        try:
            for _ in range(self.epochs):
                out_q.put(("pairs", self._make_pairs(encoded, rng)))
            out_q.put(("done", None))
        except BaseException as e:  # noqa: BLE001 - re-raised by consumer
            out_q.put(("error", e))

    def fit(self, sentences) -> "Word2Vec":
        import os
        import queue
        import threading

        token_lists = self._sentences_to_tokens(sentences)
        if len(self.vocab) == 0:
            self.build_vocab(token_lists)
        if self.syn0.shape[0] != len(self.vocab):
            self.reset_weights()
        encoded = [self.vocab.encode(t) for t in token_lists]
        key = jax.random.PRNGKey(self.seed)

        use_hs = self.negative == 0
        syn0 = jnp.asarray(self.syn0)
        out = jnp.asarray(self.syn1 if use_hs else self.syn1neg)
        step = self._step

        # Pair-gen/device-step overlap needs a second core to be a win;
        # on a single-core host a quiet A/B measures the two paths equal
        # (threaded 0.99x inline — the GIL interleaves tolerably), so
        # prefer the simpler inline loop there and skip the thread
        # machinery that cannot help.  Either way the SAME rng object
        # generates epochs in order -> bit-identical pairs and results.
        producer = None
        if (os.cpu_count() or 1) > 1:
            pair_q: "queue.Queue" = queue.Queue(maxsize=1)
            producer = threading.Thread(
                target=self._pair_producer, args=(encoded, pair_q),
                daemon=True)
            producer.start()

            def epoch_chunks():
                while True:
                    kind, payload = pair_q.get()
                    if kind == "error":
                        raise payload
                    if kind == "done":
                        return
                    yield payload
        else:
            def epoch_chunks():
                rng = np.random.default_rng(self.seed)
                for _ in range(self.epochs):
                    yield self._make_pairs(encoded, rng)

        total_pairs = None
        seen = 0
        for pairs in epoch_chunks():
            if total_pairs is None:
                total_pairs = max(len(pairs) * self.epochs, 1)
            B = self.batch_size
            # Stage the pair stream on device in BOUNDED chunks (~1M
            # pairs each): per-batch slicing inside a chunk is
            # device-side — no host->device transfer in the hot loop
            # — while memory stays O(chunk), not
            # O(corpus).  The valid mask is all-ones except the final
            # tail batch, so only two [B] masks ever exist.
            n_batches = (len(pairs) + B - 1) // B  # 0 -> epoch skipped
            chunk_batches = max(1, STAGE_PAIRS // B)
            full_valid = jnp.ones((B,), jnp.int32)
            for c0 in range(0, n_batches, chunk_batches):
                c1 = min(c0 + chunk_batches, n_batches)
                part = pairs[c0 * B:c1 * B]
                padded = np.zeros(((c1 - c0) * B, 2), np.int32)
                padded[:len(part)] = part
                chunk_dev = jnp.asarray(padded.reshape(c1 - c0, B, 2))
                for bi in range(c1 - c0):
                    n_real = min(B, len(pairs) - (c0 + bi) * B)
                    if n_real < B:
                        tail = np.zeros((B,), np.int32)
                        tail[:n_real] = 1
                        valid = jnp.asarray(tail)
                    else:
                        valid = full_valid
                    # Linear LR decay by pairs seen (reference `alpha`
                    # decay, Word2Vec.java:231-238), floored at
                    # min_learning_rate.
                    frac = min(seen / total_pairs, 1.0)
                    lr = max(self.learning_rate * (1 - frac),
                             self.min_learning_rate)
                    key, sub = jax.random.split(key)
                    syn0, out, _ = step(
                        syn0, out, chunk_dev[bi, :, 0], chunk_dev[bi, :, 1],
                        jnp.float32(lr), sub, valid)
                    seen += n_real
        if producer is not None:
            producer.join()
        self.syn0 = np.asarray(syn0)
        if use_hs:
            self.syn1 = np.asarray(out)
        else:
            self.syn1neg = np.asarray(out)
        self._norms = None
        return self

    # reference naming
    train = fit
