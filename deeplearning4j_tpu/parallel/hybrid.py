"""Hybrid-parallel training: dp x sp x tp (+ ep) and dp x pp meshes.

This is the "scale shape" of the TPU framework (scaling-book recipe: pick a
mesh, annotate shardings, let XLA insert collectives):

- `HybridParallelTrainer`: TransformerLM over mesh axes (data, seq, model).
  Batch shards over `data` (dp), sequence over `seq` with ring attention
  (sp/CP), heads/hidden/experts over `model` (tp + ep). dp/tp/ep are GSPMD
  — parameters placed by `param_specs`, activations constrained, `jax.grad`
  taken over the full-array program so XLA derives the backward collectives.
  Only the ring-attention inner loop is shard_map (see transformer.py).
- `PipelineParallelTrainer`: mesh (data, stage) — transformer blocks
  stacked and sharded over `stage` (pp), GPipe microbatching via
  scan+ppermute (`pipeline.py`) under shard_map. The loss is computed on
  the last stage, masked elsewhere, and psum'd; with shard_map's
  psum-transposes-to-psum semantics (check_vma=False) every gradient then
  carries a uniform n_stages factor, removed by one normalization, and
  io-param gradients (stage-partial by construction) are psum'd across
  stages. A test asserts step-for-step equality with the single-device
  model for both trainers.

Both run unchanged on a v5e-8 or the 8-device virtual CPU mesh, and both
keep float32 master parameters regardless of the config's compute dtype
(bf16 math via casts inside the loss; see `_master_f32`).
"""

from __future__ import annotations



import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.obs.compilewatch import compile_scope
from deeplearning4j_tpu.parallel import transformer as tfm
from deeplearning4j_tpu.parallel.mesh import shard_map
from deeplearning4j_tpu.parallel.pipeline import gpipe_apply, zero1_flat_update


def _sgd_tree(params, grads, lr):
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)


def _cast_floating(tree, dtype):
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def _master_f32(tree):
    """Float32 master copies: with a bf16 compute dtype, params must NOT
    live (or update) in bf16 — `w - lr*g` in bf16 rounds away updates
    below ~0.4% of the weight and training silently stalls.  Matches
    MultiLayerNetwork's compute_dtype policy."""
    return _cast_floating(tree, jnp.float32)


def place_params(mesh: Mesh, tree, spec_tree):
    """device_put a pytree with a matching pytree of partition specs —
    either vocabulary: raw `jax.sharding.PartitionSpec` leaves or the
    package's `parallel.partition.PartitionSpec` (normalized through
    `partition.as_jax_leaf`, the ONE spec foundation).  jax's
    PartitionSpec is itself a tuple, so flatten the spec tree with specs
    as leaves rather than tree_map-ing the two trees together."""
    from deeplearning4j_tpu.parallel import partition as part_lib

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs = jax.tree_util.tree_flatten(
        spec_tree,
        is_leaf=lambda x: isinstance(x, (P, part_lib.PartitionSpec)))[0]
    assert len(leaves) == len(specs), (len(leaves), len(specs))
    placed = [jax.device_put(a, NamedSharding(mesh, part_lib.as_jax_leaf(s)))
              for a, s in zip(leaves, specs)]
    return jax.tree_util.tree_unflatten(treedef, placed)


def make_accum_train_step(cfg: tfm.TransformerConfig, lr: float = 1e-3,
                          accum: int = 1, updater: str = "sgd",
                          clip_norm: float = None,
                          weight_decay: float = 0.0,
                          lr_schedule=None, mesh: Mesh = None):
    """Single-chip flagship train step: donated f32 master params, bf16
    compute when the config says so, gradient accumulation over `accum`
    sequential microbatches via lax.scan (activation memory of ONE
    microbatch; pair with cfg.remat for long sequences).  Any updater
    from ops.updaters ('adam' is the realistic pretraining choice; the
    optimizer state lives in f32 beside the master params).  Decoupled
    `weight_decay` requires updater='adamw' or 'lion' — make_updater
    raises for updaters that would silently ignore it.

    `mesh` (axes data/seq/model, as `HybridParallelTrainer`'s) is for a
    batch that arrives sharded over the data axis (`dl4j lm -runtime
    spmd`): the model then runs its attention under shard_map.  GSPMD
    cannot partition a Mosaic kernel — without the mesh a sharded batch
    does not lower on a TPU ("Mosaic kernels cannot be automatically
    partitioned").

    Returns (step, init_state):
      init_state(params) -> opt_state
      step(params, opt_state, tokens, targets) -> (params, opt_state,
      mean_loss); tokens/targets are [accum * mb, S].
    This is the bench_gpt2 / GPT-2-small-class training path."""
    tfm.require_classic(cfg, "make_accum_train_step")
    from deeplearning4j_tpu.ops.updaters import (
        UpdaterConfig,
        apply_updates,
        make_updater,
    )

    compute_dtype = jnp.dtype(cfg.dtype)
    transform = make_updater(UpdaterConfig(
        updater=updater, learning_rate=lr, clip_norm=clip_norm,
        weight_decay=weight_decay, epsilon=1e-8,
        lr_schedule=lr_schedule))

    def loss_fn(p32, tok, tgt):
        p = (_cast_floating(p32, compute_dtype)
             if compute_dtype != jnp.float32 else p32)
        return tfm.lm_loss(cfg, p, tok, tgt, mesh)

    def step(params, opt_state, tokens, targets):
        if tokens.shape[0] % accum:
            raise ValueError(
                f"global batch {tokens.shape[0]} must be divisible by "
                f"accum={accum}")
        if accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(
                params, tokens, targets)
        else:
            s = tokens.shape[1]
            tok_mb = tokens.reshape(accum, -1, s)
            tgt_mb = targets.reshape(accum, -1, s)

            def body(carry, xs):
                acc_g, acc_l = carry
                tok, tgt = xs
                l, g = jax.value_and_grad(loss_fn)(params, tok, tgt)
                acc_g = jax.tree_util.tree_map(jnp.add, acc_g, g)
                return (acc_g, acc_l + l), None

            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            (grads, loss), _ = lax.scan(
                body, (zeros, jnp.float32(0.0)), (tok_mb, tgt_mb))
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            loss = loss / accum
        updates, opt_state = transform.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1)), transform.init


class HybridParallelTrainer:
    """dp x sp x tp(+ep) training for the TransformerLM via GSPMD.

    `updater` selects any ops.updaters transform ('sgd' keeps the
    historical exact-SGD behavior; 'adam' is the realistic pretraining
    choice).  Optimizer state is elementwise per parameter, so GSPMD
    shards it exactly like the parameter it moments — and, with
    `shard_update=True` (the default, matching DataParallelTrainer's
    ZeRO-1 plane), each moment leaf additionally shards its first free,
    `data`-divisible dimension over the data axis: the update math is
    elementwise, so XLA partitions the optimizer step across the dp
    axis and each replica persists only 1/N of the moments (arXiv
    2004.13336 expressed the GSPMD way — placement, not collectives).

    `params` (the `transformer.init_params` layout) are the weights to
    train from, e.g. a checkpoint's; they become the float32 masters and
    are placed on the mesh.  Without them the trainer initializes its
    own from `seed`."""

    def __init__(self, cfg: tfm.TransformerConfig, mesh: Mesh,
                 lr: float = 1e-2, seed: int = 0,
                 axes: tfm.MeshAxes = tfm.MeshAxes(),
                 updater: str = "sgd", shard_update: bool = True,
                 params=None):
        tfm.require_classic(cfg, "HybridParallelTrainer")
        from deeplearning4j_tpu.ops.updaters import (
            UpdaterConfig,
            apply_updates,
            make_updater,
        )

        self.cfg = cfg
        self.mesh = mesh
        self.lr = lr
        self.axes = axes
        self.shard_update = bool(shard_update)
        self._pspecs = tfm.param_specs(cfg, axes.model)
        given = params is not None
        # the masters' and the moments' placement: built once, by key
        with compile_scope("train:place"):
            if not given:
                params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
            self.params = place_params(mesh, _master_f32(params), self._pspecs)
            if given:
                # a placed shard can share its buffer with the array it came
                # from (`device_put` onto the device that holds it), and the
                # step donates its masters: copy, so that the first step does
                # not delete the caller's weights
                self.params = jax.tree_util.tree_map(jnp.copy, self.params)
            transform = make_updater(UpdaterConfig(
                updater=updater, learning_rate=lr, epsilon=1e-8))
            self.opt_state = transform.init(self.params)
            self._opt_specs = (self._zero1_opt_specs() if self.shard_update
                               else None)
            if self._opt_specs is not None:
                self.opt_state = place_params(mesh, self.opt_state,
                                              self._opt_specs)
        cfg_, mesh_, axes_ = cfg, mesh, axes
        compute_dtype = jnp.dtype(cfg.dtype)

        def step(params, opt_state, tokens, targets):
            def loss_fn(p):
                pc = (p if compute_dtype == jnp.float32
                      else _cast_floating(p, compute_dtype))
                return tfm.lm_loss(cfg_, pc, tokens, targets, mesh_, axes_,
                                   dealt=True)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = transform.update(grads, opt_state, params)
            return apply_updates(params, updates), opt_state, loss

        if self._opt_specs is not None:
            # Pin the output placements: without the constraint XLA's
            # sharding propagation may resolve the moment-vs-gradient
            # conflict by replicating the new moments, silently undoing
            # the ZeRO placement after the first step.
            out_sh = (self._shardings(self._pspecs, self.params),
                      self._shardings(self._opt_specs, self.opt_state),
                      NamedSharding(mesh, P()))
            self._step = jax.jit(step, donate_argnums=(0, 1),
                                 out_shardings=out_sh)
        else:
            self._step = jax.jit(step, donate_argnums=(0, 1))

    def _shardings(self, spec_tree, tree):
        """A NamedSharding pytree matching `tree` from a spec pytree in
        either vocabulary (same flattening discipline as place_params)."""
        from deeplearning4j_tpu.parallel import partition as part_lib

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        specs = jax.tree_util.tree_flatten(
            spec_tree,
            is_leaf=lambda x: isinstance(x, (P, part_lib.PartitionSpec)))[0]
        assert len(leaves) == len(specs), (len(leaves), len(specs))
        return jax.tree_util.tree_unflatten(
            treedef, [NamedSharding(self.mesh, part_lib.as_jax_leaf(s))
                      for s in specs])

    def _zero1_opt_specs(self):
        """Per-leaf specs for the optimizer state under ZeRO-1: moment
        trees mirror the param specs with the data axis added on the
        first free dimension whose size divides by the dp degree; leaves
        that don't mirror the params (the shared "step" counter)
        replicate.  A moment with no divisible free dim keeps its
        param placement — correct, just not sharded (the remainder
        rule here is whole-leaf, unlike the flat plane's padding)."""
        from deeplearning4j_tpu.parallel import partition as part_lib

        n_data = dict(zip(self.mesh.axis_names,
                          self.mesh.devices.shape))[self.axes.data]

        def zspec(spec, shape):
            spec = part_lib.as_jax_leaf(spec)
            used = {ax for e in spec if e is not None
                    for ax in (e if isinstance(e, tuple) else (e,))}
            if self.axes.data in used or n_data <= 1:
                return spec
            entries = list(spec) + [None] * (len(shape) - len(spec))
            for d, e in enumerate(entries):
                if e is None and shape[d] and shape[d] % n_data == 0:
                    entries[d] = self.axes.data
                    return P(*entries)
            return spec

        p_leaves, p_def = jax.tree_util.tree_flatten(self.params)
        pspec_leaves = jax.tree_util.tree_flatten(
            self._pspecs,
            is_leaf=lambda x: isinstance(x, (P, part_lib.PartitionSpec)))[0]
        specs = {}
        for key, sub in self.opt_state.items():
            leaves, sdef = jax.tree_util.tree_flatten(sub)
            if sdef == p_def:
                specs[key] = jax.tree_util.tree_unflatten(
                    sdef, [zspec(s, np.shape(a))
                           for s, a in zip(pspec_leaves, leaves)])
            else:
                specs[key] = P()
        return specs

    def fit_batch_async(self, tokens, targets):
        """One SPMD step; returns the loss as a DEVICE array without
        synchronizing (JIT107 discipline: back-to-back steps pipeline
        on the chips — sync only where a report is due)."""
        dsh = NamedSharding(self.mesh, P(self.axes.data, self.axes.seq))
        # a causal ring wants each row dealt zigzag over the `seq` chips:
        # tokens and targets alike, here and nowhere else (the loss is a
        # mean over positions, and the step gathers the learned positions
        # in the same order)
        order = tfm.seq_order(self.mesh, self.axes, np.shape(tokens)[1])

        def place(a):
            a = a if isinstance(a, jax.Array) else np.asarray(a)
            if order is not None:
                a = a[:, order]
            return jax.device_put(a.astype(jnp.int32), dsh)

        tokens, targets = place(tokens), place(targets)
        # named on the profiler's host plane; compiles counted by key
        with compile_scope("train:hybrid"):
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, tokens, targets)
        return loss

    def fit_batch(self, tokens, targets) -> float:
        """`fit_batch_async` + host sync on the loss."""
        return float(self.fit_batch_async(tokens, targets))

    def export_params(self) -> dict:
        """Gathered host copy of the params in the standard
        `transformer.init_params` layout (for checkpointing/generation)."""
        return jax.tree_util.tree_map(np.asarray, self.params)


class PipelineParallelTrainer:
    """dp x pp training: transformer blocks sharded over `stage`."""

    def __init__(self, cfg: tfm.TransformerConfig, mesh: Mesh,
                 n_microbatches: int = 4, lr: float = 1e-2, seed: int = 0,
                 data_axis: str = "data", stage_axis: str = "stage",
                 updater: str = "sgd", shard_update: bool = True):
        tfm.require_classic(cfg, "PipelineParallelTrainer")
        if cfg.n_experts:
            # Documented boundary (PARITY): MoE rides the dp/sp/tp/ep
            # mesh (HybridParallelTrainer); pipeline stages here are
            # dense-MLP only.
            raise ValueError("pipeline trainer uses dense MLP blocks; "
                             "train MoE configs on the dp/sp/tp/ep mesh")
        self.cfg = cfg
        self.mesh = mesh
        self.lr = lr
        self.m = n_microbatches
        self.axes = (data_axis, stage_axis)
        n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[stage_axis]
        if cfg.n_layers % n_stages:
            raise ValueError(
                f"n_layers {cfg.n_layers} must divide into {n_stages} stages")
        self.layers_per_stage = cfg.n_layers // n_stages
        self.n_stages = n_stages

        full = _master_f32(tfm.init_params(cfg, jax.random.PRNGKey(seed)))
        # stack per-layer trees: leaves [n_layers, ...] regrouped to
        # [n_stages, layers_per_stage, ...]; stage dim sharded over `stage`.
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves).reshape(
                (n_stages, self.layers_per_stage) + leaves[0].shape),
            *full["layers"])
        self.stage_params = jax.tree_util.tree_map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(stage_axis))), stacked)
        # Tied configs carry no separate head: lm_head(io) scores with
        # embed.T, and the stage-psum on io grads below accumulates the
        # tied leaf's two contributions (embedding lookup + projection)
        # across every stage's disjoint microbatch share.
        io = {"embed": full["embed"], "pos": full["pos"],
              "ln_f": full["ln_f"]}
        if not cfg.tie_embeddings:
            io["head"] = full["head"]
        self.io_params = jax.device_put(io, NamedSharding(mesh, P()))
        from deeplearning4j_tpu.ops.updaters import (
            UpdaterConfig,
            make_updater,
        )

        self._transform = make_updater(UpdaterConfig(
            updater=updater, learning_rate=lr, epsilon=1e-8))
        self.shard_update = bool(shard_update)
        self.n_data = dict(zip(mesh.axis_names,
                               mesh.devices.shape))[data_axis]
        if self.shard_update:
            # ZeRO-1 over the data axis, flat-plane form (same layout as
            # DataParallelTrainer / partition.zero1): per update plane a
            # FLAT f32 vector padded to the data degree.  Stage moments
            # are [n_stages, padded_extent] placed P(stage, data) — each
            # worker persists its stage's 1/n_data slice; io moments are
            # [padded_extent_io] placed P(data).
            from deeplearning4j_tpu.parallel.partition import padded_extent
            self._k0_stage = sum(
                int(np.prod(np.shape(a)))
                for a in jax.tree_util.tree_leaves(self.stage_params)
            ) // n_stages
            self._pe_stage = padded_extent(self._k0_stage, self.n_data)
            self._k0_io = sum(
                int(np.prod(np.shape(a)))
                for a in jax.tree_util.tree_leaves(self.io_params))
            self._pe_io = padded_extent(self._k0_io, self.n_data)
            stage_flat = self._transform.init(
                {"p": jnp.zeros((n_stages, self._pe_stage), jnp.float32)})
            io_flat = self._transform.init(
                {"p": jnp.zeros((self._pe_io,), jnp.float32)})
            self.stage_opt = place_params(
                mesh, stage_flat,
                {key: (P() if key == "step" else P(stage_axis, data_axis))
                 for key in stage_flat})
            self.io_opt = place_params(
                mesh, io_flat,
                {key: (P() if key == "step" else P(data_axis))
                 for key in io_flat})
        else:
            # Optimizer state mirrors the params it moments (zeros_like
            # preserves sharding: stage accumulators shard over `stage`,
            # io accumulators replicate); the "step" scalar replicates.
            self.stage_opt = self._transform.init(self.stage_params)
            self.io_opt = self._transform.init(self.io_params)
        self._step = self._build_step()

    def _stage_fn(self, stage_params, x):
        """Apply this stage's block(s); activation shape preserved."""
        for i in range(self.layers_per_stage):
            layer = jax.tree_util.tree_map(lambda a: a[i], stage_params)
            x = x + tfm._attn(layer["attn"],
                              tfm._layer_norm(layer["ln1"], x),
                              None, tfm.MeshAxes(), True)
            x = x + tfm._mlp(layer["mlp"],
                             tfm._layer_norm(layer["ln2"], x))
        return x

    def _build_step(self):
        from deeplearning4j_tpu.ops.updaters import apply_updates

        lr, m = self.lr, self.m
        data_axis, stage_axis = self.axes
        stage_fn = self._stage_fn
        transform = self._transform
        compute_dtype = jnp.dtype(self.cfg.dtype)
        shard_zero = self.shard_update
        # shard_map prefix-specs for the optimizer states: accumulator
        # subtrees follow their params' spec; the step counter replicates.
        # Under ZeRO-1 the flat moment planes additionally split over the
        # data axis (stage moments [n_stages, pe] -> P(stage, data); io
        # moments [pe_io] -> P(data)).
        if shard_zero:
            stage_opt_spec = {
                key: (P() if key == "step" else P(stage_axis, data_axis))
                for key in self.stage_opt}
            io_opt_spec = {key: (P() if key == "step" else P(data_axis))
                           for key in self.io_opt}
            n_data = self.n_data
            k0_st, pe_st = self._k0_stage, self._pe_stage
            k0_io, pe_io = self._k0_io, self._pe_io
        else:
            stage_opt_spec = {key: (P() if key == "step" else P(stage_axis))
                              for key in self.stage_opt}
            io_opt_spec = P()

        n_stages = self.n_stages
        k = -(-m // n_stages)          # ceil: per-stage microbatch share
        m_pad = k * n_stages

        def step(stage_params, io_params, stage_opt, io_opt, tokens,
                 targets):
            stage = lax.axis_index(stage_axis)

            def loss_fn(sp, iop):
                if compute_dtype != jnp.float32:  # f32 masters, bf16 math
                    sp = _cast_floating(sp, compute_dtype)
                    iop = _cast_floating(iop, compute_dtype)
                b, s = tokens.shape
                mb_b = b // m
                # Microbatch the TOKENS (tiny int arrays), pad to K*P
                # slots, and slice THIS stage's blocked share: each stage
                # embeds, pipelines, and scores only its own K
                # microbatches — O(M/P * mb) persistent activations per
                # device instead of the old full [M, mb] replication
                # (and embed/head compute is split across stages too).
                tok_mb = jnp.pad(tokens.reshape(m, mb_b, s),
                                 ((0, m_pad - m), (0, 0), (0, 0)))
                tgt_mb = jnp.pad(targets.reshape(m, mb_b, s),
                                 ((0, m_pad - m), (0, 0), (0, 0)))
                my_tok = lax.dynamic_slice_in_dim(tok_mb, stage * k, k, 0)
                my_tgt = lax.dynamic_slice_in_dim(tgt_mb, stage * k, k, 0)
                x = iop["embed"][my_tok] + iop["pos"][None, None, :s, :]
                y = gpipe_apply(stage_fn, sp, x, stage_axis, m)
                y = tfm._layer_norm(iop["ln_f"], y)
                logits = jnp.einsum("kbsd,dv->kbsv", y, tfm.lm_head(iop))
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(
                    logp, my_tgt[..., None], axis=-1)[..., 0]  # [K,mb_b,s]
                # padding slots (global index >= m) contribute nothing
                valid = (stage * k + jnp.arange(k) < m).astype(nll.dtype)
                local = jnp.sum(nll * valid[:, None, None]) / (b * s)
                # Disjoint per-stage partial means: the psum both
                # replicates the true global mean AND (via
                # psum-transposes-to-psum) scales every gradient by
                # exactly n_stages — normalized below.
                return lax.psum(local, stage_axis)

            loss, (g_stage, g_io) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(stage_params, io_params)
            inv = 1.0 / n_stages
            loss = lax.pmean(loss, data_axis)
            if shard_zero:
                from jax.flatten_util import ravel_pytree

                didx = lax.axis_index(data_axis)
                # stage plane: flatten the LOCAL stage's grads/params
                # (leaves [1, ...] under the stage in_spec), pad, and run
                # one ZeRO-1 round over the data axis.  The 1/n_stages
                # factor rides the flat gradient; psum_scatter/n is
                # bitwise pmean's reduction tree, so this path matches
                # the replicated update exactly.
                flat_g, _ = ravel_pytree(g_stage)
                flat_g = jnp.pad(flat_g * inv, (0, pe_st - k0_st))
                flat_p, unravel = ravel_pytree(stage_params)
                flat_p = jnp.pad(flat_p, (0, pe_st - k0_st))
                opt_local = jax.tree_util.tree_map(
                    lambda a: a[0] if a.ndim == 2 else a, stage_opt)
                new_flat, opt_local = zero1_flat_update(
                    transform, opt_local, flat_g, flat_p, data_axis,
                    n_data, didx, k0_st)
                new_stage = unravel(new_flat)
                stage_opt = jax.tree_util.tree_map(
                    lambda a: a[None] if a.ndim == 1 else a, opt_local)
                # io plane: stage-partial grads sum across stages first,
                # then the same flat round over data.
                flat_gio, _ = ravel_pytree(g_io)
                flat_gio = jnp.pad(
                    lax.psum(flat_gio, stage_axis) * inv,
                    (0, pe_io - k0_io))
                flat_pio, unravel_io = ravel_pytree(io_params)
                flat_pio = jnp.pad(flat_pio, (0, pe_io - k0_io))
                new_flat_io, io_opt = zero1_flat_update(
                    transform, io_opt, flat_gio, flat_pio, data_axis,
                    n_data, didx, k0_io)
                return (new_stage, unravel_io(new_flat_io),
                        stage_opt, io_opt, loss)
            # stage params: per-shard grads are n_stages x own-slice grad.
            g_stage = jax.tree_util.tree_map(
                lambda g: lax.pmean(g * inv, data_axis), g_stage)
            # io params: per-stage partial (each stage embeds/scores its
            # own disjoint share) -> sum across stages, then remove the
            # same n_stages factor.
            g_io = jax.tree_util.tree_map(
                lambda g: lax.pmean(lax.psum(g, stage_axis) * inv,
                                    data_axis), g_io)
            up_stage, stage_opt = transform.update(g_stage, stage_opt,
                                                   stage_params)
            up_io, io_opt = transform.update(g_io, io_opt, io_params)
            return (apply_updates(stage_params, up_stage),
                    apply_updates(io_params, up_io),
                    stage_opt, io_opt, loss)

        fn = shard_map(
            step, mesh=self.mesh,
            in_specs=(P(stage_axis), P(), stage_opt_spec, io_opt_spec,
                      P(data_axis), P(data_axis)),
            out_specs=(P(stage_axis), P(), stage_opt_spec, io_opt_spec,
                       P()))
        return jax.jit(fn, donate_argnums=(0, 1, 2, 3))

    def fit_batch_async(self, tokens, targets):
        """One pipelined step; returns the loss as a DEVICE array
        without synchronizing (JIT107 discipline: the microbatch
        schedule of step k+1 overlaps step k's tail)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        n_data = dict(zip(self.mesh.axis_names,
                          self.mesh.devices.shape))[self.axes[0]]
        local = tokens.shape[0] // n_data
        if tokens.shape[0] % n_data or local % self.m:
            raise ValueError(
                f"global batch {tokens.shape[0]} must split into "
                f"{n_data} data shards x {self.m} microbatches")
        dsh = NamedSharding(self.mesh, P(self.axes[0]))
        tokens = jax.device_put(tokens, dsh)
        targets = jax.device_put(jnp.asarray(targets, jnp.int32), dsh)
        (self.stage_params, self.io_params, self.stage_opt, self.io_opt,
         loss) = self._step(self.stage_params, self.io_params,
                            self.stage_opt, self.io_opt, tokens, targets)
        return loss

    def fit_batch(self, tokens, targets) -> float:
        """`fit_batch_async` + host sync on the loss."""
        return float(self.fit_batch_async(tokens, targets))

    def export_params(self) -> dict:
        """Gathered host copy in the standard `transformer.init_params`
        layout: the [n_stages, layers_per_stage, ...] stacked leaves
        unstack back into the list-of-layer-dicts tree (for
        checkpointing/generation)."""
        stacked = jax.tree_util.tree_map(np.asarray, self.stage_params)
        n_layers = self.cfg.n_layers
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((n_layers,) + a.shape[2:]), stacked)
        out = {k: jax.tree_util.tree_map(np.asarray, v)
               for k, v in self.io_params.items()}
        out["layers"] = [jax.tree_util.tree_map(lambda a: a[i], flat)
                         for i in range(n_layers)]
        return out
