"""Synchronous data-parallel training over a device mesh.

Parity target: the reference's "iterative reduce" parameter averaging —
Spark `SparkDl4jMultiLayer.runIteration():182-202` (broadcast params → train
partitions → accumulator-sum → divide), the Akka IterativeReduce router, and
the YARN master (SURVEY §2.3 list item 1). Averaging parameters every
iteration with a common start is mathematically synchronous SGD with gradient
averaging, so the TPU-native form is: ONE jitted SPMD step, batch sharded
over the mesh's `data` axis, `lax.pmean` over ICI for the gradient exchange.
No driver, no broadcast, no accumulator — the collective is compiled into
the step.

Design notes (scaling-book recipe):
- params/updater-state replicated (pure DP); batch sharded on dim 0.
- per-shard RNG: fold in `lax.axis_index` so dropout masks differ per shard.
- the same code runs on 1 chip (mesh of 1) or a v5e-8 — tests run it on the
  8-device virtual CPU mesh (tests/conftest.py).
- the weight-update plane is ZeRO-1 sharded BY DEFAULT (`shard_update=True`;
  Xu et al., "Automatic Cross-Replica Sharding of Weight Update in
  Data-Parallel Training", arXiv:2004.13336): gradients reduce-scatter over
  the data axis, each replica updates its 1/N flat slice of the params and
  optimizer state, and the updated params all-gather back — bitwise equal
  to the replicated update for elementwise updaters, with per-replica
  optimizer memory divided by N (docs/performance.md "The weight-update
  sharding cost model").  `shard_update=False` keeps the replicated
  allreduce path as an A/B escape hatch.
- an async/local-SGD mode (`sync_every > 1`) covers the reference's Hogwild
  router semantics (SURVEY §2.3 item 2): replicas step locally and average
  params every N steps — parameter averaging as an *option*, not the default.
  Per-replica divergence is real state, so in this mode params/updater-state/
  layer-state are carried with a leading replica dimension sharded over the
  data axis (leaf shape [n_devices, ...]); the every-N average is an explicit
  `lax.pmean` over that axis.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P
from deeplearning4j_tpu.parallel import partition as part_lib
from deeplearning4j_tpu.parallel.mesh import shard_map
from deeplearning4j_tpu.models.multi_layer_network import (
    MultiLayerNetwork,
    _as_batches,
    _maybe_reset,
)
from deeplearning4j_tpu.ops.updaters import (
    apply_updates,
    global_grad_norm,
    make_updater,
)
from deeplearning4j_tpu.parallel import mesh as mesh_lib
from deeplearning4j_tpu.precision import (
    grads_finite,
    init_scaler_state,
    shard_update_finite,
    unscale_grads,
    update_scaler_state,
    where_tree,
)


class DataParallelTrainer:
    """Wraps a MultiLayerNetwork with an SPMD data-parallel train step."""

    def __init__(self, net: MultiLayerNetwork, mesh=None, axis: str = "data",
                 sync_every: int = 1, shard_update: bool = True):
        self.net = net
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        self.axis = axis
        self.sync_every = sync_every
        self.shard_update = bool(shard_update)
        self.n_devices = int(np.prod(self.mesh.devices.shape))
        if net.params is None:
            net.init()
        self._updater = make_updater(net.conf.conf.updater_config())
        # Precision plane: the net's policy rides into the SPMD step.
        # The dynamic loss scaler composes with BOTH synchronous update
        # planes (replicated allreduce and the default ZeRO-1 sharded
        # step — scale/unscale straddle the psum_scatter there); only
        # local-SGD is out, since diverged replicas would need
        # per-replica scaler automatons.
        if net.precision.loss_scale is not None and sync_every != 1:
            raise ValueError(
                "a loss-scaled precision policy (e.g. 'mixed') requires "
                "a synchronous DP path (sync_every == 1); local-SGD "
                "replicas would need per-replica scaler automatons")
        self._built_policy = net.precision
        self._step_fn = self._select_step()
        self._avg_fn = None
        self._chunk_step_fn = {}  # has_mask -> fused K-step program
        self._rep = None  # stacked (params, state, upd_state), local mode
        self._iteration = 0

    # ---- the SPMD step ----------------------------------------------------

    def _select_step(self):
        """ONE builder choice: local-SGD when sync_every > 1 (the
        sharded plane then lives in the periodic sync round — see
        `_averaged_rep`), else the ZeRO-1 sharded update (the default)
        or the replicated allreduce step (the `shard_update=False` A/B
        escape hatch)."""
        if self.sync_every != 1:
            return self._build_local_step()
        if self.shard_update:
            return self._build_sharded_update_step()
        return self._build_step()

    def _check_policy(self) -> None:
        """Rebuild the compiled SPMD steps when the net's precision
        policy changed since construction (`net.set_precision` /
        `fit(precision=...)`): the steps bake the compute dtype and the
        scaler mode in.  Same restrictions as the constructor."""
        if self.net.precision == self._built_policy:
            return
        if self.net.precision.loss_scale is not None and \
                self.sync_every != 1:
            raise ValueError(
                "a loss-scaled precision policy (e.g. 'mixed') requires "
                "a synchronous DP path (sync_every == 1); local-SGD "
                "replicas would need per-replica scaler automatons")
        self._built_policy = self.net.precision
        self._chunk_step_fn = {}
        # Trainer-held training state was built under the OLD policy and
        # must not leak through the change:
        if self._rep is not None:
            # local-SGD: fold outstanding per-replica drift into the net
            # (in the old dtype — the publish overwrites the cast
            # `set_precision` already applied), then re-apply the new
            # param dtype so the next step restacks cast masters.
            self._average_params()
            self._rep = None
            dtype = jnp.dtype(self.net.precision.param_dtype)
            self.net.params = jax.tree_util.tree_map(
                lambda a: a.astype(dtype)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                else a, self.net.params)
            if self.net.updater_state is not None:
                self.net.updater_state = self._updater.init(self.net.params)
        self._avg_fn = None  # compiled for the old dtype
        if self.shard_update and self.sync_every == 1:
            # Publish the live flat moments to the net's per-layer form
            # FIRST (with the old unravel template), then drop the
            # ravel/unravel cache — it bakes the param dtype in — so the
            # rebuilt step re-adopts the moments under the new policy.
            self.sync_updater_state_to_net()
            if hasattr(self, "_flat_cache"):
                del self._flat_cache
            self._opt_shard = None
        self._step_fn = self._select_step()

    def _build_step(self):
        net = self.net
        updater = self._updater
        axis = self.axis
        scfg = net.precision.loss_scale

        def shard_step(params, state, upd_state, sc_state, x, y, rng, mask,
                       lr_scale):
            # Different dropout/sampling per shard, same init everywhere.
            rng = jax.random.fold_in(rng, lax.axis_index(axis))

            if scfg is None:
                def lossfn(p):
                    return net._objective(p, state, x, y, rng, mask)

                (loss, new_state), grads = jax.value_and_grad(
                    lossfn, has_aux=True)(params)
            else:
                # Mixed precision: the per-shard loss is scaled BEFORE
                # differentiation; the pmean'd gradient is unscaled
                # after the collective, so an overflow on ANY shard is
                # visible to ALL replicas (pmean of inf is inf
                # everywhere) and they skip the update in lockstep —
                # no divergence, no extra collective.
                scale = sc_state["scale"]

                def lossfn(p):
                    loss, new_state = net._objective(p, state, x, y, rng,
                                                     mask)
                    return loss * scale.astype(loss.dtype), (loss, new_state)

                (_, (loss, new_state)), grads = jax.value_and_grad(
                    lossfn, has_aux=True)(params)
            # The collective: gradient allreduce over ICI. This single
            # line replaces Spark broadcast+accumulate, Akka
            # IterativeReduce, and the YARN master (SURVEY §3.2).
            grads = lax.pmean(grads, axis)
            loss = lax.pmean(loss, axis)
            if scfg is not None:
                grads = unscale_grads(grads, sc_state["scale"])
            gnorm = global_grad_norm(grads)
            new_state = jax.tree_util.tree_map(
                lambda s: lax.pmean(s, axis) if jnp.issubdtype(
                    jnp.asarray(s).dtype, jnp.floating) else s,
                new_state)
            updates, new_upd = updater.update(grads, upd_state, params)
            updates = net._apply_lr_multipliers(updates)
            updates = jax.tree_util.tree_map(lambda u: u * lr_scale,
                                             updates)
            new_params = apply_updates(params, updates)
            if scfg is None:
                return new_params, new_state, new_upd, sc_state, loss, gnorm
            finite = jnp.logical_and(grads_finite(grads),
                                     jnp.isfinite(loss))
            params = where_tree(finite, new_params, params)
            upd_state = where_tree(finite, new_upd, upd_state)
            new_state = where_tree(finite, new_state, state)
            sc_state = update_scaler_state(scfg, sc_state, finite)
            return params, new_state, upd_state, sc_state, loss, gnorm

        # ONE partition vocabulary (parallel/partition.py): replicated
        # params/state, batch-sharded data over the replica axis.
        pspec = part_lib.as_jax(part_lib.replicated())
        dspec = part_lib.as_jax(part_lib.sharded(self.axis))

        fn = shard_map(
            shard_step,
            mesh=self.mesh,
            in_specs=(pspec, pspec, pspec, pspec, dspec, dspec, pspec,
                      dspec, pspec),
            out_specs=(pspec, pspec, pspec, pspec, pspec, pspec),
        )
        return jax.jit(fn)

    def _build_chunk_step(self, has_mask: bool, unroll: int = 1):
        """Fused K-steps-per-dispatch SPMD program (plain sync DP only):
        the per-step body of `_build_step` — per-shard weighted objective,
        gradient pmean over ICI, updater — scanned over a stacked [K, B,
        ...] chunk whose batch dim shards over the mesh's data axis.
        Per-step RNG reproduces the per-batch path exactly:
        fold_in(fold_in(PRNGKey(seed), iteration), axis_index).  Returns
        per-step loss / grad-norm vectors so the host syncs once per
        chunk.  unroll semantics as in
        MultiLayerNetwork._make_train_chunk (1 = bit-stable rolled
        scan)."""
        from deeplearning4j_tpu.models.multi_layer_network import (
            _CHUNK_UNROLL_CAP,
        )

        net = self.net
        updater = self._updater
        axis = self.axis
        scfg = net.precision.loss_scale

        def shard_chunk(params, state, upd_state, sc_state, xs, ys, ws,
                        masks, it0, lr_scale):
            base = jax.random.PRNGKey(net.conf.conf.seed)
            idx = lax.axis_index(axis)

            def body(carry, inp):
                if scfg is None:
                    params, state, upd = carry
                else:
                    params, state, upd, sc = carry
                if has_mask:
                    xi, yi, wi, mi, it = inp
                else:
                    (xi, yi, wi, it), mi = inp, None
                rng = jax.random.fold_in(jax.random.fold_in(base, it), idx)

                # Differentiate the UNNORMALIZED local weighted loss sum,
                # then psum numerator/denominator/gradient separately and
                # divide by the GLOBAL weight sum: padded tail rows may
                # land unevenly across shards (a whole shard can be pure
                # padding), and a pmean of per-shard weighted means would
                # weight such shards wrongly.  This form equals the
                # single-device weighted objective exactly.  Under a
                # loss-scaled policy the numerator is scaled before
                # differentiation and the psum'd gradient unscaled after
                # — overflow anywhere is inf everywhere post-psum, so
                # every replica skips the step in lockstep.
                def lossfn(p):
                    num, den, new_state = net._weighted_loss_sums(
                        p, state, xi, yi, rng, mi, wi)
                    num_d = (num if scfg is None
                             else num * sc["scale"].astype(num.dtype))
                    return num_d, (num, den, new_state)

                (_, (num, den, new_state)), grads = jax.value_and_grad(
                    lossfn, has_aux=True)(params)
                denom = jnp.maximum(lax.psum(den, axis), 1.0)
                grads = jax.tree_util.tree_map(
                    lambda g: lax.psum(g, axis) / denom, grads)
                if scfg is not None:
                    grads = unscale_grads(grads, sc["scale"])
                loss = lax.psum(num, axis) / denom
                if net._has_reg():
                    # replicated term: add its gradient once, post-psum
                    reg, reg_grads = jax.value_and_grad(net._reg_loss)(
                        params)
                    loss = loss + reg
                    grads = jax.tree_util.tree_map(
                        lambda g, r: g + r, grads, reg_grads)
                gnorm = global_grad_norm(grads)
                new_state = jax.tree_util.tree_map(
                    lambda s: lax.pmean(s, axis) if jnp.issubdtype(
                        jnp.asarray(s).dtype, jnp.floating) else s,
                    new_state)
                updates, new_upd = updater.update(grads, upd, params)
                updates = net._apply_lr_multipliers(updates)
                updates = jax.tree_util.tree_map(lambda u: u * lr_scale,
                                                 updates)
                new_params = apply_updates(params, updates)
                if scfg is None:
                    return (new_params, new_state, new_upd), (loss, gnorm)
                finite = jnp.logical_and(grads_finite(grads),
                                         jnp.isfinite(loss))
                params = where_tree(finite, new_params, params)
                upd = where_tree(finite, new_upd, upd)
                state = where_tree(finite, new_state, state)
                sc = update_scaler_state(scfg, sc, finite)
                return (params, state, upd, sc), (loss, gnorm)

            its = it0 + jnp.arange(xs.shape[0])
            inputs = ((xs, ys, ws, masks, its) if has_mask
                      else (xs, ys, ws, its))
            carry = ((params, state, upd_state) if scfg is None
                     else (params, state, upd_state, sc_state))
            carry, (losses, gnorms) = lax.scan(
                body, carry, inputs,
                unroll=min(int(xs.shape[0]), unroll, _CHUNK_UNROLL_CAP))
            if scfg is None:
                params, state, upd_state = carry
            else:
                params, state, upd_state, sc_state = carry
            return params, state, upd_state, sc_state, losses, gnorms

        pspec = P()
        cspec = P(None, self.axis)  # [K, B, ...]: shard the batch dim
        out_specs = (pspec, pspec, pspec, pspec, pspec, pspec)
        if has_mask:
            fn = jax.jit(shard_map(
                shard_chunk, mesh=self.mesh,
                in_specs=(pspec, pspec, pspec, pspec, cspec, cspec, cspec,
                          cspec, pspec, pspec),
                out_specs=out_specs))
            return fn

        def no_mask(params, state, upd, sc, xs, ys, ws, it0, lr_scale):
            return shard_chunk(params, state, upd, sc, xs, ys, ws, None,
                               it0, lr_scale)

        fn = jax.jit(shard_map(
            no_mask, mesh=self.mesh,
            in_specs=(pspec, pspec, pspec, pspec, cspec, cspec, cspec,
                      pspec, pspec),
            out_specs=out_specs))
        return lambda p, s, u, sc, xs, ys, ws, masks, it0, lr: fn(
            p, s, u, sc, xs, ys, ws, it0, lr)

    def fit_chunk_async(self, xs, ys, masks=None, weights=None,
                        unroll: int = 1):
        """K = xs.shape[0] SPMD optimizer steps in one dispatch (fused
        driver primitive; synchronous DP modes — the default ZeRO-1
        sharded plane threads its shard-local optimizer state through
        the scan carry; only local-SGD is out, its per-replica stacks
        carry state the scan cannot thread).  Returns per-step (losses,
        grad_norms) device vectors."""
        if self.sync_every != 1:
            raise NotImplementedError(
                "fit_chunk_async supports synchronous DP paths "
                "(sync_every == 1); use per-batch fit_batch_async for "
                "local-SGD")
        net = self.net
        self._check_policy()
        sh = jax.sharding.NamedSharding(self.mesh, P(None, self.axis))
        put = lambda a: None if a is None else jax.device_put(a, sh)  # noqa: E731
        xs = put(xs)
        ys = put(ys)
        masks = put(masks)
        k = int(xs.shape[0])
        if int(xs.shape[1]) % self.n_devices:
            raise ValueError(
                f"Global batch {int(xs.shape[1])} not divisible by "
                f"{self.n_devices} devices")
        weights = (jnp.ones(xs.shape[:2], jnp.float32) if weights is None
                   else jnp.asarray(weights, jnp.float32))
        weights = put(weights)
        key = (masks is not None, max(1, int(unroll)))
        step = self._chunk_step_fn.get(key)
        if step is None:
            build = (self._build_sharded_chunk_step if self.shard_update
                     else self._build_chunk_step)
            step = self._chunk_step_fn[key] = build(key[0], key[1])
        it0 = self._iteration
        scfg = net.precision.loss_scale
        if scfg is not None and net._scaler_state is None:
            net._scaler_state = init_scaler_state(scfg)
        sc_state = net._scaler_state if scfg is not None else {}
        if self.shard_update:
            (net.params, net.state, self._opt_shard, sc_state, losses,
             gnorms) = step(
                net.params, net.state, self._opt_shard, sc_state, xs, ys,
                weights, masks, jnp.asarray(it0, jnp.int32),
                jnp.asarray(net._lr_scale, jnp.float32))
            # trainer-owned sharded moments (see fit_batch_async)
            net.updater_state = None
            net._updater_state_owner = self
        else:
            (net.params, net.state, net.updater_state, sc_state, losses,
             gnorms) = step(
                net.params, net.state, net.updater_state, sc_state, xs, ys,
                weights, masks, jnp.asarray(it0, jnp.int32),
                jnp.asarray(net._lr_scale, jnp.float32))
        if scfg is not None:
            net._scaler_state = sc_state
        self._iteration += k
        net.last_grad_norm = gnorms[-1]
        net._fire_chunk_listeners(it0, k, losses)
        return losses, gnorms

    def stage_chunk(self, chunk):
        """Fused-driver prefetch hook: stage a HostChunk with the batch
        dim sharded over the mesh's data axis (one sharded host->device
        transfer on the producer thread instead of an asarray + reshard
        on the training thread)."""
        sh = jax.sharding.NamedSharding(self.mesh, P(None, self.axis))
        put = lambda a: None if a is None else jax.device_put(a, sh)  # noqa: E731
        return chunk._replace(xs=put(chunk.xs), ys=put(chunk.ys),
                              weights=put(chunk.weights),
                              masks=put(chunk.masks))

    def _sharded_updater(self):
        """The updater CORE for the flat 1/N shard: the pre-apply
        transforms (l1/l2/clip_value/clip_norm/unit_norm) are stripped
        from the config and re-applied manually by `_shard_pre_apply` —
        norm-based transforms need cross-replica reductions the flat
        shard cannot see, and letting `pre_apply` run on a shard would
        silently compute shard-local norms.  Decoupled weight_decay
        (adamw/lion) stays: it is elementwise in (u, p)."""
        import dataclasses

        ucfg = self.net.conf.conf.updater_config()
        core = dataclasses.replace(
            ucfg, l1=0.0, l2=0.0, clip_value=None, clip_norm=None,
            unit_norm=False)
        return make_updater(core)

    def _shard_pre_apply(self, ksh: int):
        """Shard-local mirror of `ops.updaters.pre_apply` over the flat
        1/N gradient slice, in the exact transform order (l2 → l1 →
        clip_value → clip_norm → unit_norm).  Elementwise transforms are
        bitwise-identical to the replicated path; the norm-based ones
        psum shard-partial sums of squares to the GLOBAL norms (equal up
        to summation grouping).  unit_norm's per-leaf norms come from a
        host-built leaf-id vector + segment_sum, so one segmented
        reduction serves every leaf the shard straddles.  Returns None
        when no transform is configured (skip the whole stage)."""
        ucfg = self.net.conf.conf.updater_config()
        axis = self.axis
        if not (ucfg.l1 or ucfg.l2 or ucfg.clip_value is not None
                or ucfg.clip_norm is not None or ucfg.unit_norm):
            return None
        leaf_ids = None
        n_leaves = 0
        if ucfg.unit_norm:
            leaves = jax.tree_util.tree_leaves(self.net.params)
            n_leaves = len(leaves)
            ids = np.concatenate([
                np.full(int(np.size(l)), i, np.int32)
                for i, l in enumerate(leaves)])
            # padding lanes get their own segment id: zero grads, and
            # their bogus norm never multiplies a real element
            leaf_ids = jnp.asarray(np.pad(
                ids, (0, self._flat_k - ids.shape[0]),
                constant_values=n_leaves))

        def pre(g, p, idx):
            if ucfg.l2:
                g = g + ucfg.l2 * p
            if ucfg.l1:
                g = g + ucfg.l1 * jnp.sign(p)
            if ucfg.clip_value is not None:
                g = jnp.clip(g, -ucfg.clip_value, ucfg.clip_value)
            if ucfg.clip_norm is not None:
                gnorm = jnp.sqrt(lax.psum(jnp.sum(jnp.square(g)), axis))
                g = g * jnp.minimum(1.0, ucfg.clip_norm / (gnorm + 1e-12))
            if ucfg.unit_norm:
                my_ids = lax.dynamic_slice_in_dim(leaf_ids, idx * ksh, ksh)
                sq = jax.ops.segment_sum(jnp.square(g), my_ids,
                                         num_segments=n_leaves + 1)
                norms = jnp.sqrt(lax.psum(sq, axis))
                g = g / (norms[my_ids] + 1e-12)
            return g

        return pre

    def _lr_mult_flat(self):
        """Per-layer lr multipliers as ONE flat per-element vector
        aligned with the raveled parameter order (padding lanes get 1.0)
        — the flat shard has no layer structure, but a sliced multiply
        against this vector is elementwise-identical to
        `net._apply_lr_multipliers` on the per-layer trees.  None when
        every multiplier is 1.0 (skip the multiply entirely)."""
        layers = self.net.conf.layers
        if all(lc.lr_multiplier == 1.0 for lc in layers):
            return None
        segs = [np.full(int(sum(np.size(l) for l in
                              jax.tree_util.tree_leaves(sub))),
                        lc.lr_multiplier, np.float32)
                for lc, sub in zip(layers, self.net.params)]
        vec = np.concatenate([s for s in segs if s.size]
                             or [np.zeros(0, np.float32)])
        return jnp.asarray(np.pad(vec, (0, self._flat_k - vec.shape[0]),
                                  constant_values=1.0))

    def _build_sharded_update_step(self):
        """ZeRO-1-style cross-replica weight-update sharding (Xu et al.,
        "Automatic Cross-Replica Sharding of Weight Update in
        Data-Parallel Training", arXiv:2004.13336) — the DEFAULT DP
        plane: gradients are `psum_scatter`'d over the data axis so each
        replica holds only its 1/N slice of the flat gradient, updates
        ITS slice of the parameters and optimizer state (which lives
        sharded between steps — the N-fold optimizer-memory saving),
        then `all_gather`s the updated parameters for the next forward.
        For elementwise updaters (all of ours) the result is
        bit-equivalent to the replicated update — psum_scatter +
        all_gather shares pmean's reduction tree, unlike psum + slice;
        it trades one reduce_scatter + one all_gather for the pmean and
        divides update FLOPs and optimizer HBM by N.

        Precision plane composition: under a loss-scaled policy the
        per-shard loss is scaled BEFORE differentiation and the 1/N
        gradient slice unscaled AFTER the collective (scale/unscale
        straddle the psum_scatter), with the finiteness verdict a
        cross-replica psum (`shard_update_finite`) so overflow skips
        stay in lockstep.  clip_norm/unit_norm psum shard-partial square
        norms to the global norms; per-layer lr_multiplier rides as a
        flat sliced vector."""
        from jax.flatten_util import ravel_pytree

        net = self.net
        updater = self._sharded_updater()
        axis = self.axis
        scfg = net.precision.loss_scale
        # Shard over the DATA axis only (a multi-axis mesh replicates the
        # opt state over its other axes, same as the params).
        n = int(self.mesh.shape[self.axis])
        k0, unravel = self._flat_meta()
        k = self._flat_k = ((k0 + n - 1) // n) * n  # padded flat length
        ksh = k // n
        pre = self._shard_pre_apply(ksh)
        mult = self._lr_mult_flat()

        def shard_step(params, state, upd_shard, sc_state, x, y, rng,
                       mask, lr_scale):
            idx = lax.axis_index(axis)
            rng = jax.random.fold_in(rng, idx)

            if scfg is None:
                def lossfn(p):
                    return net._objective(p, state, x, y, rng, mask)

                (loss, new_state), grads = jax.value_and_grad(
                    lossfn, has_aux=True)(params)
            else:
                scale = sc_state["scale"]

                def lossfn(p):
                    loss, new_state = net._objective(p, state, x, y, rng,
                                                     mask)
                    return loss * scale.astype(loss.dtype), (loss, new_state)

                (_, (loss, new_state)), grads = jax.value_and_grad(
                    lossfn, has_aux=True)(params)
            flat_g = jnp.pad(ravel_pytree(grads)[0], (0, k - k0))
            # mean-gradient SHARD: [k/n] per replica, not the full [k]
            g_shard = lax.psum_scatter(flat_g, axis, tiled=True) / n
            loss = lax.pmean(loss, axis)
            if scfg is not None:
                g_shard = unscale_grads(g_shard, sc_state["scale"])
                finite = shard_update_finite(g_shard, loss, axis)
            # global mean-grad norm from the shards (padding is zero)
            gnorm = jnp.sqrt(lax.psum(
                jnp.sum(jnp.square(g_shard.astype(jnp.float32))), axis))
            flat_p = jnp.pad(ravel_pytree(params)[0], (0, k - k0))
            p_shard = lax.dynamic_slice_in_dim(flat_p, idx * ksh, ksh)
            g2 = g_shard if pre is None else pre(g_shard, p_shard, idx)
            updates, new_upd = updater.update(
                {"p": g2}, upd_shard, {"p": p_shard})
            u = updates["p"]
            if mult is not None:
                u = u * lax.dynamic_slice_in_dim(
                    mult, idx * ksh, ksh).astype(u.dtype)
            u = u * lr_scale
            new_shard = apply_updates({"p": p_shard}, {"p": u})["p"]
            new_state = jax.tree_util.tree_map(
                lambda s: lax.pmean(s, axis) if jnp.issubdtype(
                    jnp.asarray(s).dtype, jnp.floating) else s,
                new_state)
            if scfg is not None:
                # Overflow: keep the OLD shard/moments/layer state and
                # let the automaton back off.  Every replica takes the
                # same branch — the verdict is a cross-replica psum —
                # and selecting on the shard BEFORE the gather means the
                # skipped step gathers back exactly the old params.
                new_shard = jnp.where(finite, new_shard, p_shard)
                new_upd = where_tree(finite, new_upd, upd_shard)
                new_state = where_tree(finite, new_state, state)
                sc_state = update_scaler_state(scfg, sc_state, finite)
            new_flat = lax.all_gather(new_shard, axis, tiled=True)[:k0]
            params = unravel(new_flat)
            return params, new_state, new_upd, sc_state, loss, gnorm

        pspec = part_lib.as_jax(part_lib.replicated())
        dspec = part_lib.as_jax(part_lib.sharded(self.axis))
        # Optimizer-state leaves over the padded flat vector shard over
        # the axis; scalar leaves (step counters) stay replicated.
        self._opt_shard = self._init_sharded_opt_state()
        sspec = jax.tree_util.tree_map(
            lambda a: part_lib.as_jax(self._opt_leaf_partition(a, k)),
            self._opt_shard)
        fn = shard_map(
            shard_step,
            mesh=self.mesh,
            in_specs=(pspec, pspec, sspec, pspec, dspec, dspec, pspec,
                      dspec, pspec),
            out_specs=(pspec, pspec, sspec, pspec, pspec, pspec),
        )
        return jax.jit(fn)

    def _build_sharded_chunk_step(self, has_mask: bool, unroll: int = 1):
        """Fused K-steps-per-dispatch under the ZeRO-1 plane: the
        sharded per-step body of `_build_sharded_update_step` — weighted
        objective, psum_scatter to the 1/N gradient slice, shard-local
        optimizer step, all_gather — scanned over a stacked [K, B, ...]
        chunk.  The shard-local optimizer state (and scaler automaton)
        rides the scan CARRY, so K steps cost one dispatch and the
        moments never leave their shards.  Weighted-objective, RNG and
        unroll semantics exactly as `_build_chunk_step`."""
        from deeplearning4j_tpu.models.multi_layer_network import (
            _CHUNK_UNROLL_CAP,
        )
        from jax.flatten_util import ravel_pytree

        net = self.net
        updater = self._sharded_updater()
        axis = self.axis
        scfg = net.precision.loss_scale
        n = int(self.mesh.shape[self.axis])
        k0, unravel = self._flat_meta()
        k = self._flat_k = ((k0 + n - 1) // n) * n
        ksh = k // n
        pre = self._shard_pre_apply(ksh)
        mult = self._lr_mult_flat()

        def shard_chunk(params, state, upd_shard, sc_state, xs, ys, ws,
                        masks, it0, lr_scale):
            base = jax.random.PRNGKey(net.conf.conf.seed)
            idx = lax.axis_index(axis)

            def body(carry, inp):
                if scfg is None:
                    params, state, upd = carry
                else:
                    params, state, upd, sc = carry
                if has_mask:
                    xi, yi, wi, mi, it = inp
                else:
                    (xi, yi, wi, it), mi = inp, None
                rng = jax.random.fold_in(jax.random.fold_in(base, it), idx)

                # Same weighted-sum form as `_build_chunk_step` (padded
                # tail rows land unevenly across shards), with the psum
                # of the gradient replaced by a psum_scatter to this
                # replica's 1/N slice.
                def lossfn(p):
                    num, den, new_state = net._weighted_loss_sums(
                        p, state, xi, yi, rng, mi, wi)
                    num_d = (num if scfg is None
                             else num * sc["scale"].astype(num.dtype))
                    return num_d, (num, den, new_state)

                (_, (num, den, new_state)), grads = jax.value_and_grad(
                    lossfn, has_aux=True)(params)
                denom = jnp.maximum(lax.psum(den, axis), 1.0)
                flat_g = jnp.pad(ravel_pytree(grads)[0], (0, k - k0))
                g_shard = lax.psum_scatter(flat_g, axis, tiled=True) / denom
                if scfg is not None:
                    g_shard = unscale_grads(g_shard, sc["scale"])
                loss = lax.psum(num, axis) / denom
                if net._has_reg():
                    # replicated term: add THIS shard's slice of its
                    # gradient once, post-scatter
                    reg, reg_grads = jax.value_and_grad(net._reg_loss)(
                        params)
                    loss = loss + reg
                    flat_r = jnp.pad(ravel_pytree(reg_grads)[0],
                                     (0, k - k0))
                    g_shard = g_shard + lax.dynamic_slice_in_dim(
                        flat_r, idx * ksh, ksh)
                if scfg is not None:
                    finite = shard_update_finite(g_shard, loss, axis)
                gnorm = jnp.sqrt(lax.psum(
                    jnp.sum(jnp.square(g_shard.astype(jnp.float32))),
                    axis))
                flat_p = jnp.pad(ravel_pytree(params)[0], (0, k - k0))
                p_shard = lax.dynamic_slice_in_dim(flat_p, idx * ksh, ksh)
                g2 = g_shard if pre is None else pre(g_shard, p_shard, idx)
                updates, new_upd = updater.update(
                    {"p": g2}, upd, {"p": p_shard})
                u = updates["p"]
                if mult is not None:
                    u = u * lax.dynamic_slice_in_dim(
                        mult, idx * ksh, ksh).astype(u.dtype)
                u = u * lr_scale
                new_shard = apply_updates({"p": p_shard}, {"p": u})["p"]
                new_state = jax.tree_util.tree_map(
                    lambda s: lax.pmean(s, axis) if jnp.issubdtype(
                        jnp.asarray(s).dtype, jnp.floating) else s,
                    new_state)
                if scfg is not None:
                    new_shard = jnp.where(finite, new_shard, p_shard)
                    new_upd = where_tree(finite, new_upd, upd)
                    new_state = where_tree(finite, new_state, state)
                    sc = update_scaler_state(scfg, sc, finite)
                new_params = unravel(
                    lax.all_gather(new_shard, axis, tiled=True)[:k0])
                if scfg is None:
                    return (new_params, new_state, new_upd), (loss, gnorm)
                return (new_params, new_state, new_upd, sc), (loss, gnorm)

            its = it0 + jnp.arange(xs.shape[0])
            inputs = ((xs, ys, ws, masks, its) if has_mask
                      else (xs, ys, ws, its))
            carry = ((params, state, upd_shard) if scfg is None
                     else (params, state, upd_shard, sc_state))
            carry, (losses, gnorms) = lax.scan(
                body, carry, inputs,
                unroll=min(int(xs.shape[0]), unroll, _CHUNK_UNROLL_CAP))
            if scfg is None:
                params, state, upd_shard = carry
            else:
                params, state, upd_shard, sc_state = carry
            return params, state, upd_shard, sc_state, losses, gnorms

        pspec = P()
        cspec = P(None, self.axis)  # [K, B, ...]: shard the batch dim
        if getattr(self, "_opt_shard", None) is None:
            self._opt_shard = self._init_sharded_opt_state()
        sspec = jax.tree_util.tree_map(
            lambda a: part_lib.as_jax(self._opt_leaf_partition(a, k)),
            self._opt_shard)
        out_specs = (pspec, pspec, sspec, pspec, pspec, pspec)
        if has_mask:
            fn = jax.jit(shard_map(
                shard_chunk, mesh=self.mesh,
                in_specs=(pspec, pspec, sspec, pspec, cspec, cspec, cspec,
                          cspec, pspec, pspec),
                out_specs=out_specs))
            return fn

        def no_mask(params, state, upd, sc, xs, ys, ws, it0, lr_scale):
            return shard_chunk(params, state, upd, sc, xs, ys, ws, None,
                               it0, lr_scale)

        fn = jax.jit(shard_map(
            no_mask, mesh=self.mesh,
            in_specs=(pspec, pspec, sspec, pspec, cspec, cspec, cspec,
                      pspec, pspec),
            out_specs=out_specs))
        return lambda p, s, u, sc, xs, ys, ws, masks, it0, lr: fn(
            p, s, u, sc, xs, ys, ws, it0, lr)

    def _flat_meta(self):
        from jax.flatten_util import ravel_pytree

        if not hasattr(self, "_flat_cache"):
            flat, unravel = ravel_pytree(self.net.params)
            self._flat_cache = (int(flat.shape[0]), unravel)
        return self._flat_cache

    def _opt_leaf_partition(self, leaf, k: int) -> part_lib.PartitionSpec:
        """Partition of one sharded-optimizer-state leaf: the padded
        flat [k] moments shard over the replica axis; scalar leaves
        (step counters) replicate."""
        if np.shape(leaf) == (k,):
            return part_lib.zero1(self.axis, size=k)
        return part_lib.replicated()

    def train_state_partition(self) -> dict:
        """ONE `parallel.partition` description of where this trainer's
        training state lives across the replica axis — the spec the
        elastic checkpoint plane records in each snapshot manifest:

        - plain sync DP: params/updater replicated (every replica holds
          the full tree);
        - shard_update (ZeRO-1): the live optimizer state is flat
          moments sharded dim-0 over the data axis — but what
          CHECKPOINTS see is the published per-layer form
          (device-count independent), so the published spec is
          replicated and the live layout is reported under
          ``live_updater``;
        - local-SGD: the per-replica stack is transient (re-stacked
          from the published average on restore), so the published
          spec is replicated too.
        """
        rep = part_lib.replicated()
        out = {"params": rep, "updater": rep,
               "replicas": self.n_devices, "axis": self.axis}
        if self.shard_update and getattr(self, "_opt_shard", None) is not None:
            k = getattr(self, "_flat_k", None)
            out["live_updater"] = jax.tree_util.tree_map(
                lambda a: self._opt_leaf_partition(a, k), self._opt_shard)
        return out

    def checkpoint_partition(self) -> dict:
        """What the resilience supervisor passes to `save_checkpoint`:
        the partition spec of the published trees plus the shard count
        (one shard file per replica, so save IO scales with the
        fleet)."""
        spec = self.train_state_partition()
        return {"shards": self.n_devices,
                "spec": {"params": spec["params"],
                         "updater": spec["updater"]}}

    def resume(self, directory) -> "int | None":
        """Elastic crash-safe resume: restore the newest GOOD checkpoint
        under `directory` into this trainer — whatever replica count
        saved it.  Checksums are verified; corrupt steps are skipped
        (logged) in favor of the previous good one
        (`runtime.checkpoint.load_checkpoint` semantics); the saved
        full-tree state is adopted through `restore_train_state`, which
        rebuilds this trainer's mode-specific carriers (sharded moments,
        local-SGD stacks) for THIS mesh size — the N→M restore.
        Returns the restored step, or None when the directory holds no
        checkpoint yet (fresh start)."""
        from deeplearning4j_tpu.runtime.checkpoint import (
            resume_train_state,
        )

        return resume_train_state(directory, self)

    @staticmethod
    def _is_p_dict(node):
        return isinstance(node, dict) and set(node) == {"p"}

    def _init_sharded_opt_state(self):
        """Optimizer state over the padded flat parameter vector, laid out
        sharded over the data axis (each device holds 1/N of every flat
        moment).

        If `net.updater_state` holds a per-layer state with trained
        moments (the form `finalize()` publishes and checkpoints save —
        device-count independent), ADOPT it by raveling each moment tree
        into the flat layout, so resume keeps the moments even on a
        different mesh size."""
        from jax.flatten_util import ravel_pytree
        from jax.sharding import NamedSharding

        k0, _ = self._flat_meta()
        n = int(self.mesh.shape[self.axis])
        k = self._flat_k = ((k0 + n - 1) // n) * n
        flat0 = jnp.pad(ravel_pytree(self.net.params)[0], (0, k - k0))
        state = self._updater.init({"p": flat0})
        existing = self.net.updater_state
        if existing is not None and (
                jax.tree_util.tree_structure(existing)
                == jax.tree_util.tree_structure(
                    self._updater.init(self.net.params))):
            # per-layer moments -> padded flat moments, position-matched
            # against the flat template via the single-key {"p": .} dicts
            # init({"p": flat}) wraps every moment tree in.
            def adopt(flat_node, layer_node):
                if self._is_p_dict(flat_node):
                    vec = ravel_pytree(layer_node)[0]
                    return {"p": jnp.pad(vec, (0, k - vec.shape[0]))}
                return jnp.asarray(layer_node)  # scalar leaves (step)

            state = jax.tree_util.tree_map(
                adopt, state, existing, is_leaf=self._is_p_dict)
        sh = NamedSharding(self.mesh, P(self.axis))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a), sh)
            if np.ndim(a) == 1 and np.shape(a) == (k,) else jnp.asarray(a),
            state)

    def sync_updater_state_to_net(self) -> None:
        """Publish the sharded optimizer state back to `net.updater_state`
        in the net's own per-layer form (device-count independent) — what
        checkpoints should save.  Called by `finalize()`; cheap enough to
        call at any checkpoint boundary, too expensive for every step."""
        if not self.shard_update or getattr(self, "_opt_shard", None) is None:
            return
        k0, unravel = self._flat_meta()

        def publish(node):
            if self._is_p_dict(node):
                return unravel(jnp.asarray(node["p"])[:k0])
            return node

        self.net.updater_state = jax.tree_util.tree_map(
            publish, self._opt_shard, is_leaf=self._is_p_dict)

    def _build_local_step(self):
        """Local-SGD step: each replica holds ITS OWN params slice (leading
        replica dim sharded over the data axis) and applies its own gradient
        with no collective; divergence is representable, unlike declaring
        unsynced buffers replicated."""
        net = self.net
        updater = self._updater
        axis = self.axis

        def local_step(rep_params, rep_state, rep_upd, x, y, rng, mask,
                       lr_scale):
            # Each shard sees leaves of shape [1, ...]: this replica's slot.
            params = jax.tree_util.tree_map(lambda a: a[0], rep_params)
            state = jax.tree_util.tree_map(lambda a: a[0], rep_state)
            upd_state = jax.tree_util.tree_map(lambda a: a[0], rep_upd)
            rng = jax.random.fold_in(rng, lax.axis_index(axis))

            def lossfn(p):
                return net._objective(p, state, x, y, rng, mask)

            (loss, new_state), grads = jax.value_and_grad(
                lossfn, has_aux=True)(params)
            # mean of per-replica local grad norms (no global gradient
            # exists between syncs in local-SGD mode)
            gnorm = lax.pmean(global_grad_norm(grads), axis)
            updates, upd_state = updater.update(grads, upd_state, params)
            updates = net._apply_lr_multipliers(updates)
            updates = jax.tree_util.tree_map(lambda u: u * lr_scale,
                                             updates)
            params = apply_updates(params, updates)
            loss = lax.pmean(loss, axis)

            def restack(t):
                return jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a)[None], t)

            return (restack(params), restack(new_state), restack(upd_state),
                    loss, gnorm)

        # per-replica stacked state: leading replica dim over the axis
        rspec = part_lib.as_jax(part_lib.sharded(self.axis, dim=0))
        dspec = part_lib.as_jax(part_lib.sharded(self.axis))
        fn = shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(rspec, rspec, rspec, dspec, dspec, P(), dspec, P()),
            out_specs=(rspec, rspec, rspec, P(), P()),
        )
        return jax.jit(fn)

    def _stack(self, tree):
        """[n_devices, ...] copies of every leaf, sharded over the axis."""
        n = self.n_devices
        sh = mesh_lib.batch_sharded(self.mesh, self.axis)
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(
                np.broadcast_to(np.asarray(a), (n,) + np.shape(a)).copy(), sh),
            tree)

    # ---- public API -------------------------------------------------------

    def fit_batch_async(self, x, y, mask=None):
        """One SPMD step over the global batch (dim 0 must be divisible by
        the mesh's data-axis size); returns the loss as a DEVICE array
        without synchronizing, so back-to-back steps pipeline (mirror of
        MultiLayerNetwork.fit_batch_async).  sync_every==1: synchronous
        gradient allreduce.  sync_every>1: local step per replica, params
        averaged every N steps (net.params reflects the average at sync
        points).  Listeners force a host sync only when registered."""
        net = self.net
        self._check_policy()
        x = np.asarray(x)
        y = np.asarray(y)
        if x.shape[0] % self.n_devices:
            raise ValueError(
                f"Global batch {x.shape[0]} not divisible by "
                f"{self.n_devices} devices")
        rng = jax.random.fold_in(
            jax.random.PRNGKey(net.conf.conf.seed), self._iteration)
        xs = mesh_lib.shard_batch(self.mesh, jnp.asarray(x), self.axis)
        ys = mesh_lib.shard_batch(self.mesh, jnp.asarray(y), self.axis)
        ms = (None if mask is None
              else mesh_lib.shard_batch(self.mesh, jnp.asarray(mask), self.axis))
        scale = jnp.asarray(net._lr_scale, jnp.float32)
        if self.sync_every != 1:
            if self._rep is None:
                self._rep = tuple(self._stack(t) for t in
                                  (net.params, net.state, net.updater_state))
            p, s, u = self._rep
            p, s, u, loss, net.last_grad_norm = self._step_fn(
                p, s, u, xs, ys, rng, ms, scale)
            self._rep = (p, s, u)
        elif self.shard_update:
            scfg = net.precision.loss_scale
            if scfg is not None and net._scaler_state is None:
                net._scaler_state = init_scaler_state(scfg)
            sc_state = net._scaler_state if scfg is not None else {}
            (net.params, net.state, self._opt_shard, sc_state, loss,
             net.last_grad_norm) = self._step_fn(
                net.params, net.state, self._opt_shard, sc_state, xs, ys,
                rng, ms, scale)
            if scfg is not None:
                net._scaler_state = sc_state
            # The TRAINER owns the (sharded) optimizer state while this
            # mode runs: the net's copy is cleared (so direct
            # net.fit_batch restarts with fresh moments instead of a
            # structure-mismatch crash) and the trainer registers itself
            # as the owner, so save_model/checkpoint paths
            # (runtime.checkpoint.published_updater_state) pull the
            # sharded moments ON DEMAND at checkpoint boundaries — no
            # per-step publish cost, no finalize() needed for a
            # mid-run checkpoint to keep trained moments.
            net.updater_state = None
            net._updater_state_owner = self
        else:
            scfg = net.precision.loss_scale
            if scfg is not None and net._scaler_state is None:
                net._scaler_state = init_scaler_state(scfg)
            sc_state = net._scaler_state if scfg is not None else {}
            (net.params, net.state, net.updater_state, sc_state, loss,
             net.last_grad_norm) = self._step_fn(
                net.params, net.state, net.updater_state, sc_state, xs, ys,
                rng, ms, scale)
            if scfg is not None:
                net._scaler_state = sc_state
        self._iteration += 1
        if self.sync_every > 1 and self._iteration % self.sync_every == 0:
            self._average_params()
        due = net._due_listeners(self._iteration)
        if due:
            loss_f = float(loss)
            for listener in due:
                listener(self._iteration, loss_f)
        return loss

    def fit_batch(self, x, y, mask=None) -> float:
        """fit_batch_async + host sync on the loss."""
        return float(self.fit_batch_async(x, y, mask))

    def fit(self, data, epochs: int = 1,
            chunk_size: "int | None" = None,
            prefetch: int = 2, chunk_unroll: int = 1
            ) -> "DataParallelTrainer":
        """`chunk_size` routes the loop through the fused multi-step
        driver (runtime/fused.py): K SPMD steps per dispatch, chunks
        device-staged pre-sharded on a background thread.  Padding keeps
        tail batches at the group batch size, so ragged tails that the
        per-batch path rejects (batch % devices != 0) train fine chunked.
        Synchronous modes only (including the default ZeRO-1 plane);
        local-SGD falls back to the per-batch loop."""
        if chunk_size is not None and self.sync_every == 1:
            from deeplearning4j_tpu.runtime.fused import FusedTrainingDriver

            FusedTrainingDriver(self, chunk_size=chunk_size,
                                prefetch=prefetch,
                                unroll=chunk_unroll).fit(data, epochs=epochs)
            self.finalize()
            return self
        for _ in range(epochs):
            for x, y, mask in _as_batches(data):
                self.fit_batch(x, y, mask)
            _maybe_reset(data)
        self.finalize()  # publish trainer-held state back to the net
        return self

    def _averaged_rep(self):
        """Average over the replica axis of the stacked per-replica
        state (float updater/layer state averaged too); pure — does not
        touch self._rep.  Under the default shard_update the parameter
        average IS the sharded master step of the local-SGD sync round:
        each replica reduces and re-emits only its 1/N flat slice
        (psum_scatter + all_gather — bitwise equal to the pmean it
        replaces, same reduction tree), so the sync round's bandwidth
        and FLOPs shard even though the between-sync moments stay local
        and replicated."""
        if self._avg_fn is None:
            axis = self.axis

            def avg_tree(t):
                return jax.tree_util.tree_map(
                    lambda a: lax.pmean(a, axis) if jnp.issubdtype(
                        a.dtype, jnp.floating) else a, t)

            if self.shard_update:
                from jax.flatten_util import ravel_pytree

                n = int(self.mesh.shape[self.axis])
                k0, unravel = self._flat_meta()
                k = ((k0 + n - 1) // n) * n

                def avg(p, s, u):
                    local = jax.tree_util.tree_map(lambda a: a[0], p)
                    flat = jnp.pad(ravel_pytree(local)[0], (0, k - k0))
                    shard = lax.psum_scatter(flat, axis, tiled=True) / n
                    avg_p = unravel(
                        lax.all_gather(shard, axis, tiled=True)[:k0])
                    avg_p = jax.tree_util.tree_map(
                        lambda a: a[None], avg_p)
                    return avg_p, avg_tree(s), avg_tree(u)

                self._avg_fn = jax.jit(shard_map(
                    avg, mesh=self.mesh, in_specs=(P(self.axis),) * 3,
                    out_specs=(P(self.axis),) * 3))
            else:
                self._avg_fn = jax.jit(shard_map(
                    lambda p, s, u: (avg_tree(p), avg_tree(s), avg_tree(u)),
                    mesh=self.mesh, in_specs=(P(self.axis),) * 3,
                    out_specs=(P(self.axis),) * 3))
        return self._avg_fn(*self._rep)

    def _publish_rep(self, rep) -> None:
        """Write one replica-averaged copy to the net (replica 0's slot —
        all equal after _averaged_rep)."""
        p, s, u = rep
        unstack = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)  # noqa: E731
        self.net.params = unstack(p)
        self.net.state = unstack(s)
        self.net.updater_state = unstack(u)

    def _average_params(self) -> None:
        """Every-N parameter averaging for the local-SGD/Hogwild-parity mode
        (the reference's HogWildWorkRouter semantics): the replicas restart
        the next round identical."""
        if self._rep is None:
            return
        self._rep = self._averaged_rep()
        self._publish_rep(self._rep)

    def publish_train_state(self) -> None:
        """Publish a CHECKPOINTABLE snapshot to net.params/state/
        updater_state without perturbing training: local-SGD mode writes
        the replica average to the net but leaves the per-replica `_rep`
        untouched (no extra sync point is injected into the schedule);
        shard_update publishes the sharded moments.  The resilience
        supervisor calls this before every checkpoint so mid-sync-window
        checkpoints carry current (not last-sync) parameters."""
        if self.sync_every > 1 and self._rep is not None:
            self._publish_rep(self._averaged_rep())
        self.sync_updater_state_to_net()

    def set_lr_scale(self, scale: float) -> None:
        """Rollback-backoff hook (see MultiLayerNetwork.set_lr_scale);
        the trainer reads the net's scale each step, so both paths stay
        in sync."""
        self.net.set_lr_scale(scale)

    def restore_train_state(self, step: int, params, updater_state=None,
                            net_state=None) -> None:
        """Adopt checkpointed training state into BOTH the net and the
        trainer's mode-specific carriers — the supervisor's
        rollback/resume entry point.

        - plain sync DP: the net's replicated state IS the training state;
        - local-SGD (sync_every > 1): the stacked per-replica copy is
          dropped and re-stacked from the restored net state at the next
          step (per-replica drift since the checkpoint is not a thing
          worth preserving across a rollback);
        - shard_update: the sharded optimizer state is REPARTITIONED
          from the restored per-layer moments (device-count independent
          — the N→M elastic restore), never installed replicated over a
          sharded step.  `net.updater_state` stays populated (callers
          may hand the net elsewhere after a rollback); the first
          trainer step re-takes ownership."""
        net = self.net
        net.restore_train_state(step, params, updater_state, net_state)
        self._iteration = int(step)
        self._rep = None
        if self.shard_update and self.sync_every == 1:
            self._opt_shard = self._init_sharded_opt_state()

    def finalize(self) -> None:
        """Publish trainer-held state back to the net: averages any
        outstanding per-replica drift (local-SGD mode) and converts the
        sharded optimizer state to the net's per-layer form
        (shard_update mode).  Call before checkpointing or handing the
        net to other training paths; no-op for the plain sync path."""
        if self.sync_every > 1 and self._rep is not None:
            self._average_params()
        self.sync_updater_state_to_net()
        if getattr(self.net, "_updater_state_owner", None) is self:
            self.net._updater_state_owner = None

    def train_state_bytes(self, x=None, mask=None) -> int:
        """PER-REPLICA training-state residency under this trainer's
        update plane: the default ZeRO-1 plane divides the flat
        optimizer/parameter/gradient extents by the data-axis size
        (docs/performance.md "The weight-update sharding cost model");
        the replicated escape hatch and local-SGD report the full
        footprint."""
        from deeplearning4j_tpu.precision.policy import train_state_bytes

        shards = (self.n_devices
                  if self.shard_update and self.sync_every == 1 else 1)
        return train_state_bytes(self.net, x, mask, shards=shards)

    def scaling_report(self) -> dict:
        if self.sync_every != 1:
            collective = f"param-average every {self.sync_every}"
            if self.shard_update:
                collective += " (sharded sync round)"
        elif self.shard_update:
            collective = "psum_scatter+all_gather (zero-1 weight update)"
        else:
            collective = "pmean"
        return {
            "devices": self.n_devices,
            "mesh": dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
            "collective": collective,
        }
