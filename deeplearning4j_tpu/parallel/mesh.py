"""Device-mesh helpers.

The reference's distribution fabric was Spark broadcast/accumulators, Akka
actors over Hazelcast maps, and YARN Avro RPC (SURVEY §2.3) — all moving full
dense parameter vectors through a central master, O(workers x params). The
TPU-native fabric is a `jax.sharding.Mesh` over the chips: gradient exchange
becomes `lax.pmean` over ICI, compiled into the step function itself; there
is no master and no parameter server.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel import partition as part_lib


def make_mesh(
    shape: Optional[Tuple[int, ...]] = None,
    axis_names: Sequence[str] = ("data",),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a mesh over the available devices.

    Default: 1-D data-parallel mesh over all devices. For hybrid
    parallelism pass e.g. shape=(4, 2), axis_names=("data", "model").
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),)
    if int(np.prod(shape)) != len(devices):
        raise ValueError(
            f"Mesh shape {shape} needs {int(np.prod(shape))} devices, "
            f"have {len(devices)}")
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def named_sharding(mesh: Mesh, spec) -> NamedSharding:
    """NamedSharding from either spec vocabulary — the package's
    `partition.PartitionSpec` or a raw `jax.sharding.PartitionSpec`."""
    return NamedSharding(mesh, part_lib.as_jax_leaf(spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return named_sharding(mesh, part_lib.replicated())


def batch_sharded(mesh: Mesh, axis: str = "data") -> NamedSharding:
    return named_sharding(mesh, part_lib.sharded(axis))


def shard_batch(mesh: Mesh, tree, axis: str = "data"):
    """Place host arrays so dim 0 shards over the mesh's data axis."""
    sh = batch_sharded(mesh, axis)
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sh) if a is not None else None, tree,
        is_leaf=lambda a: a is None)


def replicate(mesh: Mesh, tree):
    sh = replicated(mesh)
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off — the one form every
    shard_map user in the package calls (psum'd gradients transpose to
    psum only without the check)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def round_batch_to_mesh(batch_size: int, mesh: Mesh) -> int:
    """Smallest batch >= batch_size divisible into equal shards over the
    mesh's devices."""
    n = mesh.devices.size
    return ((batch_size + n - 1) // n) * n


def sparse_allgather_step(mesh: Optional[Mesh], deltas_fn, apply_fn,
                          n_state: int, n_sharded: int, n_scalar: int = 0,
                          with_key: bool = False):
    """Data-parallel harness for sparse embedding updates (shared by
    Word2Vec and GloVe `mesh=`): builds ``step(*state, *scalars,
    *sharded[, key]) -> (*new_state, loss)`` where

    - ``deltas_fn(same args) -> (loss, aux)`` computes per-shard sparse
      pieces (aux: any pytree of [B, ...] arrays — row indices, deltas),
    - ``apply_fn(*state, *scalars, aux) -> new_state tuple`` scatters
      them into the replicated state.

    mesh=None applies directly.  With a mesh, the trailing ``n_sharded``
    args shard over the FIRST axis, loss is psum'd, aux is all_gathered
    (tiled — O(B) comms, never a dense table), and every replica applies
    the identical scatter, so replicated state never diverges.  with_key
    folds the axis index into a trailing PRNG key."""

    def single(*args):
        lead = args[:n_state + n_scalar]
        loss, aux = deltas_fn(*args)
        return (*apply_fn(*lead, aux), loss)

    if mesh is None:
        return single
    axis = mesh.axis_names[0]

    def sharded(*args):
        if with_key:
            *rest, key = args
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))
            args = (*rest, key)
        lead = args[:n_state + n_scalar]
        loss, aux = deltas_fn(*args)
        loss = jax.lax.psum(loss, axis)
        aux = jax.tree_util.tree_map(
            lambda a: jax.lax.all_gather(a, axis, tiled=True), aux)
        return (*apply_fn(*lead, aux), loss)

    in_specs = ((P(),) * (n_state + n_scalar) + (P(axis),) * n_sharded
                + ((P(),) if with_key else ()))
    return shard_map(sharded, mesh=mesh, in_specs=in_specs, out_specs=P())
