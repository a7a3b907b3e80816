"""Sharded embedding tables — the EP ancestor in the reference (SURVEY §2.5):
row-sharded embeddings on pservers (SparseRemoteParameterUpdater,
RemoteParameterUpdater.h:265; SparsePrefetchRowCpuMatrix prefetch;
--ports_num_for_sparse).

TPU-native: the table's rows are sharded over a mesh axis ('expert'); lookup
runs under shard_map — each device gathers the ids that fall in its row range
and a psum combines the partial one-hot results. Autodiff of the masked
gather yields exactly the row-sparse gradient scatter the pserver protocol
implements by hand; XLA keeps it as a scatter-add on the owning shard."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# feature-detects the check_vma/check_rep kwarg rename across jax versions
from jax import shard_map

Array = jax.Array


def shard_table(table: Array, mesh: Mesh, axis: str = "expert") -> Array:
    """Place a [V, D] table row-sharded over `axis` (V must divide evenly)."""
    n = mesh.shape[axis]
    if table.shape[0] % n != 0:
        raise ValueError(
            f"vocab {table.shape[0]} not divisible by mesh axis {axis!r} ({n})"
        )
    return jax.device_put(table, NamedSharding(mesh, P(axis, None)))


def sharded_lookup(
    table: Array,  # [V, D] sharded over rows on `axis`
    ids: Array,  # [...] int32 (replicated or batch-sharded on another axis)
    mesh: Mesh,
    axis: str = "expert",
) -> Array:
    """ids → [..., D]. Each shard serves its own row range; psum combines."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
        check_vma=False,
    )
    def lookup(tab, idx):
        rows = tab.shape[0]
        my = lax.axis_index(axis)
        lo = my * rows
        local = idx - lo
        mine = (local >= 0) & (local < rows)
        safe = jnp.clip(local, 0, rows - 1)
        part = jnp.where(mine[..., None], tab[safe], 0.0)
        return lax.psum(part, axis)

    return lookup(table, ids)


class ShardedEmbeddingState:
    """Bundles the sharded table with its mesh/axis for the layer seam."""

    def __init__(self, table: Array, mesh: Mesh, axis: str = "expert"):
        self.mesh = mesh
        self.axis = axis
        self.table = shard_table(table, mesh, axis)

    def __call__(self, ids: Array) -> Array:
        return sharded_lookup(self.table, ids, self.mesh, self.axis)
