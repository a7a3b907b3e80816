"""Named sharding rules: logical array axes -> mesh axes (ISSUE 12).

The problem with raw ``ParamAttr.sharding`` tuples is that every call site
hard-codes MESH axis names ("model", "expert") into model code, so the same
model cannot move between a data-only training mesh, a 2-D dp x tp mesh and
a serving TP mesh without editing each tuple.  The fix is the DEFAULT_RULES
pattern (the t5x/flax ``logical_axis_rules`` idiom):

  * arrays declare LOGICAL axis names once at creation
    (``ParamAttr(logical_axes=("embed", "mlp"))``, or
    ``ServableLM.param_logical_axes()`` for the serving LM), and
  * ONE rules table maps logical names to mesh axes for the deployment at
    hand — ``{"batch": "data", "heads": "model", "mlp": "model", ...}``.

Training (ShardedUpdater canonical seams, elastic resize, checkpoints) and
serving then share a single sharding vocabulary: re-deploying the same
model on a different mesh is a rules-table edit, not a model edit.

Resolution semantics:

  * a logical name maps through the table to a mesh axis (or None =
    replicated);
  * a resolved mesh axis NOT present in the target mesh resolves to
    replicated — that is what lets a model declaring ``heads: "model"``
    run unchanged on the single-axis data mesh the CPU tests use and on a
    real TP mesh (the rules name the full vocabulary, the mesh decides
    which entries bite);
  * a name in neither the table nor the mesh axes raises, naming the
    parameter — typos must not silently replicate;
  * legacy ``ParamAttr.sharding`` tuples (mesh-axis names used directly)
    keep working as a deprecation shim: every mesh-axis name is implicitly
    a logical name that resolves to itself, so old call sites translate
    INTO the table rather than bypassing it.

``pipeline`` is deliberately present but unmapped: PARITY §2.5 reserves a
pipeline-parallel axis, and reserving it as a rules-table entry means the
day the mesh grows a "pipe" axis the mapping is one line here."""

from __future__ import annotations

import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import AXES, make_mesh

# the legacy ParamAttr(sharding=...) shim warns EXACTLY once per process —
# per-call warnings would spam every step trace of a legacy model, and
# python's default "once" filter dedups per call SITE, not per process
_legacy_sharding_warned = False


def warn_legacy_sharding(param: str) -> None:
    """One DeprecationWarning per process for raw mesh-axis ParamAttr.sharding
    tuples (they still resolve through the rules table's identity shim)."""
    global _legacy_sharding_warned
    if _legacy_sharding_warned:
        return
    _legacy_sharding_warned = True
    warnings.warn(
        f"ParamAttr(sharding=...) mesh-axis tuples are deprecated (first "
        f"seen on {param!r}): declare ParamAttr(logical_axes=...) and let "
        f"the rules table (parallel/rules.py DEFAULT_RULES) map logical "
        f"axes to mesh axes",
        DeprecationWarning,
        stacklevel=3,
    )

# the one serving+training sharding vocabulary. Values are mesh axis names
# or None (replicated).
DEFAULT_RULES: Dict[str, Optional[str]] = {
    "batch": "data",      # batch rows over the data axis
    "heads": "model",     # attention query heads (column-parallel qkv)
    "kv_heads": "model",  # KV heads — the paged KV pool shards this too
    "mlp": "model",       # MLP hidden (column-parallel w1 / row-parallel w2)
    "vocab": "model",     # embed rows / unembed columns
    "embed": None,        # d_model stays replicated (activations are small)
    "length": None,       # sequence positions (the seq axis exists for ring
                          # attention; decode activations never shard it)
    "expert": "expert",   # row-sharded embedding tables (parallel/embedding)
    "pipeline": None,     # RESERVED (PARITY §2.5): maps to a mesh axis the
                          # day pipeline parallelism lands — a table edit
}


class ShardingRules:
    """A logical-axis -> mesh-axis table with validated resolution.

    ``spec_for`` is the single resolution seam: DataParallel.param_sharding
    (training) and ServableLM.param_sharding (serving) both call it, so the
    two runtimes cannot drift on what a logical name means."""

    def __init__(self, rules: Optional[Dict[str, Optional[str]]] = None):
        self.table: Dict[str, Optional[str]] = dict(
            DEFAULT_RULES if rules is None else rules
        )

    def with_overrides(self, **overrides: Optional[str]) -> "ShardingRules":
        return ShardingRules({**self.table, **overrides})

    def mesh_axis(
        self,
        logical: Optional[str],
        mesh: Optional[Mesh] = None,
        param: str = "<array>",
    ) -> Optional[str]:
        """One logical name -> the mesh axis it shards over (None =
        replicated). Unknown names that are not mesh axes raise, naming the
        parameter; known names whose mesh axis is absent from `mesh` resolve
        to replicated (see module docstring)."""
        if logical is None:
            return None
        if logical in self.table:
            axis = self.table[logical]
        elif logical in AXES or (mesh is not None and logical in mesh.axis_names):
            # deprecation shim: a raw mesh-axis name (legacy
            # ParamAttr.sharding tuples) is its own logical name
            axis = logical
        else:
            raise KeyError(
                f"unknown logical sharding axis {logical!r} for {param!r}: "
                f"not in the rules table {sorted(self.table)} and not a mesh "
                "axis — add a rules entry or fix the axis name"
            )
        if axis is not None and mesh is not None and axis not in mesh.axis_names:
            return None  # the mesh has no such axis: this entry does not bite
        return axis

    def spec_for(
        self,
        logical_axes: Sequence[Optional[str]],
        mesh: Optional[Mesh] = None,
        ndim: Optional[int] = None,
        param: str = "<array>",
    ) -> P:
        """Resolve a logical-axes tuple to a PartitionSpec.

        A spec LONGER than the array's rank is rejected loudly (the silent
        truncation this replaces dropped trailing axes — a param declared
        ("mlp", "embed") on a 1-D bias would silently shard over "mlp");
        shorter specs pad with None (trailing dims replicated), the
        documented convenience."""
        axes = tuple(logical_axes)
        if ndim is not None:
            if len(axes) > ndim:
                raise ValueError(
                    f"sharding spec {axes} for {param!r} names {len(axes)} "
                    f"axes but the array has rank {ndim} — rank-mismatched "
                    "specs are rejected (they used to be silently truncated)"
                )
            axes = axes + (None,) * (ndim - len(axes))
        return P(*[self.mesh_axis(a, mesh, param) for a in axes])

    def sharding_for(
        self,
        mesh: Mesh,
        logical_axes: Sequence[Optional[str]],
        ndim: Optional[int] = None,
        param: str = "<array>",
    ) -> NamedSharding:
        return NamedSharding(
            mesh, self.spec_for(logical_axes, mesh, ndim, param)
        )


def make_tp_mesh(
    tp: int,
    data: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """The 2-D ("data", "model") mesh the rules table targets: `tp` chips on
    the model axis, `data` replicas on the data axis (serving uses data=1 —
    replica scale-out is the router's job, ROADMAP item 1). Axis order
    follows mesh.AXES so the data axis stays the outermost, the layout every
    trainer/updater assumes."""
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tensor-parallel size must be >= 1, got {tp}")
    return make_mesh({"data": int(data), "model": tp}, devices=devices)
