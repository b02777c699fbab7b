"""Logical-axis sharding rules.

Every parameter / activation in the tree is annotated with *logical* axis
names ("embed", "mlp", "batch", ...).  A :class:`ShardingRules` instance maps
each logical axis onto zero or more *mesh* axes; :func:`divisible_spec` turns
an annotation tuple into a concrete :class:`PartitionSpec` for a given shape,
dropping mesh axes that do not divide the dimension (so the 16x16 production
mesh and the 8-device test mesh both compile from the same annotations) and
dropping mesh axes already consumed by an earlier dimension (so e.g. MoE
weights annotated ``("experts", "embed", "mlp")`` put the ``model`` axis on
the expert dim when E divides it — expert parallelism — and fall back to the
``d_ff`` dim otherwise).

This module must never touch jax device state at import time (no
``jax.devices()``) — same convention as ``launch/mesh.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Union

import jax
from jax.sharding import AxisType, Mesh, PartitionSpec

# one logical axis maps to a mesh axis, an ordered tuple of mesh axes
# (tried left to right), or None / absent (replicated)
MeshAxes = Union[str, tuple, None]


def _as_tuple(v: MeshAxes) -> tuple:
    if v is None:
        return ()
    if isinstance(v, str):
        return (v,)
    return tuple(v)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """A mesh plus the logical-axis -> mesh-axis mapping used on it."""

    mesh: Mesh
    rules: Mapping[str, MeshAxes]

    def mesh_axes(self, logical) -> tuple:
        """Mesh axes a logical axis maps to (empty tuple = replicated)."""
        if logical is None:
            return ()
        return _as_tuple(self.rules.get(logical))


def make_mesh(shape, axes) -> Mesh:
    """A device mesh whose axes are all ``Auto``.

    The rules here place only what model code annotates (through
    ``with_sharding_constraint``) and leave the rest to the partitioner.
    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which every op
    computes its output sharding from its inputs' — and a gather of an
    FSDP table (``embed -> data``) by batch-sharded ids (``batch -> data``)
    then names ``data`` twice in one spec."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def default_rules(mesh: Mesh) -> ShardingRules:
    """Rules covering every logical axis used in the tree, for any mesh built
    from ("pod",) x ("data",) x ("model",) axes (test meshes included).

    * batch-like axes shard over the data axes; fully data-parallel tensors
      ("edges", "table_rows") additionally spill onto "model",
    * parameter "embed" dims shard over the data axes (ZeRO/FSDP),
    * tensor-parallel dims ("heads", "mlp", "experts", "vocab", ...) and the
      activation TP axes ("embed_tp", "act_seq", "kv_seq") take "model".
    """
    names = set(mesh.axis_names)
    data = tuple(a for a in ("pod", "data") if a in names)
    model = tuple(a for a in ("model",) if a in names)
    every = data + model
    return ShardingRules(mesh, {
        # activations
        "batch": data,
        "act_seq": model,
        "embed_tp": model,
        "kv_seq": model,
        "edges": every,
        # parameters
        "embed": data,
        "mlp": model,
        "heads": model,
        "kv_heads": model,
        "experts": model,
        "vocab": model,
        "table_rows": every,
        "layers": None,
    })


def replicated_serving_rules(mesh: Mesh) -> ShardingRules:
    """Serving cells: batch sharded over *every* mesh axis, weights (and all
    other logical axes) replicated — TP only adds collectives for the
    110M-param PreTTR model."""
    every = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    return ShardingRules(mesh, {"batch": every})


def sharded_serving_rules(mesh: Mesh) -> ShardingRules:
    """Scale-out serving cells: a mesh with a ``"shard"`` axis, one index
    shard (and one ``ShardWorker``) per position along it.

    The *query path* rules: ``batch`` (packed micro-batch rows) shards over
    the non-``shard`` axes; **nothing** maps onto ``"shard"`` — that axis
    is not a tensor-parallel dimension but a *data-ownership* one.  Each
    worker holds a full replica of the (small) model parameters and the
    exclusive slice of the (huge) document-side state, so doc bytes never
    cross the shard axis; only candidate ids travel to a shard and only
    ``[rows]`` float32 scores travel back (the router's all-gather)."""
    if "shard" not in mesh.axis_names:
        raise ValueError(
            f"sharded serving needs a mesh with a 'shard' axis; got axes "
            f"{tuple(mesh.axis_names)}")
    rest = tuple(a for a in mesh.axis_names if a != "shard")
    return ShardingRules(mesh, {"batch": rest})


def serving_shard_devices(mesh: Mesh) -> list:
    """One representative device per serving shard -> list of length
    ``mesh.shape["shard"]``, in shard order.

    :class:`~repro.serving.sharded.ShardWorker` ``i`` pins its params,
    doc-cache pools, and staged batches to ``devices[i]`` via explicit
    ``jax.device_put`` (thread-safe, unlike the thread-local
    ``jax.default_device``), so N workers score concurrently with zero
    cross-device traffic on the doc side.  Axes other than ``"shard"``
    are replica dimensions for the query path; the worker uses each
    shard's first replica device."""
    if "shard" not in mesh.axis_names:
        raise ValueError(
            f"sharded serving needs a mesh with a 'shard' axis; got axes "
            f"{tuple(mesh.axis_names)}")
    ax = mesh.axis_names.index("shard")
    devs = mesh.devices
    # index every non-shard axis at 0, keep the shard axis whole
    sel = tuple(slice(None) if i == ax else 0
                for i in range(devs.ndim))
    return list(devs[sel].reshape(-1))


def divisible_spec(rules: ShardingRules, axes, shape) -> PartitionSpec:
    """Annotation tuple + concrete shape -> PartitionSpec.

    A mesh axis is kept on a dimension only if (a) it was not already placed
    on an earlier dimension of this spec and (b) the dimension size is
    divisible by the product of mesh-axis sizes accumulated on it so far.
    """
    mesh_shape = dict(rules.mesh.shape)
    axes = _as_tuple(axes)
    used: set = set()
    parts = []
    for i, dim in enumerate(tuple(shape)):
        logical = axes[i] if i < len(axes) else None
        kept = []
        size = 1
        for a in rules.mesh_axes(logical):
            n = mesh_shape.get(a)
            if n is None or a in used:
                continue
            if dim % (size * n) == 0:
                kept.append(a)
                size *= n
                used.add(a)
        parts.append(tuple(kept) if len(kept) > 1 else
                     (kept[0] if kept else None))
    return PartitionSpec(*parts)
