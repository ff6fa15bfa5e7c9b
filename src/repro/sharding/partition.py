"""Logical-axis -> mesh-axis partitioning rules.

Model code annotates every parameter dimension with a *logical* name
(``repro.models.*`` init functions return spec trees).  This module maps
those to concrete ``PartitionSpec``s for a given mesh and sharding profile:

profile   embed-dim ('embed')        everything tensor-parallel ('heads',
                                     'ff', 'experts', 'vocab', 'mamba_*')
-------   -------------------------  ------------------------------------
dp        replicated                 'model'
fsdp      'data'                     'model'
zero3     ('pod','data') when the    'model'
          mesh has a pod axis

Optimizer state inherits the parameter specs (ZeRO: optimizer shards
wherever the parameter does).  Batch dims shard over all data-parallel axes.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax._src.mesh import thread_resources as _thread_resources

TENSOR_AXES = {"heads", "ff", "experts", "vocab", "mamba_inner", "mamba_heads"}
# head-count axes: shard over 'model' only when the count divides the axis
# (GQA kv heads usually don't — they stay replicated, Megatron-style)
HEAD_AXES = {"q_heads", "kv_heads"}


def ambient_mesh() -> Mesh | None:
    mesh = _thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def model_axis_size() -> int:
    mesh = ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]


def shard_dim(x, dim: int, axis: str = "model"):
    """Constrain one dim of x to shard over a mesh axis, all others
    UNCONSTRAINED (so batch/data sharding propagates through).

    No-op when there is no ambient mesh / named axis, when the dim doesn't
    divide it, or when the dim is degenerate.
    """
    mesh = ambient_mesh()
    if mesh is None or axis not in mesh.axis_names:
        return x
    m = mesh.shape[axis]
    if x.shape[dim] == 1 or x.shape[dim] % m:
        return x
    spec = [P.UNCONSTRAINED] * x.ndim
    spec[dim] = axis
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def seq_shard(x, dim: int = 1):
    """Context parallelism: shard a sequence dim over 'model'."""
    return shard_dim(x, dim, "model")


def batch_shard(x, dim: int = 0):
    """Constrain the batch dim over the data-parallel axes.

    The embedding gather otherwise DROPS batch sharding when the table's
    embed axis occupies 'data' (fsdp/zero3 profiles): GSPMD propagates the
    table operand's sharding into the output and replicates batch — every
    downstream activation then runs data-replicated (§Perf 1.2, measured
    16x flop inflation at phi3.5 train_4k).
    """
    mesh = ambient_mesh()
    if mesh is None:
        return x
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    # drop trailing axes until the product divides the batch
    while axes:
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        if x.shape[dim] % prod == 0:
            break
        axes.pop()
    if not axes:
        return x
    spec = [P.UNCONSTRAINED] * x.ndim
    spec[dim] = tuple(axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _map_axis(name, profile: str, mesh: Mesh, dim_size: int | None = None):
    if name is None or name in ("layers", "embed_nosplit"):
        return None
    if name in HEAD_AXES:
        if "model" in mesh.axis_names and dim_size is not None \
                and dim_size % mesh.shape["model"] == 0:
            return "model"
        return None
    if name in TENSOR_AXES:
        return "model" if "model" in mesh.axis_names else None
    if name == "embed":
        if profile == "dp":
            return None
        if profile == "zero3":
            ax = data_axes(mesh)
            return ax if len(ax) > 1 else (ax[0] if ax else None)
        return "data" if "data" in mesh.axis_names else None
    raise ValueError(f"unknown logical axis {name!r}")


def spec_to_pspec(spec: tuple, profile: str, mesh: Mesh, shape=None) -> P:
    sizes = shape if shape is not None else (None,) * len(spec)
    return P(*(_map_axis(a, profile, mesh, d) for a, d in zip(spec, sizes)))


def param_shardings(specs: Any, profile: str, mesh: Mesh, shapes: Any = None):
    """Map a logical spec tree to a NamedSharding tree.

    ``shapes`` (a matching tree of ShapeDtypeStructs/arrays) lets the
    head-count axes decide divisibility; without it they stay replicated.
    """
    def is_spec(t):
        return isinstance(t, tuple) and all(
            a is None or isinstance(a, str) for a in t
        )

    if shapes is None:
        return jax.tree.map(
            lambda s: NamedSharding(mesh, spec_to_pspec(s, profile, mesh)),
            specs,
            is_leaf=is_spec,
        )
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    assert len(flat_shapes) == len(flat_specs), "specs/shapes tree mismatch"
    out = [
        NamedSharding(mesh, spec_to_pspec(s, profile, mesh, x.shape))
        for s, x in zip(flat_specs, flat_shapes)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_pspec(mesh: Mesh, batch_size: int) -> P:
    """Shard the batch dim over every data axis that divides it."""
    axes = []
    for a in data_axes(mesh):
        sz = mesh.shape[a]
        if batch_size % sz == 0:
            axes.append(a)
            batch_size //= sz
    return P(tuple(axes) if axes else None)


def cache_pspec(mesh: Mesh, batch: int, seq: int, kv_heads: int) -> P:
    """KV-cache (B, S, KV, HD) sharding: batch over data axes; the KV-head
    dim over 'model' when divisible, else the sequence dim (emergent
    sequence-parallel decode attention; DESIGN.md §6.3)."""
    bspec = batch_pspec(mesh, batch)
    m = mesh.shape.get("model", 1)
    if kv_heads % m == 0:
        return P(bspec[0] if bspec else None, None, "model", None)
    if seq % m == 0:
        return P(bspec[0] if bspec else None, "model", None, None)
    return P(bspec[0] if bspec else None, None, None, None)
