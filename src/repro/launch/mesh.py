"""Production mesh construction + sharding helpers.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


__all__ = ["make_production_mesh", "filter_spec", "shardings_for",
           "batch_partition_spec"]


def make_production_mesh(*, multi_pod: bool = False):
    """Build the production device mesh (Auto axis types)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def filter_spec(spec: P, mesh) -> P:
    """Drop mesh axes a spec references that this mesh doesn't have
    (e.g. 'pod' on the single-pod mesh)."""
    names = set(mesh.axis_names)
    fixed = []
    for s in spec:
        if s is None:
            fixed.append(None)
        elif isinstance(s, tuple):
            keep = tuple(a for a in s if a in names)
            fixed.append(keep if keep else None)
        else:
            fixed.append(s if s in names else None)
    return P(*fixed)


def shardings_for(spec_tree, mesh):
    """Pytree of PartitionSpec -> pytree of NamedSharding on ``mesh``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, filter_spec(s, mesh)),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P))


def sanitize_spec(spec: P, shape, mesh) -> P:
    """filter_spec + drop axes whose size doesn't divide the array dim."""
    sizes = dict(mesh.shape)
    fixed = []
    for i, s in enumerate(filter_spec(spec, mesh)):
        dim = shape[i] if i < len(shape) else 1
        if s is None:
            fixed.append(None)
        elif isinstance(s, tuple):
            pick, prod = [], 1
            for a in s:
                if dim % (prod * sizes[a]) == 0:
                    pick.append(a)
                    prod *= sizes[a]
            fixed.append(tuple(pick) if pick else None)
        else:
            fixed.append(s if dim % sizes[s] == 0 else None)
    return P(*fixed)


def sanitized_shardings(spec_tree, abstract_tree, mesh):
    """NamedShardings with per-dimension divisibility filtering."""
    def one(s, x):
        return NamedSharding(mesh, sanitize_spec(s, x.shape, mesh))
    return jax.tree.map(
        one, spec_tree, abstract_tree,
        is_leaf=lambda s: isinstance(s, P))


def batch_partition_spec(batch_size: int, mesh,
                         trailing: Tuple = ()) -> P:
    """Shard the batch dim over ('pod','data') when divisible, else leave it
    unsharded (batch-1 long-context decode)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if axes and batch_size % size == 0:
        return P(axes, *trailing)
    return P(None, *trailing)
