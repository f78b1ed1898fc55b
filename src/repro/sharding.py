"""Sharding-constraint helpers usable from model code without a mesh.

All model code calls `constrain(x, spec)`; outside a mesh context (CPU
smoke tests) it is a no-op, inside `jax.set_mesh(...)` it becomes a `with_sharding_constraint`. Axis
names: 'pod' (outer replica/data), 'data' (batch), 'model'
(tensor/expert/neuron/seq shards).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


__all__ = ["current_mesh", "batch_axes", "constrain", "constrain_batch",
           "BATCH"]


def current_mesh():
    """The ambient mesh entered with `jax.set_mesh`, or None outside
    any."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return None
    return m


def batch_axes(mesh=None):
    """The axis names that shard the global batch in the current mesh."""
    m = mesh or current_mesh()
    if m is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in m.axis_names)


def _filter_spec(spec: P, mesh, shape=None) -> P:
    """Drop axis names that don't exist in the mesh, and (when `shape`
    is given) axes whose size doesn't evenly divide the dimension —
    e.g. batch=1 long-context decode replicates over 'data'."""
    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.shape.values())) \
        if hasattr(mesh.shape, "values") else dict(mesh.shape)

    def axsize(e):
        if isinstance(e, (tuple, list)):
            n = 1
            for a in e:
                n *= sizes.get(a, 1)
            return n
        return sizes.get(e, 1)

    def keep(e, dim):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in names)
            e = kept if kept else None
        else:
            e = e if e in names else None
        if e is not None and dim is not None and dim % axsize(e) != 0:
            return None
        return e

    dims = list(shape) + [None] * (len(spec) - len(shape)) \
        if shape is not None else [None] * len(spec)
    return P(*[keep(e, d) for e, d in zip(spec, dims)])


def constrain(x, spec: P):
    m = current_mesh()
    if m is None:
        return x
    spec = _filter_spec(spec, m, shape=getattr(x, "shape", None))
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, spec))


def constrain_batch(x):
    """Shard the leading (batch) dim over pod+data."""
    m = current_mesh()
    if m is None:
        return x
    spec = P(batch_axes(m), *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, spec))


BATCH = ("pod", "data")
