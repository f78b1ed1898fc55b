"""Production mesh construction (deliverable e).

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — 'pod'
is the outer replica/data axis crossing the ICI/DCN boundary.

A FUNCTION, not a module constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh for CPU smoke runs of mesh-aware code paths."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_serving_mesh(n_model: int, n_data: int = 1):
    """(data, model) mesh for the serving plane: 'model' is the
    tensor-parallel axis, 'data' the replica-routing axis
    (DESIGN.md §3/§5).

    Uses the first n_data*n_model visible devices (on CPU runs, force
    them with XLA_FLAGS=--xla_force_host_platform_device_count=N before
    the first jax call)."""
    need = n_data * n_model
    avail = jax.device_count()
    if need > avail:
        raise ValueError(
            f"serving mesh ({n_data}, {n_model}) needs {need} devices "
            f"but only {avail} are visible")
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:need])


def dispatch_groups(mesh) -> int:
    """Data-local MoE dispatch groups for a mesh: one token group per
    (pod x data) row, so the dispatch buffer shards over the batch
    axes while the expert dim shards over 'model' (EP). This is the
    single source of truth for `cfg.moe_dispatch_groups` — the dry-run
    derives the launcher-global group count from the production mesh,
    and each serving replica derives its own (its submesh has
    data == 1, so replica dispatch is one local group and dp x tp x ep
    composes). Meshless hosts dispatch in one group."""
    if mesh is None:
        return 1
    shape = dict(mesh.shape)
    n = 1
    for ax in ("pod", "data"):
        n *= shape.get(ax, 1)
    return int(n)


def replica_submeshes(mesh):
    """One (1, n_model) tensor-parallel submesh per 'data'-axis row of
    `mesh` — replica r keeps exactly the devices of row r, so a
    replica-routed engine places each serving stack on its own slice
    of the parent mesh."""
    import numpy as np
    shape = dict(mesh.shape)
    n_data = shape.get("data", 1)
    n_model = shape.get("model", 1)
    devs = np.asarray(mesh.devices).reshape(n_data, n_model)
    return [jax.make_mesh((1, n_model), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2,
                          devices=list(devs[r]))
            for r in range(n_data)]


# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link
