"""Registry of traceable entry points for the semantic analysis tier.

Every clock-driven entry point the serving stack stages — the kernel
wrappers in kernels/ops.py, the jnp and pallas cold paths
(core/sparse_ffn.ffn_hybrid, whose shard_map body carries the one
per-layer psum), and every ServingFamily's decode step — is registered
here as a TraceEntry: a lazy builder returning (fn, args) plus the
entry's *declared* collective budget. jaxpr_rules traces each entry to
a ClosedJaxpr under its declared mesh and asserts the declaration.

Coverage is the grid the golden tests sample: representative plan
buckets (core/adaptation.DEFAULT_BUCKETS) x mesh shapes tp/ep in
{1, 2} x cold-path backends (each family's ServingFamily.backends)
x storage dtypes for the fused kernel. Entries needing more devices
than the process has are skipped by `entries()` — the CI semantic job
forces 8 host devices so the full grid runs there.

The KERNEL_ENTRY_POINTS tuple below is the drift anchor: the AST rule
trace-registry-drift (drift.py) fails the gate when kernels/ops.py
exports an entry point not named here — a new kernel cannot ship
without semantic coverage, mirroring the family/bench drift rules.

Declared budgets (verified ground truth, not aspiration):
tp1/ep1 traces contain zero collectives (no mesh, no shard_map);
tp2/ep2 dense and vlm traces contain exactly one f32 psum (the cold
path's output reduction, inside the layer scan body = once per layer)
plus one integer all_gather (the selected-cluster ids); moe ep2
contains the one f32 psum only (expert combine; ids stay local).
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = ["TraceEntry", "KERNEL_ENTRY_POINTS", "entries",
           "entry_names"]

# one name per kernels/ops.py __all__ export — the trace-registry-drift
# AST rule matches these literals against that __all__
KERNEL_ENTRY_POINTS = ("cluster_gather_ffn", "cluster_gather_ffn_grouped",
                       "fused_cold_ffn", "dense_ffn")


@dataclass(frozen=True)
class TraceEntry:
    """One traceable entry point plus its declared post-trace facts."""
    name: str                      # e.g. "decode/dense/jnp/tp2/b4"
    build: Callable                # () -> (fn, example_args)
    n_devices: int = 1             # mesh 'model' axis size (1 = no mesh)
    psums: int = 0                 # exact psum count the trace must show
    all_gathers: int = 0           # exact all_gather count
    clock_driven: bool = True      # jaxpr-callback rule applies
    const_cap_bytes: int = 1 << 20
    trace_ctx: Callable = None     # extra context-manager factory

    def trace(self):
        """Stage to a ClosedJaxpr under the declared mesh."""
        from repro.launch.mesh import make_serving_mesh
        fn, args = self.build()
        mesh = (make_serving_mesh(self.n_devices)
                if self.n_devices > 1 else None)
        mesh_ctx = (jax.set_mesh(mesh) if mesh is not None
                    else contextlib.nullcontext())
        extra = self.trace_ctx() if self.trace_ctx else \
            contextlib.nullcontext()
        with mesh_ctx, extra:
            return jax.make_jaxpr(fn)(*args)


# ------------------------------------------------- kernel entries ----
# tiny MXU-shaped operands: B=2 tokens, D=32, R=3 bundles, cs=8,
# G=2 groups x nc_g=3 clusters, predictor rank 4

def _kernel_operands():
    k = jax.random.key(0)
    G, nc_g, cs, R, D, r = 2, 3, 8, 3, 32, 4
    x = jnp.zeros((2, D), jnp.float32)
    wc = jax.random.normal(k, (G, nc_g, cs, R, D), jnp.float32)
    A = jnp.zeros((D, r), jnp.float32)
    Bp = jnp.zeros((r, G * nc_g * cs), jnp.float32)
    return x, wc, A, Bp


def _build_dense_ffn():
    from repro.kernels.ops import dense_ffn
    x = jnp.zeros((2, 32), jnp.float32)
    w = jnp.zeros((16, 3, 32), jnp.float32)
    return (lambda xx, ww: dense_ffn(xx, ww, activation="silu",
                                     interpret=True)), (x, w)


def _build_cluster_gather():
    from repro.kernels.ops import cluster_gather_ffn
    x = jnp.zeros((2, 32), jnp.float32)
    w = jnp.zeros((48, 3, 32), jnp.float32)
    idx = jnp.zeros((2,), jnp.int32)
    return (lambda xx, ww, ii: cluster_gather_ffn(
        xx, ww, ii, activation="silu", cluster_size=8,
        interpret=True)), (x, w, idx)


def _build_cluster_gather_grouped():
    from repro.kernels.ops import cluster_gather_ffn_grouped
    x, wc, _, _ = _kernel_operands()
    cidx = jnp.zeros((2, 2), jnp.int32)
    return (lambda xx, ww, ii: cluster_gather_ffn_grouped(
        xx, ww, ii, activation="silu", interpret=True)), (x, wc, cidx)


def _build_fused(storage_dtype: str, mode: str = "relu"):
    def build():
        from repro.kernels.ops import fused_cold_ffn
        x, wc, A, Bp = _kernel_operands()
        quant = {}
        if storage_dtype != "fp16":
            quant["wq"] = jnp.zeros(wc.shape, jnp.int8)
            quant["wsc"] = jnp.ones(wc.shape[:-1], jnp.float32)
        if storage_dtype == "int4-mixed":
            quant["wout"] = jnp.zeros(wc.shape, jnp.float16)
        fn = lambda xx, ww, aa, bb: fused_cold_ffn(  # noqa: E731
            xx, ww, aa, bb, activation="silu", mode=mode, kc=2,
            interpret=True, **quant)
        return fn, (x, wc, A, Bp)
    return build


def _kernel_entries():
    yield TraceEntry("kernel/dense_ffn", _build_dense_ffn)
    yield TraceEntry("kernel/cluster_gather_ffn", _build_cluster_gather)
    yield TraceEntry("kernel/cluster_gather_ffn_grouped",
                     _build_cluster_gather_grouped)
    for sd in ("fp16", "int8", "int4-mixed"):
        yield TraceEntry(f"kernel/fused_cold_ffn/{sd}", _build_fused(sd))
    yield TraceEntry("kernel/fused_cold_ffn/fp16-cats",
                     _build_fused("fp16", mode="cats"))


# ---------------------------------------------- cold-path entries ----

def _build_cold(backend: str, mode: str = "relu"):
    def build():
        from repro.core.clusters import make_plan
        from repro.core.sparse_ffn import ffn_hybrid, init_ffn
        D, d_ff = 32, 256
        params = init_ffn(jax.random.key(0), D, d_ff, "silu",
                          jnp.float32, predictor_rank=4)
        plan = make_plan(d_ff, 0.25, 0.25, 16, groups=4,
                         backend=backend)
        x = jnp.zeros((2, D), jnp.float32)
        fn = lambda p, xx: ffn_hybrid(  # noqa: E731
            p, xx, "silu", mode, plan, return_indices=True)
        return fn, (params, x)
    return build


def _cold_entries():
    for backend in ("jnp", "pallas"):
        for tp in (1, 2):
            n_coll = 1 if tp > 1 else 0
            yield TraceEntry(f"cold/{backend}/tp{tp}",
                             _build_cold(backend), n_devices=tp,
                             psums=n_coll, all_gathers=n_coll)
    yield TraceEntry("cold/jnp/tp2/cats", _build_cold("jnp", "cats"),
                     n_devices=2, psums=1, all_gathers=1)


# ------------------------------------------- decode-step entries ----

@functools.lru_cache(maxsize=None)
def _family_setup(family: str):
    """One tiny reduced-config model per family, shared across every
    mesh shape / bucket / backend variant of its decode entries."""
    from repro.configs import get_config
    from repro.serving.families import default_archs, serving_family
    cfg = get_config(default_archs()[family]).reduced()
    fam = serving_family(cfg)
    model = fam.make_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, fam, model, params


def _build_decode(family: str, backend: str, bucket: int):
    def build():
        from repro.core.clusters import make_plan
        cfg, fam, model, params = _family_setup(family)
        plan = fam.build_plan(cfg)
        if cfg.family != "moe":
            # group-aligned bucket plans so tp in {1, 2} both divide
            # the neuron groups (the test_distributed tp pattern)
            base = make_plan(cfg.d_ff, 0.25, 0.25,
                             cfg.sparse_ffn.cluster_size, groups=4,
                             backend=backend)
            plan.plans = {b: base for b in plan.plans}
        step = fam.make_decode_step(cfg)
        pb = plan.plan_for_batch(bucket)
        tokens = jnp.zeros((bucket, 1), jnp.int32)
        cache = model.init_cache(bucket, 32)
        mask = jnp.ones((bucket,), bool)
        fn = lambda p, t, c, m: step(p, t, c, pb, m)  # noqa: E731
        return fn, (params, tokens, cache, mask)
    return build


def _decode_entries():
    from repro.core.adaptation import DEFAULT_BUCKETS
    buckets = (DEFAULT_BUCKETS[0], DEFAULT_BUCKETS[2])     # 1 and 4
    axis = {"dense": "tp", "vlm": "tp", "moe": "ep"}
    grid = [
        # (family, backend, tp, buckets) — moe psums=1/ag=0 at ep2,
        # dense/vlm psums=1/ag=1 at tp2 (id gather), all-zero at 1
        ("dense", "jnp", 1, buckets[:1]),
        ("dense", "jnp", 2, buckets),
        ("dense", "pallas", 1, buckets[:1]),
        ("dense", "pallas", 2, buckets[:1]),
        ("vlm", "jnp", 1, buckets[:1]),
        ("vlm", "jnp", 2, buckets[:1]),
        ("moe", "jnp", 1, buckets[:1]),
        ("moe", "jnp", 2, buckets[:1]),
    ]
    for family, backend, tp, bks in grid:
        for b in bks:
            psums = 1 if tp > 1 else 0
            ags = 1 if tp > 1 and family != "moe" else 0
            yield TraceEntry(
                f"decode/{family}/{backend}/{axis[family]}{tp}/b{b}",
                _build_decode(family, backend, b), n_devices=tp,
                psums=psums, all_gathers=ags)


# -------------------------------------------------------- registry ----

def entries(max_devices: int = None) -> tuple:
    """Every registered entry runnable with `max_devices` host devices
    (default: what the process actually has). Backend variants a family
    does not declare (ServingFamily.backends) are filtered out."""
    from repro.serving.families import serving_family
    limit = max_devices if max_devices is not None else \
        jax.device_count()
    out = list(_kernel_entries()) + list(_cold_entries())
    for e in _decode_entries():
        _, family, backend = e.name.split("/")[:3]
        cfg, _, _, _ = _family_setup(family)
        if backend not in serving_family(cfg).backends:
            continue
        out.append(e)
    return tuple(e for e in out if e.n_devices <= limit)


def entry_names(max_devices: int = None) -> tuple:
    return tuple(e.name for e in entries(max_devices))
