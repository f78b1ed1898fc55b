"""Dynamic CPU/NPU-ratio adaptation (paper §4.1.3).

The NPU executes static graphs: PowerInfer-2 pre-builds one graph per
(batch size, hot ratio) and swaps them asynchronously while attention
runs. The XLA analogue is exact: we pre-jit one decode executable per
batch bucket (static shapes) and swap executables as the live batch
size changes. `BucketedDecoder` tracks sequence creation/completion and
serves the right executable with zero-recompile switches.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict

import jax

from repro.core.clusters import HybridPlan
from repro.core.planner import ExecutionPlan


# the serving bucket ladder: one pre-jitted executable per bucket.
# Shared by bucket_for, BucketedDecoder and the semantic analysis
# trace registry's representative-bucket coverage.
DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32)


def bucket_for(batch: int, buckets=DEFAULT_BUCKETS) -> int:
    for b in buckets:
        if batch <= b:
            return b
    return buckets[-1]


def mesh_key(mesh):
    """Hashable executable-table key for a device mesh (None = no mesh)."""
    if mesh is None:
        return None
    return tuple(zip(mesh.axis_names, tuple(dict(mesh.shape).values())))


@dataclass
class BucketedDecoder:
    """Pre-jitted decode executables per (batch bucket × mesh shape).

    make_step(plan) must return a decode callable
    (params, tokens, cache) -> (logits, cache) specialized to the plan;
    it is jitted once per key and cached (the paper's pre-generated
    NPU graph table, §5 Batch-Adaptive Planning). With a `mesh`, the
    executable is traced and run inside that mesh context, so the
    sparse-FFN shard_map path and all sharding constraints bind to it —
    tensor-parallel and single-device executables coexist in the table.

    `backend` ('jnp' | 'pallas' | None) overrides each bucket plan's
    cold-path backend before tracing: every executable in the table
    runs the chosen kernel path (DESIGN.md §10), regardless of how the
    offline planner built the per-bucket plans.
    """
    plan_source: ExecutionPlan
    make_step: Callable[[HybridPlan], Callable]
    buckets: tuple = DEFAULT_BUCKETS
    mesh: object = None
    backend: str = None
    _cache: Dict[tuple, tuple] = field(default_factory=dict)

    def prewarm(self):
        for b in self.buckets:
            self.executable_for(b)

    def executable_for(self, batch: int):
        b = bucket_for(batch, self.buckets)
        key = (b, mesh_key(self.mesh))
        if key not in self._cache:
            plan = self.plan_source.plan_for_batch(b)
            if self.backend and plan.backend != self.backend:
                import dataclasses
                plan = dataclasses.replace(plan, backend=self.backend)
            fn = jax.jit(self.make_step(plan))
            if self.mesh is not None:
                fn = self._bind_mesh(fn, self.mesh)
            self._cache[key] = (plan, fn)
        return self._cache[key]

    @staticmethod
    def _bind_mesh(fn, mesh):
        def call(*args, **kwargs):
            with jax.set_mesh(mesh):
                return fn(*args, **kwargs)
        return call

    def live_plans(self):
        return {b: p for (b, _), (p, _) in self._cache.items()}


@dataclass
class BatchTracker:
    """Tracks live decoding sequences (Best-of-N / continuous batching):
    the *effective* batch size falls as sequences hit EOS (paper Fig 13)."""
    active: int = 0
    history: list = field(default_factory=list)

    def start(self, n: int = 1):
        self.active += n
        self.history.append(self.active)

    def finish(self, n: int = 1):
        self.active = max(0, self.active - n)
        self.history.append(self.active)
