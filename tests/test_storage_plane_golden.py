"""StoragePlane.step on the smollm-135m geometry against TokenStats
pinned from the per-neuron cold LRU, and the `price_keys` count the
plane's caches make a step on aligned and unaligned geometries.

The pinned values in data/storage_plane_golden.json were recorded from
the storage plane as it was at commit 537d1be, when the cold LRU kept
one key per neuron; holding whole clusters as one key must not move
any of them."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.baselines import POWERINFER2
from repro.core.planner import build_plan, synthetic_frequencies
from repro.serving.storage_plane import StoragePlane

GOLDEN = Path(__file__).parent / "data" / "storage_plane_golden.json"


@pytest.fixture(scope="module")
def geometry():
    """smollm-135m at published widths; the plane reads only the FFN
    tensor's shape and dtype, so no weights are made."""
    cfg = get_config("smollm-135m")
    freqs = -np.sort(-synthetic_frequencies(cfg), axis=1)
    plan = build_plan(cfg, freqs)
    w = jax.ShapeDtypeStruct((cfg.num_layers, cfg.d_ff, 3, cfg.d_model),
                             jnp.bfloat16)
    return cfg, plan, {"layers": {"ffn": {"w": w}}}


def _traces(cfg, plan, n, seed=17):
    p8 = plan.plan_for_batch(8)
    n_cold = (cfg.d_ff - p8.n_hot) // p8.cluster_size
    rng = np.random.default_rng(seed)
    return p8, rng.integers(0, n_cold, (n, cfg.num_layers, 1, 1))


def test_step_matches_per_neuron_lru_golden(geometry):
    cfg, plan, params = geometry
    want = json.loads(GOLDEN.read_text())["steps"]
    p8, traces = _traces(cfg, plan, len(want))
    assert (p8.n_hot, p8.clusters_per_group, p8.cluster_size) == (64, 1, 64)
    plane = StoragePlane(cfg, params, plan, spec=POWERINFER2)
    try:
        assert plane.cache.cold.clustered
        for i, (tr, w) in enumerate(zip(traces, want)):
            ops = plane.key_ops
            st = plane.step(tr, p8, 8, 600.0)
            got = [st.effective_s, st.io_s, st.n_miss, st.cache_hit_rate]
            assert got == w, f"step {i}"
            # one key per layer's cluster looked up, one per cluster
            # admitted: at most two per layer
            keys = plane.key_ops - ops
            assert keys == cfg.num_layers + st.n_miss // 64
            assert keys <= 2 * cfg.num_layers
        assert plane.cache.cold.clustered
    finally:
        plane.close()


def test_price_keys_count_neurons_on_an_unaligned_plane(geometry):
    """A fifth of the budget per replica leaves 89 cold neurons a
    layer, not whole clusters: the cache keys neurons from the start."""
    cfg, plan, params = geometry
    p8, traces = _traces(cfg, plan, 20, seed=3)
    plane = StoragePlane(cfg, params, plan, spec=POWERINFER2, n_replicas=5)
    try:
        assert plane.cache.cold.capacity == 89 * cfg.num_layers
        assert not plane.cache.cold.clustered
        for tr in traces:
            ops = plane.key_ops
            st = plane.step(tr, p8, 8, 600.0)
            # every cold neuron looked up, every missed one admitted
            assert plane.key_ops - ops == cfg.num_layers * 64 + st.n_miss
    finally:
        plane.close()
