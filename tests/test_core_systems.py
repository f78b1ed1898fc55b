"""Property-based tests (hypothesis) for the cache, pipeline, planner,
MoE dispatch, and I/O model invariants."""
import math
from collections import OrderedDict

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.cache import LRUSet, NeuronCache
from repro.core.io_model import UFS40, UFS31, HOST_DMA, with_core, \
    with_queue_contention
from repro.core.pipeline import make_decode_tasks, simulate_pipeline


# ------------------------------------------------------------ LRU/cache ----

@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 30), min_size=1, max_size=200),
       st.integers(1, 16))
def test_lru_capacity_never_exceeded(keys, cap):
    lru = LRUSet(cap)
    for k in keys:
        lru.admit(k)
        assert len(lru) <= cap


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 10), min_size=2, max_size=100))
def test_lru_most_recent_always_present(keys):
    lru = LRUSet(3)
    for k in keys:
        lru.admit(k)
        assert k in lru


def test_lru_evicts_least_recently_used():
    lru = LRUSet(2)
    lru.admit(1)
    lru.admit(2)
    lru.touch(1)          # 2 is now LRU
    ev = lru.admit(3)
    assert ev == [2]
    assert 1 in lru and 3 in lru


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 4), st.integers(32, 256), st.integers(1, 32))
def test_neuron_cache_hit_rate_bounds(layers, cap, reqs):
    nc = NeuronCache(layers, 1024, 16, capacity_neurons=cap,
                     bytes_per_neuron=128)
    rng = np.random.default_rng(0)
    for _ in range(reqs):
        ids = rng.integers(0, 1024, size=8)
        h, m = nc.lookup_cold(0, ids)
        nc.admit_cold(0, m)
        assert len(h) + len(m) == len(ids)
    assert 0.0 <= nc.stats.hit_rate <= 1.0
    assert nc.resident_neurons >= 0


def test_neuron_cache_repeat_requests_hit():
    nc = NeuronCache(1, 256, 16, capacity_neurons=64, bytes_per_neuron=1)
    ids = list(range(32))
    _, m1 = nc.lookup_cold(0, ids)
    nc.admit_cold(0, m1)
    h2, m2 = nc.lookup_cold(0, ids)
    assert m2 == [] and len(h2) == 32


class RefColdCache:
    """The cold region of NeuronCache as a plain per-neuron LRU: one
    OrderedDict key per (layer, neuron), touched, admitted and evicted
    one neuron at a time. The hot region is never used here, so it
    adds no evictions."""

    def __init__(self, capacity, bytes_per_neuron):
        self.capacity = capacity
        self.cold_cap = capacity
        self.bpn = bytes_per_neuron
        self.d = OrderedDict()
        self.hits = self.misses = self.evictions = self.bytes_loaded = 0
        self.ops = 0

    def _evict_to(self, n):
        while len(self.d) > n:
            self.d.popitem(last=False)
            self.evictions += 1

    def lookup(self, layer, ids):
        hits, misses = [], []
        for nid in ids:
            k = (layer, int(nid))
            if k in self.d:
                self.d.move_to_end(k)
                hits.append(int(nid))
            else:
                misses.append(int(nid))
        self.hits += len(hits)
        self.misses += len(misses)
        self.ops += len(ids)
        return hits, misses

    def admit(self, layer, ids):
        for nid in ids:
            k = (layer, int(nid))
            if k in self.d:
                self.d.move_to_end(k)
                continue
            self._evict_to(max(self.cold_cap, 1) - 1)
            self.d[k] = True
        self.bytes_loaded += len(ids) * self.bpn
        self.ops += len(ids)

    def rebalance(self, batch):
        t = min(math.log2(max(batch, 1)) / 5.0, 1.0)
        self.cold_cap = max(self.capacity - int(self.capacity
                                                * (0.5 + 0.3 * t)), 1)
        self._evict_to(self.cold_cap)


CS, N_LAYERS, N_NEURONS = 4, 2, 64


def _cold_cache_calls(draw, kind):
    """A sequence of cold-region calls. `aligned`: whole clusters on one
    grid, and a capacity that stays a multiple of CS; `unaligned`:
    loose ids, clusters on another grid or on two, clusters short of a
    neuron, or float ids, from the first call; `mixed`: aligned, then
    unaligned. Ids come as lists or as int64 arrays."""
    off = draw(st.integers(0, CS - 1))

    def clusters(o):
        cl = draw(st.lists(st.integers(0, N_NEURONS // CS - 2), max_size=5))
        return [o + c * CS + i for c in cl for i in range(CS)]

    def unaligned():
        how = draw(st.sampled_from(["loose", "shifted", "straddle",
                                    "overlap", "short"]))
        if how == "loose":
            return draw(st.lists(st.integers(0, N_NEURONS - 1),
                                 max_size=12))
        d = draw(st.integers(1, CS - 1))
        shifted = (off + d) % CS
        if how == "shifted":
            return clusters(shifted)
        if how == "straddle":
            return clusters(off) + clusters(shifted)
        if how == "overlap":
            # a cluster on the grid, then one shifted off it that
            # shares CS - d of its neurons
            s = off + draw(st.integers(0, N_NEURONS // CS - 2)) * CS
            return clusters(off) + [s + i for i in range(CS)] \
                + [s + d + i for i in range(CS)]
        return clusters(off)[:-1]

    n = draw(st.integers(1, 25))
    switch = {"aligned": n, "unaligned": 0,
              "mixed": draw(st.integers(1, n))}[kind]
    calls = []
    for j in range(n):
        op = draw(st.sampled_from(["lookup", "admit", "price", "rebalance"]))
        if op == "rebalance":
            batches = [1, 32] if j < switch else [1, 2, 4, 8, 16, 32]
            calls.append((op, draw(st.sampled_from(batches))))
        else:
            layer = draw(st.integers(0, N_LAYERS - 1))
            if j < switch:
                ids = clusters(off)
                dtype = draw(st.sampled_from([None, np.int64]))
            else:
                ids = unaligned()
                dtype = draw(st.sampled_from([None, np.int64, np.float64]))
            if dtype is not None:
                ids = np.asarray(ids, dtype=dtype)
            calls.append((op, layer, ids))
    return calls


@pytest.mark.parametrize("kind", ["aligned", "unaligned", "mixed"])
@settings(max_examples=80, deadline=None)
@given(data=st.data())
def test_neuron_cache_cold_region_matches_per_neuron_lru(kind, data):
    # a capacity of 10 * CS * k keeps the cold region whole clusters
    # under rebalance(1) and rebalance(32), so the cluster keys hold
    # until a `mixed` sequence turns unaligned
    cap = data.draw(st.integers(1, 3)) * 10 * CS if kind != "unaligned" \
        else data.draw(st.integers(1, 120))
    nc = NeuronCache(N_LAYERS, N_NEURONS, CS, capacity_neurons=cap,
                     hot_fraction=0.0, bytes_per_neuron=3)
    ref = RefColdCache(cap, 3)
    for call in _cold_cache_calls(data.draw, kind):
        if call[0] == "rebalance":
            nc.rebalance(call[1])
            ref.rebalance(call[1])
        else:
            op, layer, ids = call
            if op in ("lookup", "price"):
                got = nc.lookup_cold(layer, ids)
                want = ref.lookup(layer, ids)
                assert got == want
                if op == "price":
                    nc.admit_cold(layer, got[1])
                    ref.admit(layer, want[1])
            else:
                nc.admit_cold(layer, ids)
                ref.admit(layer, ids)
        s = nc.stats
        assert (s.hits, s.misses, s.evictions, s.bytes_loaded) == (
            ref.hits, ref.misses, ref.evictions, ref.bytes_loaded)
        assert nc.cold.capacity == ref.cold_cap
        assert len(nc.cold) == len(ref.d) == nc.resident_neurons
        assert nc.cold.keys() == list(ref.d)
    if kind == "aligned":
        # the cluster path held throughout: one key per cluster
        assert nc.cold.clustered
        assert nc.cold.ops * CS == ref.ops


@pytest.mark.parametrize("ids", [
    [0, 1, 2, 3, 2, 3, 4, 5],       # a held cluster, then one off its grid
    [8, 9, 10, 11, 1, 2, 3, 4],     # a new cluster, then one off the grid
    [1, 2, 3, 4],                   # one whole cluster on another grid
])
def test_neuron_cache_cold_region_leaves_clusters_off_the_grid(ids):
    """Runs of CS ids that overlap the held clusters' neurons are not
    clusters of the grid: the cold region turns to neuron keys and
    prices them as the per-neuron LRU does."""
    nc = NeuronCache(1, N_NEURONS, CS, capacity_neurons=10 * CS,
                     hot_fraction=0.0, bytes_per_neuron=1)
    ref = RefColdCache(10 * CS, 1)
    for call in ([0, 1, 2, 3], ids):
        got = nc.lookup_cold(0, call)
        assert got == ref.lookup(0, call)
        nc.admit_cold(0, got[1])
        ref.admit(0, got[1])
    assert not nc.cold.clustered
    assert nc.cold.keys() == list(ref.d)
    assert nc.stats.evictions == ref.evictions == 0


# -------------------------------------------------------------- pipeline ----

@settings(max_examples=30, deadline=None)
@given(st.integers(1, 6), st.integers(1, 8),
       st.floats(0.0, 1.0), st.integers(1, 6))
def test_cluster_pipeline_never_slower_than_matrix(nm, nc, frac, workers):
    tasks = make_decode_tasks(nm, nc, frac, comp_time=1.0, io_time=1.5,
                              seed=3)
    rm = simulate_pipeline(tasks, n_compute=workers, policy="matrix")
    rc = simulate_pipeline(tasks, n_compute=workers, policy="cluster")
    assert rc.makespan <= rm.makespan + 1e-9


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 5), st.integers(1, 6), st.floats(0.0, 1.0))
def test_pipeline_lower_bounds(nm, nc, frac):
    tasks = make_decode_tasks(nm, nc, frac, comp_time=0.7, io_time=1.1,
                              seed=4)
    for pol in ("matrix", "cluster"):
        r = simulate_pipeline(tasks, n_compute=2, policy=pol)
        io_total = sum(t.io_time for t in tasks)
        comp_total = sum(t.comp_time for t in tasks)
        assert r.makespan >= io_total - 1e-9          # single I/O queue
        assert r.makespan >= comp_total / 2 - 1e-9    # 2 workers
        assert 0.0 <= r.compute_util <= 1.0 + 1e-9
        assert 0.0 <= r.io_fraction <= 1.0


def test_pipeline_all_cached_has_no_io():
    tasks = make_decode_tasks(4, 4, 1.0, comp_time=1.0, io_time=9.9)
    r = simulate_pipeline(tasks, n_compute=4, policy="cluster")
    assert r.io_busy == 0.0
    assert abs(r.makespan - 4.0) < 1e-9   # 16 tasks / 4 workers * 1s


# --------------------------------------------------------------- io model ----

def test_bandwidth_monotone_in_block_size():
    for model in (UFS40, UFS31, HOST_DMA):
        bws = [model.bandwidth(bs, random=True)
               for bs in (4096, 8192, 65536, 524288)]
        assert bws == sorted(bws)


def test_paper_table1_core_ordering():
    big = with_core(UFS40, "big").bandwidth(4096, True)
    mid = with_core(UFS40, "mid").bandwidth(4096, True)
    little = with_core(UFS40, "little").bandwidth(4096, True)
    assert big > mid > little
    assert abs(big / little - 1076.10 / 761.87) < 0.15


def test_queue_contention_degrades():
    one = with_queue_contention(UFS40, 1).bandwidth(4096, True)
    four = with_queue_contention(UFS40, 4).bandwidth(4096, True)
    assert four < one
    assert four / one >= 0.6    # paper: up to 40% degradation


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 10_000_000), st.sampled_from([4096, 24576, 524288]))
def test_read_time_positive_and_monotone(nbytes, bs):
    t1 = UFS40.read_time(nbytes, bs, random=True)
    t2 = UFS40.read_time(nbytes * 2, bs, random=True)
    assert t1 > 0 and t2 >= t1


# ------------------------------------------------------------ moe dispatch ----

@settings(max_examples=30, deadline=None)
@given(st.integers(2, 64), st.integers(2, 8), st.integers(1, 4))
def test_moe_dispatch_invariants(T, E, k):
    import jax
    import jax.numpy as jnp
    from repro.models.moe import moe_dispatch
    k = min(k, E)
    gates = jax.nn.softmax(
        jax.random.normal(jax.random.key(T * E + k), (T, E)), -1)
    C = max(1, (T * k) // E)
    tope, topv, slot, keep = moe_dispatch(gates, k, C)
    slot_np, keep_np = np.asarray(slot), np.asarray(keep)
    kept = slot_np[keep_np]
    assert len(set(kept.tolist())) == len(kept)        # no slot collisions
    assert (kept < E * C).all() and (kept >= 0).all()
    w = np.asarray(topv)
    assert (w >= -1e-6).all()
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-5)  # renormalized
    # capacity respected per expert
    e_of_slot = kept // C
    counts = np.bincount(e_of_slot, minlength=E)
    assert (counts <= C).all()
