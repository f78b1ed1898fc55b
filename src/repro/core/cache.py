"""Segmented in-memory neuron cache (paper §4.2).

Three regions with different granularity and policy:
  * fixed  — attention weights + KV cache; preloaded, never evicted.
  * hot    — dense matrices for the NPU/MXU path; LRU at *cluster*
             granularity (a cluster = `cluster_size` bundled neurons).
  * cold   — individually managed neurons for the sparse path; LRU at
             *neuron* granularity (co-activation after removing hot
             neurons is <20%, so bundling whole groups wastes I/O).
             While every access is whole clusters on one grid, the
             same LRU is held with one key per cluster (`ColdLRU`).

Evictions are discards (weights are read-only; no write-back).
`rebalance(batch_size)` grows the hot region for larger batches and
shrinks it back when sequences complete (paper Fig 2 dynamics).
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass

import numpy as np


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    bytes_loaded: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 1.0

    def reset(self):
        self.hits = self.misses = self.evictions = self.bytes_loaded = 0


class LRUSet:
    """LRU over integer keys with capacity in item count."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._d: OrderedDict = OrderedDict()

    def __contains__(self, k):
        return k in self._d

    def __len__(self):
        return len(self._d)

    def touch(self, k) -> bool:
        """Mark k used. Returns True if it was present (hit)."""
        if k in self._d:
            self._d.move_to_end(k)
            return True
        return False

    def admit(self, k) -> list:
        """Insert k; returns list of evicted keys."""
        evicted = []
        if k in self._d:
            self._d.move_to_end(k)
            return evicted
        while len(self._d) >= max(self.capacity, 1):
            old, _ = self._d.popitem(last=False)
            evicted.append(old)
        self._d[k] = True
        return evicted

    def resize(self, capacity: int) -> list:
        self.capacity = capacity
        evicted = []
        while len(self._d) > max(capacity, 0):
            old, _ = self._d.popitem(last=False)
            evicted.append(old)
        return evicted

    def keys(self):
        return list(self._d.keys())


class ColdLRU:
    """LRU over (layer, neuron_id) keys, capacity in neurons, that holds
    whole clusters as one key while it can.

    While the capacity is a multiple of `cluster_size` and every id set
    it has been given is whole clusters on one grid (runs of
    `cluster_size` ascending ids, each starting at the same offset
    mod `cluster_size`), a neuron-keyed LRU keeps each cluster's
    neurons adjacent in LRU order and all present or all absent, and
    evicting the oldest `cluster_size` neurons evicts exactly one
    cluster. So it is the same LRU with one key `(layer, first_id)`
    per cluster. The first call that breaks this converts the state to
    neuron keys, in LRU order, and from then on every key is a neuron.
    Either way `keys()`, `len()`, the returned ids and the counts are
    those of the neuron-keyed LRU.

    `ops` counts the LRU keys touched or admitted: one per cluster
    while clustered, one per neuron after."""

    def __init__(self, capacity: int, cluster_size: int):
        self.capacity = capacity
        self.cs = cluster_size
        self.off = None           # grid offset, fixed by the first ids
        self.clustered = capacity > 0 and capacity % cluster_size == 0
        self._lru = LRUSet(capacity // cluster_size if self.clustered
                           else capacity)
        self.ops = 0

    def __len__(self):
        return len(self._lru) * (self.cs if self.clustered else 1)

    def keys(self):
        if not self.clustered:
            return self._lru.keys()
        return [(layer, s + i) for layer, s in self._lru.keys()
                for i in range(self.cs)]

    def _clusters(self, ids):
        """`ids` as a list of ints, and the first id of each whole
        cluster it is made of; None for the latter (and the state
        turned to neuron keys) if it is not whole clusters on this
        LRU's grid."""
        ids = np.asarray(ids, dtype=np.int64).tolist()
        if not self.clustered:
            return ids, None
        cs = self.cs
        starts = ids[::cs]
        off = starts[0] % cs if starts else self.off
        if self.off in (None, off) and all(
                s % cs == off and ids[j:j + cs] == list(range(s, s + cs))
                for j, s in zip(range(0, len(ids), cs), starts)):
            self.off = off
            return ids, starts
        self._to_neurons()
        return ids, None

    def _to_neurons(self):
        cs = self.cs
        lru = LRUSet(self.capacity)
        for layer, s in self._lru.keys():
            for i in range(cs):
                lru.admit((layer, s + i))
        self._lru = lru
        self.clustered = False

    def lookup(self, layer: int, ids) -> tuple:
        """Touch `ids`; returns (hit_ids, miss_ids), each in input order."""
        ids, starts = self._clusters(ids)
        if starts is None:
            hits, misses = [], []
            for nid in ids:
                (hits if self._lru.touch((layer, nid))
                 else misses).append(nid)
            self.ops += len(ids)
            return hits, misses
        hit = [self._lru.touch((layer, s)) for s in starts]
        self.ops += len(hit)
        if all(hit):
            return ids, []
        if not any(hit):
            return [], ids
        cs = self.cs
        hits, misses = [], []
        for j, h in enumerate(hit):
            (hits if h else misses).extend(ids[j * cs:(j + 1) * cs])
        return hits, misses

    def admit(self, layer: int, ids) -> int:
        """Insert `ids` in order; returns the neurons evicted."""
        ids, starts = self._clusters(ids)
        keys = [(layer, nid) for nid in ids] if starts is None \
            else [(layer, s) for s in starts]
        self.ops += len(keys)
        n = sum(len(self._lru.admit(k)) for k in keys)
        return n if starts is None else n * self.cs

    def resize(self, capacity: int) -> int:
        """Set the capacity; returns the neurons evicted."""
        if self.clustered and capacity > 0 and capacity % self.cs == 0:
            n = len(self._lru.resize(capacity // self.cs)) * self.cs
        else:
            if self.clustered:
                self._to_neurons()
            n = len(self._lru.resize(capacity))
        self.capacity = capacity
        return n


class NeuronCache:
    """Per-layer segmented neuron cache.

    Keys: (layer, neuron_id) for cold entries; (layer, cluster_id) for
    hot entries. Capacities are in *neurons* (bytes_per_neuron converts).
    """

    def __init__(self, n_layers: int, neurons_per_layer: int,
                 cluster_size: int, capacity_neurons: int,
                 hot_fraction: float = 0.5, bytes_per_neuron: int = 0):
        self.n_layers = n_layers
        self.N = neurons_per_layer
        self.cluster_size = cluster_size
        self.capacity = capacity_neurons
        self.bytes_per_neuron = bytes_per_neuron
        n_hot = int(capacity_neurons * hot_fraction)
        self.hot = LRUSet(max(n_hot // cluster_size, 1))
        self.cold = ColdLRU(max(capacity_neurons - n_hot, 1), cluster_size)
        self.stats = CacheStats()

    # -- hot region: cluster granularity ------------------------------
    def lookup_hot_cluster(self, layer: int, cluster_id: int) -> bool:
        hit = self.hot.touch((layer, cluster_id))
        self.stats.hits += self.cluster_size if hit else 0
        self.stats.misses += 0 if hit else self.cluster_size
        return hit

    def admit_hot_cluster(self, layer: int, cluster_id: int):
        ev = self.hot.admit((layer, cluster_id))
        self.stats.evictions += len(ev) * self.cluster_size
        self.stats.bytes_loaded += self.cluster_size * self.bytes_per_neuron

    # -- cold region: neuron granularity ------------------------------
    def lookup_cold(self, layer: int, neuron_ids) -> tuple:
        """Returns (hit_ids, miss_ids)."""
        hits, misses = self.cold.lookup(layer, neuron_ids)
        self.stats.hits += len(hits)
        self.stats.misses += len(misses)
        return hits, misses

    def admit_cold(self, layer: int, neuron_ids):
        self.stats.evictions += self.cold.admit(layer, neuron_ids)
        self.stats.bytes_loaded += len(neuron_ids) * self.bytes_per_neuron

    # -- dynamic rebalancing (paper §4.2 last para) --------------------
    def rebalance(self, batch_size: int):
        """Grow hot region with batch size (more dense NPU work), shrink
        cold; and vice versa. Ratio ramps 0.5 -> 0.8 from batch 1 to 32."""
        import math
        t = min(math.log2(max(batch_size, 1)) / 5.0, 1.0)
        hot_frac = 0.5 + 0.3 * t
        n_hot = int(self.capacity * hot_frac)
        ev_h = self.hot.resize(max(n_hot // self.cluster_size, 1))
        ev_c = self.cold.resize(max(self.capacity - n_hot, 1))
        self.stats.evictions += len(ev_h) * self.cluster_size + ev_c

    @property
    def resident_neurons(self) -> int:
        return len(self.hot) * self.cluster_size + len(self.cold)
