"""PowerInfer-2 serving engine — the thin orchestrator.

Three layers, cleanly separated (DESIGN.md §2 records why):

* **Data plane** — always numerically real: pre-jitted decode
  executables per batch bucket (core/adaptation.BucketedDecoder — the
  paper's per-batch NPU graph table) run the hybrid hot/cold FFN and
  return, besides logits, the *true* per-layer cold-cluster selections
  (the activation trace).
* **Storage plane** (serving/storage_plane.py) — the trace drives the
  segmented NeuronCache and the bundled ColdStore exactly as on the
  phone; I/O time comes from the StorageModel, per-token effective
  latency is composed by the neuron-cluster pipeline simulator, and a
  single-I/O-thread prefetcher overlaps next-layer miss fetches with
  current-layer pricing.
* **Scheduler** (serving/scheduler.py) — request-level continuous
  batching: an admission queue, per-step admission up to the decoder's
  next bucket boundary, prefill-on-admit, completion/eviction.

This module only orchestrates: submit()/step()/run_until_drained()
drive requests through slot-based KV management (models/kv_cache.
KVSlotArena); generate() remains as a static-batch compatibility
wrapper over the same loop.

Tensor parallel (DESIGN.md §3): pass `mesh=` a (data, model) device
mesh and all three layers shard over 'model' — params and the KV arena
are placed on the mesh, decode executables are keyed on (bucket × mesh
shape) and traced in the mesh context (the sparse-FFN cold path goes
shard-local via shard_map), and the storage plane prices per-device
cache slices and I/O channels, aggregating TokenStats across shards.

Data parallel (DESIGN.md §5): with the mesh's 'data' axis > 1 (or an
explicit `dp=N` on meshless hosts) the engine becomes a replica
router: one full serving stack — BatchScheduler, KVSlotArena,
StoragePlane, BucketedDecoder, modeled clock — per 'data'-axis row,
each replica running over its own (1, n_model) tensor-parallel
submesh. Submits route least-loaded with a FIFO tiebreak
(serving/scheduler.py::ReplicaRouter); each replica admits at its own
decoder bucket boundary and advances its own clock; run_until_drained
merges the per-replica TokenStats onto the shared timeline and
reports span-based throughput.

Families (DESIGN.md §8): every family-specific piece — model factory,
traced decode step, plan builder, storage view — resolves through the
serving family registry (serving/families.py) keyed on `cfg.family`,
so dense, vlm and moe share this one orchestrator. For moe, the mesh
'model' axis is the *expert-parallel* axis (E/n experts per shard,
shard-local dispatch, one psum per layer) and the storage plane
prices expert residency as cold-cluster residency.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig
from repro.core.adaptation import BucketedDecoder, bucket_for
from repro.core.baselines import SystemSpec, POWERINFER2
from repro.core.io_model import StorageModel, UFS40
from repro.core.planner import ExecutionPlan, HardwareProfile
from repro.models.kv_cache import KVSlotArena
from repro.models.modules import dtype_of
from repro.serving.families import serving_family
from repro.serving import spans
from repro.serving.sampler import sample_tokens
from repro.serving.scheduler import BatchScheduler
from repro.serving.storage_plane import StoragePlane, TimingProfile, \
    TokenStats

__all__ = ["ServeEngine", "GenerationResult", "ServeReport", "StepResult",
           "TimingProfile", "TokenStats"]


def _percentiles(lat: np.ndarray) -> dict:
    """Latency percentile summary; empty input (a stream cancelled
    before any step, a zero-token generation) yields zeros instead of
    np.percentile's IndexError / nan-mean."""
    if lat.size == 0:
        return {"mean": 0.0, "p50": 0.0, "p90": 0.0, "p99": 0.0}
    return {"mean": float(lat.mean()),
            "p50": float(np.percentile(lat, 50)),
            "p90": float(np.percentile(lat, 90)),
            "p99": float(np.percentile(lat, 99))}


@dataclass
class GenerationResult:
    tokens: np.ndarray                 # (B, new)
    stats: list                        # TokenStats per step
    wall_s: float = 0.0

    @property
    def tokens_per_s(self) -> float:
        total = sum(s.effective_s for s in self.stats)
        n = sum(s.batch for s in self.stats)
        return n / total if total else 0.0

    def latency_percentiles(self):
        return _percentiles(np.array([s.effective_s for s in self.stats]))


@dataclass
class StepResult:
    """Outcome of one continuous-batching decode step."""
    stats: TokenStats
    tokens: dict                       # uid -> generated token
    admitted: list = field(default_factory=list)
    finished: list = field(default_factory=list)
    replica: int = 0                   # 'data'-axis row that stepped
    t_s: float = 0.0                   # that replica's clock after the step
    trace: np.ndarray = None           # the activation trace it priced
    counts: dict = field(default_factory=dict)   # spans.COUNTS -> int


@dataclass
class ServeReport:
    """Aggregate serving metrics over a drained request stream.

    With replica routing the stats list merges every replica's steps
    ordered by completion time on the shared modeled timeline, and
    `span_s` is the drained makespan (slowest replica clock) —
    `throughput_tok_s` is the span-based rate that actually scales
    with the 'data' axis, while `tokens_per_s` keeps the legacy
    sum-of-step-latency semantics (per-engine pipeline rate)."""
    stats: list                        # TokenStats per step
    requests: list                     # finished Requests
    span_s: float = 0.0                # drained span on the shared timeline

    @property
    def total_tokens(self) -> int:
        return sum(s.batch for s in self.stats)

    @property
    def tokens_per_s(self) -> float:
        total = sum(s.effective_s for s in self.stats)
        return self.total_tokens / total if total else 0.0

    @property
    def throughput_tok_s(self) -> float:
        return self.total_tokens / self.span_s if self.span_s else 0.0

    def ttft(self) -> np.ndarray:
        """TTFT over requests that produced a first token — requests
        cancelled before their first token have `first_token_time is
        None` and are filtered, never coerced into the array."""
        return np.array([r.ttft for r in self.requests
                         if r.ttft is not None])

    def token_latencies(self) -> np.ndarray:
        """Per-token effective latency: every token generated in a step
        experienced that step's effective seconds."""
        out = []
        for s in self.stats:
            out.extend([s.effective_s] * s.batch)
        return np.array(out)

    def latency_percentiles(self):
        return _percentiles(self.token_latencies())


class ServeEngine:
    """Single-host continuous-batching engine for every registered
    serving family (dense sparse-FFN, vlm backbone, expert-parallel
    moe). Orchestrates the data plane (BucketedDecoder), the storage
    plane (StoragePlane) and the scheduler (BatchScheduler) over a
    slot-based KV arena.

    With a mesh whose 'data' axis is > 1 (or an explicit dp=N) the
    engine instead owns one single-replica engine per 'data'-axis row
    and routes requests across them (DESIGN.md §5)."""

    def __init__(self, cfg: ModelConfig, params, plan: ExecutionPlan,
                 spec: SystemSpec = POWERINFER2,
                 storage: StorageModel = UFS40,
                 offload_ratio: float = 0.5,
                 hw: HardwareProfile = None,
                 timing: TimingProfile = None,
                 n_compute_workers: int = 4,
                 seed: int = 0,
                 buckets: tuple = None,
                 ctx_budget: int = None,
                 eos_id: int = None,
                 temperature: float = 0.8,
                 prefetch: bool = True,
                 mesh=None,
                 dp: int = None,
                 n_replicas: int = 1,
                 backend: str = None):
        # family registry lookup (DESIGN.md §8): raises with the
        # servable set named when cfg.family has no entry
        self.family = serving_family(cfg)
        # cold-path kernel backend override, threaded per bucket into
        # the decoder's executable table (DESIGN.md §10). The moe cold
        # path is expert dispatch, not a cluster gather — no pallas
        # kernel exists for it, so refuse loudly instead of silently
        # serving the jnp path under a 'pallas' label.
        if backend not in (None, "jnp", "pallas"):
            raise ValueError(f"unknown cold-path backend {backend!r}; "
                             f"expected 'jnp' or 'pallas'")
        if backend == "pallas" and cfg.num_experts:
            raise ValueError(
                "backend='pallas' is the dense-family fused cold-path "
                "kernel; the moe family's cold path is expert dispatch "
                "(models/moe.py) and has no pallas backend yet")
        self.backend = backend
        self.cfg = cfg
        self.plan = plan
        self.spec = spec
        self.key = jax.random.key(seed)
        # ---- device mesh (tensor parallel over 'model') ----
        self.mesh = mesh
        mesh_shape = dict(mesh.shape) if mesh is not None else {}
        self.n_shards = mesh_shape.get("model", 1)
        # ---- replica routing over the 'data' axis (DESIGN.md §5) ----
        self.replicas = None
        self.router = None
        n_data = int(dp) if dp is not None else mesh_shape.get("data", 1)
        if mesh is not None and dp is not None \
                and n_data != mesh_shape.get("data", 1):
            raise ValueError(
                f"dp={dp} disagrees with the mesh's 'data' axis "
                f"({mesh_shape.get('data', 1)})")
        if n_data > 1:
            # One full serving stack per replica, each an ordinary
            # dp=1 engine: same seed (so its sampling-key chain is the
            # one an independent engine would use), its own scheduler /
            # KV arena / storage plane / modeled clock, and — when
            # tensor-parallel — its own (1, n_model) row of the mesh.
            if mesh is not None and self.n_shards > 1:
                from repro.launch.mesh import replica_submeshes
                subs = replica_submeshes(mesh)
            else:
                subs = [None] * n_data
            # each replica's storage plane gets a 1/n_data share of the
            # resident NeuronCache budget (DESIGN.md §9): the host
            # memory budget is per machine, so dp must not multiply it
            self.replicas = [
                ServeEngine(cfg, params, plan, spec=spec, storage=storage,
                            offload_ratio=offload_ratio, hw=hw,
                            timing=timing,
                            n_compute_workers=n_compute_workers, seed=seed,
                            buckets=buckets, ctx_budget=ctx_budget,
                            eos_id=eos_id, temperature=temperature,
                            prefetch=prefetch, mesh=subs[r],
                            n_replicas=n_data, backend=backend)
                for r in range(n_data)]
            if subs[0] is None:
                # meshless replicas run identical executables on the
                # same params object: share the jit caches so dp
                # doesn't multiply trace time (replica state that must
                # stay independent — scheduler, arena, key chain,
                # clock — lives outside them). Meshed replicas keep
                # their own: executables bind to their submesh.
                for rep in self.replicas[1:]:
                    rep.decoder._cache = self.replicas[0].decoder._cache
                    rep._prefill_fns = self.replicas[0]._prefill_fns
            from repro.serving.scheduler import ReplicaRouter
            self.router = ReplicaRouter([r.sched for r in self.replicas])
            self.sched = self.router
            self.arena = None
            self.decoder = None
            self.storage = None
            self.ctx_budget = ctx_budget
            self.clock_s = 0.0         # max over replica clocks
            return

        # ---- data plane ----
        if cfg.num_experts:
            # retie MoE dispatch groups to this replica's token block:
            # groups follow the engine's own submesh (its 'data' axis
            # is always 1 here — replica routing handled above), not
            # the launcher-global 'data' axis, so dp x tp x ep composes
            # (each replica dispatches over exactly its local tokens)
            from repro.launch.mesh import dispatch_groups
            cfg = cfg.replace(moe_dispatch_groups=dispatch_groups(mesh))
            self.cfg = cfg
        self.model = self.family.make_model(cfg)
        if mesh is not None:
            params = self._shard_params(params)
        self.params = params
        self._step_traced = self.family.make_decode_step(cfg)

        def make_step(p):
            def decode(pr, t, c, m):
                return self._step_traced(pr, t, c, p, m)
            return decode
        self.decoder = BucketedDecoder(
            plan_source=plan,
            make_step=make_step,
            buckets=tuple(buckets) if buckets else tuple(range(1, 65)),
            mesh=mesh, backend=backend)

        # ---- storage plane ----
        self.storage = StoragePlane(
            cfg, params, plan, spec=spec, storage=storage,
            offload_ratio=offload_ratio, hw=hw, timing=timing,
            n_compute_workers=n_compute_workers, prefetch=prefetch,
            n_shards=self.n_shards, n_replicas=n_replicas)

        # ---- scheduler + KV slots ----
        self.sched = BatchScheduler(eos_id=eos_id)
        self.arena: Optional[KVSlotArena] = None
        self._last = None                  # (n_slots, V) next-token logits
        self._prefill_fns = {}
        self._bucket = None                # bucket of the last decode
        self._n_steps = 0
        self._temperature = temperature
        self.ctx_budget = ctx_budget
        self.clock_s = 0.0                 # modeled serving clock

    def close(self):
        """Release the storage plane's I/O thread (also runs at GC)."""
        if self.replicas is not None:
            for r in self.replicas:
                r.close()
            return
        self.storage.close()

    # --------------------------------------------------- mesh placement ----
    # Quantized-bundle containers (quant/storage.py) ride next to the
    # (L, N, R, D) ffn tensor but aren't in the static model spec; they
    # shard like `w` does — neuron dim over 'model'.
    _QUANT_FFN_SPECS = {
        "wq": PartitionSpec(None, "model", None, None),
        "wsc": PartitionSpec(None, "model", None),
        "wout": PartitionSpec(None, "model", None, None),
    }

    @classmethod
    def param_shardings(cls, model, params, mesh):
        """The model's param sharding on `mesh`, one NamedSharding per
        leaf of `params` (arrays or shapes) — the bundled (L, N, R, D)
        FFN tensor and the predictor columns row/col-split over
        'model'; non-dividing dims replicate."""
        from jax.sharding import NamedSharding
        from repro.sharding import _filter_spec
        specs = model.param_spec()
        ffn = params.get("layers", {}).get("ffn", {})
        extra = {k: s for k, s in cls._QUANT_FFN_SPECS.items() if k in ffn}
        if extra and "ffn" in specs.get("layers", {}):
            specs = dict(specs, layers=dict(
                specs["layers"],
                ffn=dict(specs["layers"]["ffn"], **extra)))
        return jax.tree.map(
            lambda a, s: NamedSharding(
                mesh, _filter_spec(s, mesh, shape=a.shape)),
            params, specs)

    def _shard_params(self, params):
        """Place params on the mesh (a no-op for params initialised
        there)."""
        return jax.device_put(
            params, self.param_shardings(self.model, params, self.mesh))

    # ------------------------------------------------ legacy attributes ----
    # Storage-plane internals used to live on the engine; keep read
    # access for benchmarks/examples without re-exposing the wiring.
    # Replica-routed engines delegate to replica 0 (every replica is
    # configured identically).
    @property
    def _plane_owner(self):
        return self.replicas[0] if self.replicas is not None else self

    @property
    def cache(self):
        return self._plane_owner.storage.cache

    @property
    def coldstore(self):
        return self._plane_owner.storage.coldstore

    @property
    def timing(self):
        return self._plane_owner.storage.timing

    @property
    def hw(self):
        return self._plane_owner.storage.hw

    @property
    def max_slots(self) -> int:
        return self._plane_owner.decoder.buckets[-1]

    # --------------------------------------------- gateway reporting ----
    # The fleet gateway (serving/gateway.py, DESIGN.md §11) routes on
    # these two: the engine's reported load and its next modeled event
    # time. Both delegate to the scheduler layer, so a replica-routed
    # engine reports fleet-correct aggregates for free.
    @property
    def load(self) -> int:
        """Outstanding requests (queued + running) — the per-backend
        reported load weighted least-loaded dispatch divides by the
        backend weight."""
        return self.sched.load

    def next_event_time(self) -> Optional[float]:
        """When this engine's next decode event completes work on the
        modeled clock: its clock while a batch is running, else the
        head arrival it would jump to; None when drained. Replicated
        engines report the earliest replica's event (the same rule
        `_next_replica` steps by)."""
        if self.replicas is not None:
            best_t = None
            for rep in self.replicas:
                t = rep.next_event_time()
                if t is not None and (best_t is None or t < best_t):
                    best_t = t
            return best_t
        if not self.sched.has_work:
            return None
        if self.sched.running:
            return self.clock_s
        nxt = self.sched.next_arrival()
        return max(self.clock_s, nxt) if nxt is not None else self.clock_s

    # ------------------------------------------------------- admission ----
    def submit(self, prompt, max_new: int = 32,
               arrival_time: float = None) -> int:
        """Enqueue one request (prompt: (S,) token ids). Returns uid.

        Replica-routed engines pick the least-loaded replica (FIFO
        tiebreak) and return a router-global uid."""
        if self.replicas is not None:
            r = self.router.pick_replica()
            # default "now" is the engine's shared clock (max over
            # replicas), not the routed replica's possibly-lagging
            # one — a submit must never arrive before steps that had
            # already completed elsewhere on the merged timeline
            local = self.replicas[r].submit(
                prompt, max_new,
                self.clock_s if arrival_time is None else arrival_time)
            return self.router.bind(r, local)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.shape[0] == 0:
            raise ValueError("empty prompt: at least one token required")
        if arrival_time is None:
            arrival_time = self.clock_s
        need = prompt.shape[0] + max_new
        if self.arena is not None and need > self.arena.max_len:
            raise ValueError(
                f"request needs {need} KV positions but the arena was "
                f"sized for {self.arena.max_len}; raise ctx_budget")
        req = self.sched.submit(prompt, max_new, arrival_time)
        return req.uid

    def _ensure_arena(self, n_slots: int, min_len: int) -> bool:
        """Create the arena, or resize it to `n_slots`; True when it
        resized."""
        cfg = self.cfg
        dtype = dtype_of(cfg.param_dtype)
        if self.arena is None:
            T = max(self.ctx_budget or 0, min_len)
            self.arena = KVSlotArena(cfg.num_layers, n_slots, T,
                                     cfg.num_kv_heads, cfg.d_head, dtype,
                                     mesh=self.mesh)
            self._last = jnp.zeros((n_slots, cfg.vocab_padded),
                                   dtype_of(cfg.compute_dtype))
        elif min_len > self.arena.max_len:
            raise ValueError(
                f"admitted request needs {min_len} KV positions but the "
                f"arena was sized for {self.arena.max_len}; raise "
                f"ctx_budget")
        elif self.arena.n_slots != n_slots:
            order = list(self.sched.running)
            rows = self.arena.rows_for(order)
            self.arena.resize(n_slots, order)
            # gather the per-slot logits the same way
            if rows:
                gat = self._last.take(jnp.asarray(rows, jnp.int32), axis=0)
            else:
                gat = self._last[:0]
            pad = n_slots - len(rows)
            if pad:
                zeros = jnp.zeros((pad,) + self._last.shape[1:],
                                  self._last.dtype)
                gat = jnp.concatenate([gat, zeros], axis=0)
            self._last = gat
            return True
        return False

    def _prefill(self, tokens: np.ndarray):
        """Jitted dense prefill padded to the arena length (traced and
        run inside the serving mesh when tensor-parallel)."""
        B, S = tokens.shape
        T = self.arena.max_len
        key = (B, S, T)
        if key not in self._prefill_fns:
            def prefill(p, b):
                with jax.named_scope("prefill"):
                    return self.model.prefill(p, b, max_len=T)
            self._prefill_fns[key] = jax.jit(prefill)
        if self.mesh is not None:
            with jax.set_mesh(self.mesh):
                return self._prefill_fns[key](self.params,
                                              {"tokens": tokens})
        return self._prefill_fns[key](self.params, {"tokens": tokens})

    def _admit(self, reqs: list, counts: dict):
        """Prefill-on-admit: joint prefill per prompt-length group,
        then write each request's KV row into a free slot."""
        i = 0
        while i < len(reqs):
            group = [reqs[i]]
            i += 1
            while i < len(reqs) and reqs[i].prompt_len == group[0].prompt_len:
                group.append(reqs[i])
                i += 1
            S = group[0].prompt_len
            counts["prefill_groups"] += 1
            counts["prefill_tokens"] += S * len(group)
            with spans.span(spans.PREFILL, uids=str([r.uid for r in group]),
                            prompt_len=S, group=len(group)):
                tokens = np.stack([r.prompt for r in group]).astype(np.int32)
                logits, cache = self._prefill(tokens)
                self.clock_s += self.storage.prefill_cost(S, len(group))
                for j, req in enumerate(group):
                    self.sched.admit(req, self.clock_s)
                    self.arena.alloc(req.uid)
                    row = {
                        "k": cache["k"][:, j:j + 1],
                        "v": cache["v"][:, j:j + 1],
                        "kv_pos": cache["kv_pos"][j:j + 1],
                        "length": cache["length"][j:j + 1],
                    }
                    slot = self.arena.write(req.uid, row)
                    self._last = self._last.at[slot].set(logits[j, -1])

    @staticmethod
    def _pull(x, what: str, counts: dict) -> np.ndarray:
        """Read a device array on the host: the one place a step waits
        for the device."""
        counts["pulls"] += 1
        with spans.span(spans.PULL, what=what):
            return np.asarray(x)

    # ------------------------------------------------------ decode loop ----
    def _next_replica(self) -> Optional[int]:
        """Earliest-next-event replica with work: its clock, or the
        head arrival it would jump to when idle (ties -> lowest row).
        This is the event-driven interleaving of clocks that advance
        independently in parallel on real hardware."""
        best, best_t = None, None
        for i, rep in enumerate(self.replicas):
            if not rep.sched.has_work:
                continue
            t = rep.clock_s
            if not rep.sched.running:
                nxt = rep.sched.next_arrival()
                if nxt is not None and nxt > t:
                    t = nxt
            if best is None or t < best_t:
                best, best_t = i, t
        return best

    def step(self) -> Optional[StepResult]:
        """One continuous-batching step: admit -> (resize at bucket
        boundary) -> sample+decode -> price -> complete.

        Replica-routed engines step the replica whose next event is
        earliest on the shared timeline; each replica admits at its
        own decoder bucket boundary and advances its own clock."""
        if self.replicas is not None:
            i = self._next_replica()
            if i is None:
                return None
            rep = self.replicas[i]
            r = rep.step()
            if r is None:
                return None
            self.clock_s = max(e.clock_s for e in self.replicas)
            self.router.batch_history.append(self.router.batch_size)
            r.stats.replica = i
            g = self.router.to_global
            return StepResult(
                stats=r.stats,
                tokens={g(i, u): t for u, t in r.tokens.items()},
                admitted=[g(i, u) for u in r.admitted],
                finished=[g(i, u) for u in r.finished],
                replica=i, t_s=rep.clock_s, trace=r.trace, counts=r.counts)
        if not self.sched.has_work:
            return None
        counts = dict.fromkeys(spans.COUNTS, 0)
        with spans.span(spans.STEP, step=self._n_steps) as sp:
            self._n_steps += 1
            r = self._step(counts)
            sp.set_metadata(**counts)
        return r

    def _step(self, counts: dict) -> Optional[StepResult]:
        """The single-replica step, filling `counts`."""
        sched = self.sched
        built = len(self.decoder._cache) + len(self._prefill_fns)
        with spans.span(spans.ADMIT) as sp:
            # idle engine: jump the modeled clock to the next arrival
            if not sched.running:
                nxt = sched.next_arrival()
                if nxt is not None and nxt > self.clock_s:
                    self.clock_s = nxt
            room = self.max_slots - len(sched.running)
            admits = sched.pop_admissible(self.clock_s, room)
            n_active = len(sched.running) + len(admits)
            if n_active == 0:
                return None
            # the KV arena tracks the decoder's bucket table: one resize
            # (and at most one retrace) per boundary crossing. Its length
            # is fixed at creation, so size it for everything already
            # submitted (still-queued requests were never checked
            # against an arena).
            b = bucket_for(n_active, self.decoder.buckets)
            need = [r.prompt_len + r.max_new for r in admits]
            if self.arena is None:
                need += [sched.sequences[u].prompt_len
                         + sched.sequences[u].max_new for u in sched.queue]
            counts["resized"] = int(self._ensure_arena(b, max(need,
                                                              default=0)))
            if admits:
                self._admit(admits, counts)
            sp.set_metadata(admitted=len(admits))
        n_slots = self.arena.n_slots
        counts["rows"], counts["live"] = n_slots, n_active

        rows = self.arena.rows_for(sched.running)
        with spans.span(spans.SAMPLE):
            idx = jnp.asarray(rows, jnp.int32)
            self.key, sk = jax.random.split(self.key)
            toks_active = sample_tokens(sk, self._last.take(idx, axis=0),
                                        self._temperature)    # (n_active,)
        toks_active = self._pull(toks_active, "tokens", counts)
        with spans.span(spans.DECODE):
            plan_b, step_fn = self.decoder.executable_for(n_active)
            counts["switched"] = int(b != self._bucket)
            self._bucket = b
            feed = np.zeros((n_slots,), np.int32)
            feed[rows] = toks_active
            mask = np.zeros((n_slots,), bool)
            mask[rows] = True
            logits, cache, cidx = step_fn(self.params,
                                          jnp.asarray(feed)[:, None],
                                          self.arena.cache, jnp.asarray(mask))
            self.arena.cache = cache
            self._last = logits[:, 0]
        counts["built"] = (len(self.decoder._cache) + len(self._prefill_fns)
                           - built)

        trace = self._pull(cidx, "clusters", counts)
        with spans.span(spans.PRICE):
            ctx = float(np.mean([sched.sequences[u].prompt_len
                                 + sched.sequences[u].n_generated
                                 for u in sched.running]))
            ops = self.storage.key_ops
            st = self.storage.step(trace, plan_b, n_active, ctx)
            counts["price_keys"] = self.storage.key_ops - ops
            self.clock_s += st.effective_s

        with spans.span(spans.FINISH):
            tok_map = {u: int(feed[s])
                       for u, s in zip(sched.running, rows)}
            for u in sched.running:
                req = sched.sequences[u]
                if req.first_token_time is None:
                    req.first_token_time = self.clock_s
            done = sched.step(tok_map)
            for u in done:
                sched.sequences[u].finish_time = self.clock_s
                self.arena.release(u)
        return StepResult(stats=st, tokens=tok_map,
                          admitted=[r.uid for r in admits], finished=done,
                          t_s=self.clock_s, trace=trace, counts=counts)

    def next_logits(self, uids) -> np.ndarray:
        """(len(uids), V) fp32 logits the running requests' next tokens
        are sampled from — after a step(), that step's decode logits."""
        if self.replicas is not None:
            raise ValueError("next_logits reads one replica's KV slots; "
                             "ask the replica engine")
        rows = jnp.asarray(self.arena.rows_for(list(uids)), jnp.int32)
        return np.asarray(self._last.take(rows, axis=0), np.float32)

    def cancel(self, uids):
        """Force-finish requests (Best-of-N early stop / client
        cancel). Running requests release their KV slot immediately;
        still-queued requests are dequeued before ever being admitted
        — they finish with no tokens and `first_token_time` stays
        None, so reports must (and do) filter them from TTFT."""
        if self.replicas is not None:
            for uid in list(uids):
                r, local = self.router.locate(uid)
                was_running = local in self.replicas[r].sched.running
                self.replicas[r].cancel([local])
                if was_running:
                    # mirror BatchScheduler.finish: a between-step
                    # cancel is a decay event on the merged timeline
                    self.router.batch_history.append(
                        self.router.batch_size)
            return
        for uid in list(uids):
            if uid in self.sched.running:
                self.sched.finish(uid, self.clock_s)
                self.arena.release(uid)
            elif not self.sched.sequences[uid].finished:
                self.sched.finish(uid, self.clock_s)   # queued: no slot yet

    def run_until_drained(self, max_steps: int = 100000) -> ServeReport:
        """Step until queue and batch are empty. The report covers every
        request finished so far (including cancellations and requests
        completed by manual step() calls before the drain).

        Replica-routed engines merge every replica's TokenStats onto
        the shared timeline (ordered by each step's completion time)
        and report the drained makespan as `span_s`; requests come
        back in global-uid (submission) order."""
        if self.replicas is not None:
            log = []
            for _ in range(max_steps):
                r = self.step()
                if r is None:
                    break
                log.append((r.t_s, r.replica, r.stats))
            log.sort(key=lambda e: (e[0], e[1]))
            reqs = [self.router.request(u) for u in self.router.assignment]
            return ServeReport(
                stats=[s for _, _, s in log],
                requests=[q for q in reqs if q.finished],
                span_s=max(r.clock_s for r in self.replicas))
        stats = []
        for _ in range(max_steps):
            r = self.step()
            if r is None:
                break
            stats.append(r.stats)
        return ServeReport(stats=stats,
                           requests=[r for r in
                                     self.sched.sequences.values()
                                     if r.finished],
                           span_s=self.clock_s)

    # ---------------------------------------------- compatibility API ----
    def generate(self, prompt_tokens, max_new: int = 32,
                 temperature: float = 0.8,
                 completion_schedule: Optional[dict] = None,
                 eos_id: Optional[int] = None) -> GenerationResult:
        """Static-batch wrapper over the continuous loop: submit B
        requests at the current clock, drain, return (B, max_new)
        tokens. With the default integer bucket table this reproduces
        the seed engine token-for-token (same executables, same
        sampling-key sequence, same storage trace).

        completion_schedule: {step: n_finish} forces sequences to finish
        (reproduces Fig 13's Best-of-N batch decay deterministically).
        """
        prompt = np.asarray(prompt_tokens)
        B, S = prompt.shape
        if self.replicas is not None:
            raise ValueError(
                "generate() is the static-batch compat path; a "
                "replica-routed engine serves via submit()/"
                "run_until_drained()")
        assert not self.sched.has_work, \
            "generate() requires an idle engine (drain submitted work first)"
        # wall_s is an observability stat, never fed back into the
        # modeled device clock or any scheduling decision
        t_wall = time.perf_counter()  # repro: ignore[wall-clock]
        old_temp, old_eos = self._temperature, self.sched.eos_id
        self._temperature = temperature
        self.sched.eos_id = eos_id
        # static batch wants an exact-length arena (seed behavior)
        if self.arena is not None and self.arena.max_len != S + max_new \
                and self.ctx_budget is None:
            self.arena = None
        uids = [self.submit(prompt[i], max_new) for i in range(B)]
        stats = []
        step_i = 0
        try:
            while self.sched.has_work:
                r = self.step()
                if r is None:
                    break
                stats.append(r.stats)
                if completion_schedule and step_i in completion_schedule:
                    still = [u for u in uids if u in self.sched.running]
                    self.cancel(still[: completion_schedule[step_i]])
                step_i += 1
        finally:
            self._temperature, self.sched.eos_id = old_temp, old_eos
        tokens = np.full((B, max_new), -1, np.int32)
        for i, u in enumerate(uids):
            gen = self.sched.sequences[u].generated
            tokens[i, :len(gen)] = gen
        return GenerationResult(
            tokens=tokens, stats=stats,
            # observability only, see t_wall above
            wall_s=time.perf_counter() - t_wall)  # repro: ignore[wall-clock]
