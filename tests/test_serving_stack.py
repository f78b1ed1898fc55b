"""Layered serving stack: continuous batching (mid-stream admission,
bucket-boundary retrace discipline), slot-based KV recycling, the
generate() compatibility wrapper vs the seed decode loop,
StoragePlane.step determinism with/without the prefetch thread, and
data-parallel replica routing (meshless dp — the scheduler-level
mechanism; the meshed goldens live in test_distributed.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.adaptation import BucketedDecoder
from repro.core.baselines import POWERINFER2
from repro.core.planner import build_plan, permute_ffn_params
from repro.models import dense
from repro.serving.engine import GenerationResult, ServeEngine, ServeReport
from repro.serving.sampler import sample_tokens
from repro.serving.scheduler import BatchScheduler, ReplicaRouter
from repro.serving.storage_plane import StoragePlane


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-135m").reduced()
    model = dense.make_model(cfg)
    params = model.init(jax.random.key(0))
    plan = build_plan(cfg)
    params = permute_ffn_params(params, plan.neuron_order)
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 16)).astype(np.int32)
    return cfg, params, plan, prompt


# ------------------------------------------------- continuous batching ----

def test_midstream_admission_grows_then_decays(setup):
    """A request admitted at step k>0 joins the running batch, crosses
    a bucket boundary with at most one decoder retrace, and completes;
    batch_history shows growth then decay."""
    cfg, params, plan, _ = setup
    eng = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                      offload_ratio=0.5, buckets=(1, 2, 4, 8),
                      ctx_budget=40, temperature=0.8)
    rng = np.random.default_rng(1)
    for _ in range(2):
        eng.submit(rng.integers(0, cfg.vocab_size, 16), max_new=8)
    r = eng.step()
    assert r.stats.batch == 2
    eng.step()

    # mid-stream admission: 2 -> 3 crosses the 2->4 bucket boundary
    traces0 = len(eng.decoder._cache)
    resizes0 = eng.arena.resizes
    uid = eng.submit(rng.integers(0, cfg.vocab_size, 16), max_new=4)
    r = eng.step()
    assert uid in r.admitted
    assert r.stats.batch == 3
    assert eng.arena.n_slots == 4                      # next bucket
    assert r.counts["switched"] == 1                   # one swap
    assert len(eng.decoder._cache) - traces0 == 1      # one new trace
    assert eng.arena.resizes - resizes0 == 1           # one reshape

    rep = eng.run_until_drained()
    assert not eng.sched.has_work
    assert eng.sched.sequences[uid].finished
    hist = eng.sched.batch_history
    assert max(hist) == 3 and hist[0] == 2 and hist[-1] == 0
    grow = hist.index(3)
    assert any(b < 3 for b in hist[grow:])             # decay after growth
    # the joiner generated its full budget
    assert len(eng.sched.sequences[uid].generated) == 4
    assert rep.total_tokens == sum(s.batch for s in rep.stats)


def test_kv_slots_recycled_after_completion(setup):
    """A completed request's slot returns to the free list and is
    reused by the next admission without any arena reshape."""
    cfg, params, plan, _ = setup
    eng = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                      offload_ratio=0.5, buckets=(1, 2, 4),
                      ctx_budget=40, temperature=0.8)
    rng = np.random.default_rng(2)
    uids = [eng.submit(rng.integers(0, cfg.vocab_size, 16), max_new=n)
            for n in (2, 6, 6, 6)]
    eng.step()
    r = eng.step()                                     # uid 0 completes here
    assert uids[0] in r.finished
    freed_slot = 0
    assert freed_slot in eng.arena.free
    resizes0 = eng.arena.resizes

    new_uid = eng.submit(rng.integers(0, cfg.vocab_size, 16), max_new=2)
    r = eng.step()
    assert new_uid in r.admitted
    assert eng.arena.slot_of[new_uid] == freed_slot    # recycled
    assert eng.arena.resizes == resizes0               # no reshape
    assert eng.arena.n_slots == 4
    eng.run_until_drained()
    assert not eng.sched.has_work
    assert eng.arena.n_free == eng.arena.n_slots


def test_finish_records_batch_decay_on_timeline():
    """Force-finishing a running request between step() calls is a
    batch-decay event the adaptation timeline must see; dequeuing a
    still-queued request is not (no live batch changed)."""
    sched = BatchScheduler()
    r1 = sched.add(4, 8)
    r2 = sched.add(4, 8)
    sched.step({r1.uid: 1, r2.uid: 2})
    assert sched.batch_history == [2]
    sched.finish(r1.uid, now=1.0)                      # running -> decay
    assert sched.batch_history == [2, 1]
    assert r1.finished and r1.finish_time == 1.0
    r3 = sched.submit(np.arange(4), 8, arrival_time=9.0)
    sched.finish(r3.uid)                               # queued -> no entry
    assert sched.batch_history == [2, 1]
    assert r3.finished and r3.uid not in sched.queue


def test_scheduler_admission_queue_fifo():
    sched = BatchScheduler()
    r1 = sched.submit(np.arange(4), 8, arrival_time=0.0)
    r2 = sched.submit(np.arange(4), 8, arrival_time=5.0)
    r3 = sched.submit(np.arange(4), 8, arrival_time=1.0)
    # r2 blocks the head at t=2 even though r3 has arrived (FIFO)
    assert [r.uid for r in sched.pop_admissible(2.0, 10)] == [r1.uid]
    assert sched.next_arrival() == 5.0
    got = sched.pop_admissible(6.0, 10)
    assert [r.uid for r in got] == [r2.uid, r3.uid]
    assert sched.pop_admissible(100.0, 10) == []


# -------------------------------------------------- compat wrapper ----

def _reference_generate(cfg, params, plan, prompt, max_new, temperature,
                        seed=0):
    """The seed engine's decode loop (static batch, compaction-by-take),
    data plane only — the behavioral contract generate() must keep."""
    model = dense.make_model(cfg)
    step_traced = dense.make_decode_step(cfg, collect_indices=True)
    decoder = BucketedDecoder(
        plan_source=plan,
        make_step=lambda p: (lambda pr, t, c: step_traced(pr, t, c, p)),
        buckets=tuple(range(1, 65)))
    key = jax.random.key(seed)
    prompt = jnp.asarray(prompt)
    B, S = prompt.shape
    logits, cache = jax.jit(lambda p, b: model.prefill(
        p, b, max_len=S + max_new))(params, {"tokens": prompt})
    out = np.full((B, max_new), -1, np.int32)
    active = list(range(B))
    n_gen = {i: 0 for i in active}
    last = logits[:, -1]
    for _step in range(max_new):
        if not active:
            break
        _, step_fn = decoder.executable_for(len(active))
        key, sk = jax.random.split(key)
        toks = sample_tokens(sk, last, temperature)
        logits, cache, _ = step_fn(params, toks[:, None], cache)
        last = logits[:, 0]
        finish = []
        for row, uid in enumerate(active):
            out[uid, n_gen[uid]] = int(toks[row])
            n_gen[uid] += 1
            if n_gen[uid] >= max_new:
                finish.append(uid)
        if finish:
            keep = [r for r, u in enumerate(active) if u not in finish]
            active = [u for u in active if u not in finish]
            if keep and len(keep) < len(n_gen):
                rows = jnp.asarray(keep)
                cache = {"k": cache["k"].take(rows, axis=1),
                         "v": cache["v"].take(rows, axis=1),
                         "kv_pos": cache["kv_pos"].take(rows, axis=0),
                         "length": cache["length"].take(rows, axis=0)}
                last = last.take(rows, axis=0)
    return out


def test_generate_matches_seed_loop(setup):
    """generate() (continuous loop + slot arena + active-mask union)
    reproduces the seed static-batch path token-for-token."""
    cfg, params, plan, prompt = setup
    ref = _reference_generate(cfg, params, plan, prompt, max_new=6,
                              temperature=0.8, seed=0)
    eng = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                      offload_ratio=0.5, seed=0)
    res = eng.generate(prompt, max_new=6, temperature=0.8)
    assert np.array_equal(res.tokens, ref)


def test_generate_deterministic_and_stats_shape(setup):
    cfg, params, plan, prompt = setup
    r1 = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                     offload_ratio=0.5).generate(prompt, max_new=4,
                                                 temperature=0.0)
    r2 = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                     offload_ratio=0.5).generate(prompt, max_new=4,
                                                 temperature=0.0)
    assert np.array_equal(r1.tokens, r2.tokens)
    assert [s.batch for s in r1.stats] == [4, 4, 4, 4]


# ------------------------------------------------------ storage plane ----

def test_storage_plane_stats_prefetch_invariant(setup):
    """The prefetch thread moves real bytes but must not change any
    modeled number: step() stats with the I/O thread on equal the
    sequential (pre-refactor _storage_step) pricing exactly."""
    cfg, params, plan, prompt = setup

    def run(prefetch):
        eng = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                          offload_ratio=0.5, prefetch=prefetch, seed=0)
        res = eng.generate(prompt, max_new=5, temperature=0.0)
        return eng, res

    eng_p, res_p = run(True)
    eng_s, res_s = run(False)
    assert eng_p.storage.prefetcher is not None
    assert eng_p.storage.prefetcher.submitted > 0
    assert eng_s.storage.prefetcher is None
    assert np.array_equal(res_p.tokens, res_s.tokens)
    for a, b in zip(res_p.stats, res_s.stats):
        assert a == b                      # dataclass field-wise equality
    assert eng_p.coldstore.total_bytes == eng_s.coldstore.total_bytes
    assert eng_p.coldstore.total_io_time == eng_s.coldstore.total_io_time
    assert eng_p.cache.stats.hits == eng_s.cache.stats.hits
    assert eng_p.cache.stats.misses == eng_s.cache.stats.misses


def test_engine_has_no_storage_pricing(setup):
    """Acceptance: the orchestrator no longer owns storage-plane
    pricing; cache/coldstore construction lives in StoragePlane."""
    cfg, params, plan, _ = setup
    import inspect
    from repro.serving import engine as engine_mod
    src = inspect.getsource(engine_mod)
    assert "_storage_step" not in src
    assert "NeuronCache(" not in src
    assert "ColdStore(" not in src
    eng = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                      offload_ratio=0.5)
    assert isinstance(eng.storage, StoragePlane)
    # legacy read access still works
    assert eng.cache is eng.storage.cache
    assert eng.coldstore is eng.storage.coldstore


# ------------------------------------------------- replica routing (dp) ----

def _dp_engine(cfg, params, plan, dp=None, seed=0):
    return ServeEngine(cfg, params, plan, spec=POWERINFER2,
                       offload_ratio=0.5, buckets=(1, 2, 4),
                       ctx_budget=40, temperature=0.8, seed=seed, dp=dp)


def test_replica_router_least_loaded_fifo_tiebreak():
    """Equal loads round-robin (FIFO over replicas); an unbalanced
    replica is skipped until loads equalize."""
    scheds = [BatchScheduler(), BatchScheduler()]
    router = ReplicaRouter(scheds)
    picks = []
    for _i in range(4):
        r = router.pick_replica()
        picks.append(r)
        local = scheds[r].submit(np.arange(4), 8).uid
        assert router.locate(router.bind(r, local)) == (r, local)
    assert picks == [0, 1, 0, 1]
    # load replica 1 twice more: next two picks must go to replica 0
    for _ in range(2):
        scheds[1].submit(np.arange(4), 8)
    assert router.pick_replica() == 0
    scheds[0].submit(np.arange(4), 8)
    assert router.pick_replica() == 0
    # global-uid view covers every routed request in submission order
    assert list(router.sequences) == [0, 1, 2, 3]
    assert router.has_work


def test_fifo_head_of_line_is_per_replica():
    """Satellite regression: FIFO admission blocks behind the queue
    head *within* a replica only — a not-yet-arrived head routed to
    one replica must not starve an already-arrived request on the
    other (pop_admissible is per-scheduler under the router)."""
    scheds = [BatchScheduler(), BatchScheduler()]
    router = ReplicaRouter(scheds)
    ra = router.pick_replica()                 # A -> replica 0 (far future)
    a = scheds[ra].submit(np.arange(4), 8, arrival_time=50.0)
    router.bind(ra, a.uid)
    rb = router.pick_replica()                 # B -> replica 1 (arrived)
    assert rb != ra
    b = scheds[rb].submit(np.arange(4), 8, arrival_time=0.0)
    router.bind(rb, b.uid)
    # at t=1: A's replica is head-blocked, B's replica admits B
    assert scheds[ra].pop_admissible(1.0, 10) == []
    assert [r.uid for r in scheds[rb].pop_admissible(1.0, 10)] == [b.uid]


def test_dp_head_of_line_engine_vs_single(setup):
    """End to end: the same two-request stream head-blocks a dp=1
    engine until the late head arrives, while a dp=2 engine serves the
    early request immediately on the other replica."""
    cfg, params, plan, _ = setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 16) for _ in range(2)]

    eng1 = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                       offload_ratio=0.5, buckets=(1, 2),
                       ctx_budget=40, temperature=0.8)
    eng1.submit(prompts[0], max_new=4, arrival_time=50.0)
    b1 = eng1.submit(prompts[1], max_new=4, arrival_time=0.0)
    eng1.run_until_drained()
    # single replica: FIFO head A blocks B past A's arrival
    assert eng1.sched.sequences[b1].first_token_time > 50.0

    eng2 = _dp_engine(cfg, params, plan, dp=2)
    a2 = eng2.submit(prompts[0], max_new=4, arrival_time=50.0)
    b2 = eng2.submit(prompts[1], max_new=4, arrival_time=0.0)
    rep = eng2.run_until_drained()
    reqs = eng2.sched.sequences
    assert reqs[b2].finish_time < 50.0         # served while A in flight
    assert reqs[a2].first_token_time > 50.0
    assert len(rep.requests) == 2
    eng1.close(), eng2.close()


def test_dp_engine_token_identical_to_routed_dp1(setup):
    """Tentpole golden (meshless): a dp=2 engine decodes
    token-identical to two independent dp=1 engines fed the routed
    sub-streams, and the merged report aggregates both replica
    timelines."""
    cfg, params, plan, _ = setup
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, cfg.vocab_size, 16),
             int(rng.integers(3, 7)), i * 1e-3) for i in range(5)]

    eng = _dp_engine(cfg, params, plan, dp=2)
    # meshless replicas share jit caches (identical executables on the
    # same params) — dp must not multiply trace time
    assert eng.replicas[1].decoder._cache is eng.replicas[0].decoder._cache
    for p, m, t in reqs:
        eng.submit(p, m, arrival_time=t)
    rep = eng.run_until_drained()
    assert not eng.sched.has_work
    toks_dp = {u: list(r.generated) for u, r in eng.sched.sequences.items()}
    assignment = dict(eng.router.assignment)
    # merged report: every replica contributed, span is the slowest
    # replica's clock, timeline length covers every step
    assert {s.replica for s in rep.stats} == {0, 1}
    assert rep.span_s == max(r.clock_s for r in eng.replicas)
    assert rep.span_s == eng.clock_s
    # one merged entry per replica step (no cancels -> batch_history
    # is exactly one append per step)
    assert len(rep.stats) == sum(len(r.sched.batch_history)
                                 for r in eng.replicas)
    assert rep.throughput_tok_s > 0 and rep.total_tokens == \
        sum(len(t) for t in toks_dp.values())
    assert len(eng.sched.batch_history) == len(rep.stats)
    eng.close()

    toks_ref = {}
    for r in (0, 1):
        sub = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                          offload_ratio=0.5, buckets=(1, 2, 4),
                          ctx_budget=40, temperature=0.8, seed=0)
        local_to_global = {}
        for g, (rep_idx, _) in assignment.items():
            if rep_idx != r:
                continue
            p, m, t = reqs[g]
            local_to_global[sub.submit(p, m, arrival_time=t)] = g
        sub.run_until_drained()
        for lu, g in local_to_global.items():
            toks_ref[g] = list(sub.sched.sequences[lu].generated)
        sub.close()
    assert toks_dp == toks_ref


def test_dp_cancel_routes_and_report_survives(setup):
    """Satellite regression via ServeEngine.cancel(): requests
    cancelled before their first token (still queued, or the whole
    stream) must neither crash the report nor leak into TTFT."""
    cfg, params, plan, _ = setup
    rng = np.random.default_rng(4)

    # whole stream cancelled before any step: empty-report edge
    eng = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                      offload_ratio=0.5, buckets=(1, 2),
                      ctx_budget=40, temperature=0.8)
    uids = [eng.submit(rng.integers(0, cfg.vocab_size, 16), max_new=4)
            for _ in range(2)]
    eng.cancel(uids)
    rep = eng.run_until_drained()
    assert rep.stats == [] and len(rep.requests) == 2
    assert rep.ttft().size == 0                        # None never coerced
    assert rep.tokens_per_s == 0.0 and rep.throughput_tok_s == 0.0
    pct = rep.latency_percentiles()                    # must not raise
    assert pct["p99"] == 0.0
    eng.close()

    # dp engine: cancel routes to the owning replica; a queued cancel
    # finishes tokenless while the rest of the stream completes
    eng = _dp_engine(cfg, params, plan, dp=2)
    keep, drop = [], None
    for i in range(3):
        u = eng.submit(rng.integers(0, cfg.vocab_size, 16), max_new=3,
                       arrival_time=0.0)
        (keep.append(u) if i < 2 else (drop := u))
    eng.cancel([drop])
    rep = eng.run_until_drained()
    reqs = eng.sched.sequences
    assert reqs[drop].finished and reqs[drop].generated == []
    assert reqs[drop].first_token_time is None
    assert all(len(reqs[u].generated) == 3 for u in keep)
    assert rep.ttft().size == 2                        # cancelled filtered
    rep.latency_percentiles()
    eng.close()


def test_dp_failed_submit_does_not_perturb_routing(setup):
    """A submit that fails validation must leave the FIFO tiebreak
    order untouched — the deterministic round-robin resumes as if the
    bad call never happened."""
    cfg, params, plan, _ = setup
    eng = _dp_engine(cfg, params, plan, dp=2)
    with pytest.raises(ValueError):
        eng.submit(np.array([], np.int32), max_new=2)
    u0 = eng.submit(np.arange(4), 2, arrival_time=0.0)
    u1 = eng.submit(np.arange(4), 2, arrival_time=0.0)
    assert eng.router.locate(u0)[0] == 0
    assert eng.router.locate(u1)[0] == 1
    eng.run_until_drained()
    eng.close()


def test_dp_cancel_running_records_merged_decay(setup):
    """A between-step cancel of a running request is a decay event on
    the *merged* batch timeline too, mirroring the per-scheduler
    BatchScheduler.finish fix."""
    cfg, params, plan, _ = setup
    rng = np.random.default_rng(5)
    eng = _dp_engine(cfg, params, plan, dp=2)
    uids = [eng.submit(rng.integers(0, cfg.vocab_size, 16), max_new=6,
                       arrival_time=0.0) for _ in range(2)]
    eng.step(), eng.step()                     # both replicas running
    total0 = eng.sched.batch_size
    assert total0 == 2
    hist0 = len(eng.sched.batch_history)
    eng.cancel([uids[0]])
    assert eng.sched.batch_history[hist0:] == [total0 - 1]
    eng.run_until_drained()
    eng.close()


def test_zero_token_reports_return_zero():
    """Satellite: empty stats must read as 0.0 tok/s (was inf) in both
    report classes, and percentile summaries must not crash."""
    g = GenerationResult(tokens=np.zeros((1, 0), np.int32), stats=[])
    assert g.tokens_per_s == 0.0
    assert g.latency_percentiles()["mean"] == 0.0
    r = ServeReport(stats=[], requests=[])
    assert r.tokens_per_s == 0.0
    assert r.throughput_tok_s == 0.0
    assert r.total_tokens == 0
    assert r.ttft().size == 0
    assert r.latency_percentiles()["p50"] == 0.0
