"""Distribution tests in a subprocess with 8 forced host devices
(device count locks at first jax init, so the main test process stays
single-device)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str, timeout=420, ndev=8):
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=%d"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax import make_mesh, set_mesh
        from jax.sharding import AxisType
    """ % ndev) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_shard_map_cold_path_matches_local_8dev(backend):
    """The shard-local cold path must reproduce the single-device jnp
    math — output within tolerance, selected cluster ids identical —
    for every mesh whose 'model' size divides the plan's groups, under
    both cold-path backends (pallas = the fused kernel, interpret mode,
    running inside the shard_map body — DESIGN.md §10)."""
    out = run_in_subprocess("""
        import dataclasses
        from repro.core.sparse_ffn import init_ffn, ffn_hybrid
        from repro.core.clusters import HybridPlan
        D, N, cs, G = 64, 512, 32, 4
        params = init_ffn(jax.random.key(0), D, N, "relu2", jnp.float32,
                          predictor_rank=16)
        x = jax.random.normal(jax.random.key(1), (2, D)) * 0.5
        plan = HybridPlan(n_hot=128, k_cold=64, groups=G, cluster_size=cs)
        # reference is always the single-device jnp chain
        y_local, cidx_local = ffn_hybrid(params, x, "relu2", "relu", plan,
                                         return_indices=True)
        plan = dataclasses.replace(plan, backend=%r)
        for nd, nm in ((2, 4), (2, 2), (1, 4)):
            mesh = make_mesh((nd, nm), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=jax.devices()[:nd * nm])
            with set_mesh(mesh):
                spec = {"w": NamedSharding(mesh, P("model", None, None)),
                        "pred": {"A": NamedSharding(mesh, P(None, None)),
                                 "B": NamedSharding(mesh, P(None, "model"))}}
                ps = jax.tree.map(jax.device_put, params, spec)
                y_sm, cidx = jax.jit(lambda p, xx: ffn_hybrid(
                    p, xx, "relu2", "relu", plan,
                    return_indices=True))(ps, x)
            np.testing.assert_allclose(np.asarray(y_sm),
                                       np.asarray(y_local),
                                       atol=1e-3, rtol=1e-3)
            np.testing.assert_array_equal(np.asarray(cidx),
                                          np.asarray(cidx_local))
        print("OK shard_map")
    """ % backend)
    assert "OK shard_map" in out


def test_sharded_train_step_matches_single_device():
    out = run_in_subprocess("""
        from repro.configs import get_config
        from repro.models.model import build_model
        from repro.optim.adamw import AdamW
        from repro.train.steps import make_train_step
        from repro.launch.input_specs import param_specs

        cfg = get_config("smollm-135m").reduced()
        model = build_model(cfg)
        opt = AdamW(lr=1e-3)
        params = model.init(jax.random.key(0))
        state = opt.init(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32),
                 "labels": rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32)}
        step = make_train_step(model, opt)
        _, _, m1 = jax.jit(step)(params, state, batch)

        mesh = make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        with set_mesh(mesh):
            specs = param_specs(model, cfg, mesh)
            ps = jax.tree.map(lambda a, s: jax.device_put(a, s.sharding),
                              params, specs)
            ss = opt.init(ps)
            b = {k: jax.device_put(v, NamedSharding(mesh, P("data", None)))
                 for k, v in batch.items()}
            _, _, m2 = jax.jit(step)(ps, ss, b)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   atol=1e-3, rtol=1e-4)
        print("OK sharded train", float(m1["loss"]), float(m2["loss"]))
    """)
    assert "OK sharded train" in out


def test_sharded_moe_forward_matches_single_device():
    out = run_in_subprocess("""
        from repro.configs import get_config
        from repro.models.model import build_model
        from repro.launch.input_specs import param_specs

        cfg = get_config("deepseek-moe-16b").reduced().replace(
            moe_capacity_factor=8.0, moe_dispatch_groups=2)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(0, cfg.vocab_size,
                                        (4, 32)).astype(np.int32)}
        y1 = jax.jit(lambda p, b: model.forward(p, b))(params, batch)

        mesh = make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        with set_mesh(mesh):
            specs = param_specs(model, cfg, mesh)
            ps = jax.tree.map(lambda a, s: jax.device_put(a, s.sharding),
                              params, specs)
            b = {"tokens": jax.device_put(
                batch["tokens"], NamedSharding(mesh, P("data", None)))}
            y2 = jax.jit(lambda p, bb: model.forward(p, bb))(ps, b)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=2e-3, rtol=2e-3)
        print("OK sharded moe")
    """)
    assert "OK sharded moe" in out


def test_tensor_parallel_decode_token_identical_4dev():
    """The tentpole guarantee (golden comparison): the serving engine
    over a forced 4-host-device mesh decodes token-for-token what the
    single-device engine decodes — same grouped plan, same sampling-key
    sequence, cluster selection shard-local — while the storage plane
    reports per-shard accounting."""
    out = run_in_subprocess("""
        from repro.configs import get_config
        from repro.core.planner import build_plan, permute_ffn_params
        from repro.core.clusters import make_plan, scale_plan_for_batch
        from repro.data.pipeline import DataConfig, SyntheticTokens
        from repro.models.model import build_model
        from repro.optim.adamw import AdamW
        from repro.train.steps import make_train_step
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.engine import ServeEngine

        cfg = get_config("smollm-135m").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        # brief training: real logit margins so greedy decode is
        # robust to the mesh's fp reassociation noise (~1e-5)
        opt = AdamW(lr=2e-3)
        step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
        state = opt.init(params)
        data = SyntheticTokens(DataConfig(cfg.vocab_size, 64, 4, seed=0))
        for _ in range(30):
            params, state, _ = step(params, state, data.batch())

        plan = build_plan(cfg)
        base = make_plan(cfg.d_ff, 0.25, 0.25, cfg.sparse_ffn.cluster_size,
                         groups=4)
        plan.plans = {b: scale_plan_for_batch(base, cfg.d_ff, b,
                                              cfg.sparse_ffn.cluster_size)
                      for b in (1, 2, 4, 8)}
        params = permute_ffn_params(params, plan.neuron_order)

        def run(mesh, backend=None):
            eng = ServeEngine(cfg, params, plan, buckets=(1, 2, 4),
                              ctx_budget=48, temperature=0.0, seed=0,
                              mesh=mesh, backend=backend)
            rng = np.random.default_rng(0)
            for i in range(3):
                eng.submit(rng.integers(0, cfg.vocab_size, 16), max_new=8,
                           arrival_time=i * 1e-3)
            rep = eng.run_until_drained()
            toks = {u: list(r.generated)
                    for u, r in eng.sched.sequences.items()}
            eng.close()
            return rep, toks

        rep1, toks1 = run(None)
        rep4, toks4 = run(make_serving_mesh(4))
        assert toks1 == toks4, (toks1, toks4)
        assert all(len(t) == 8 for t in toks1.values())
        # the fused pallas cold path (DESIGN.md §10) decodes the same
        # tokens as jnp, single-device and under the tp=4 mesh
        _, toksp1 = run(None, backend="pallas")
        assert toksp1 == toks1, (toksp1, toks1)
        _, toksp4 = run(make_serving_mesh(4), backend="pallas")
        assert toksp4 == toks1, (toksp4, toks1)
        s1, s4 = rep1.stats[0], rep4.stats[0]
        assert s1.n_shards == 1 and s1.shards is None
        assert s4.n_shards == 4 and len(s4.shards) == 4
        # per-shard raw I/O demand shrinks vs the single-device plane
        assert s4.io_s <= s1.io_s + 1e-12
        assert abs(s4.io_total_s
                   - sum(sh.io_s for sh in s4.shards)) < 1e-12
        # modeled per-step time must not regress under the mesh split
        e1 = sum(s.effective_s for s in rep1.stats)
        e4 = sum(s.effective_s for s in rep4.stats)
        assert e4 <= e1 * 1.01, (e1, e4)
        print("OK tp golden", len(rep4.stats), round(e1 / e4, 3))
    """, ndev=4)
    assert "OK tp golden" in out


def test_expert_parallel_moe_decode_token_identical_4dev():
    """The EP tentpole golden: a MoE engine over a forced-host-device
    mesh — experts sharded E/n per 'model' shard, dispatch/combine
    shard-local with one psum per layer (_moe_ep_shard_map) — decodes
    token-for-token what the single-device engine decodes, at ep=2 and
    composed dp=2 x ep=2; the storage plane reports per-shard expert
    slices whose raw I/O demand never exceeds the single-device
    plane's."""
    out = run_in_subprocess("""
        from repro.configs import get_config
        from repro.core.planner import build_moe_plan
        from repro.data.pipeline import DataConfig, SyntheticTokens
        from repro.models.model import build_model
        from repro.optim.adamw import AdamW
        from repro.train.steps import make_train_step
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.engine import ServeEngine

        cfg = get_config("deepseek-moe-16b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        # brief training: real logit margins so greedy decode is
        # robust to the mesh's fp reassociation noise (~1e-5)
        opt = AdamW(lr=2e-3)
        step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
        state = opt.init(params)
        data = SyntheticTokens(DataConfig(cfg.vocab_size, 64, 4, seed=0))
        for _ in range(20):
            params, state, _ = step(params, state, data.batch())
        plan = build_moe_plan(cfg)

        def run(mesh):
            eng = ServeEngine(cfg, params, plan, buckets=(1, 2),
                              ctx_budget=48, temperature=0.0, seed=0,
                              mesh=mesh)
            rng = np.random.default_rng(0)
            for i in range(3):
                eng.submit(rng.integers(0, cfg.vocab_size, 16), max_new=6,
                           arrival_time=i * 1e-3)
            rep = eng.run_until_drained()
            toks = {u: list(r.generated)
                    for u, r in eng.sched.sequences.items()}
            eng.close()
            return rep, toks

        rep1, toks1 = run(None)
        rep2, toks2 = run(make_serving_mesh(2))
        assert toks1 == toks2, (toks1, toks2)
        assert all(len(t) == 6 for t in toks1.values())
        s1, s2 = rep1.stats[0], rep2.stats[0]
        assert s1.n_shards == 1 and s1.shards is None
        assert s2.n_shards == 2 and len(s2.shards) == 2
        # per-shard raw I/O demand (the shard's expert slice) shrinks
        assert s2.io_s <= s1.io_s + 1e-12
        assert abs(s2.io_total_s
                   - sum(sh.io_s for sh in s2.shards)) < 1e-12

        # dp=2 x ep=2 over a (2, 2) mesh: replica routing composes
        # with expert parallelism without changing a single token
        # (per-request greedy decode is batch-composition-free)
        repg, toksg = run(make_serving_mesh(2, 2))
        assert toksg == toks1, (toksg, toks1)
        assert all(s.n_shards == 2 and len(s.shards) == 2
                   for s in repg.stats)
        assert {s.replica for s in repg.stats} == {0, 1}
        print("OK ep golden", len(rep2.stats))
    """, ndev=4, timeout=600)
    assert "OK ep golden" in out


def test_intra_expert_moe_decode_token_identical_4dev():
    """The two-level golden (DESIGN.md §9): intra-expert decode —
    per-expert hot/cold clusters, per-expert hot-first permutation,
    (L, E, 1+ncc) trace — is token-identical to the dense-expert
    decode at ep=1 AND over a 2-shard expert-parallel mesh (the
    per-expert cold gathers stay shard-local; the trace blocks
    all_gather in expert order), while per-shard raw I/O demand
    shrinks vs the single-device plane."""
    out = run_in_subprocess("""
        from repro.configs import get_config
        from repro.data.pipeline import DataConfig, SyntheticTokens
        from repro.models.model import build_model
        from repro.optim.adamw import AdamW
        from repro.train.steps import make_train_step
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.engine import ServeEngine
        from repro.serving.families import serving_family

        cfg = get_config("turbosparse-mixtral-47b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        # brief training: real logit margins so greedy decode is
        # robust to the permutation's fp reassociation noise (~1e-5)
        opt = AdamW(lr=2e-3)
        step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
        state = opt.init(params)
        data = SyntheticTokens(DataConfig(cfg.vocab_size, 64, 4, seed=0))
        for _ in range(20):
            params, state, _ = step(params, state, data.batch())

        fam = serving_family(cfg)
        plan = fam.build_plan(cfg)
        assert all(p.n_expert_hot > 0 for p in plan.plans.values())
        p_intra = fam.prepare_params(params, plan)
        cfgw = cfg.replace(moe_intra_expert=False)
        planw = serving_family(cfgw).build_plan(cfgw)

        def run(c, pp, pl, mesh):
            eng = ServeEngine(c, pp, pl, buckets=(1, 2), ctx_budget=48,
                              temperature=0.0, seed=0, mesh=mesh)
            rng = np.random.default_rng(0)
            for i in range(3):
                eng.submit(rng.integers(0, c.vocab_size, 16), max_new=6,
                           arrival_time=i * 1e-3)
            rep = eng.run_until_drained()
            toks = {u: list(r.generated)
                    for u, r in eng.sched.sequences.items()}
            eng.close()
            return rep, toks

        # dense-expert reference (whole-expert plan, unpermuted params)
        _, toks_ref = run(cfgw, params, planw, None)
        rep1, toks1 = run(cfg, p_intra, plan, None)
        assert toks1 == toks_ref, (toks1, toks_ref)
        rep2, toks2 = run(cfg, p_intra, plan, make_serving_mesh(2))
        assert toks2 == toks_ref, (toks2, toks_ref)
        assert all(len(t) == 6 for t in toks1.values())
        s1, s2 = rep1.stats[0], rep2.stats[0]
        assert s1.n_shards == 1 and s1.shards is None
        assert s2.n_shards == 2 and len(s2.shards) == 2
        assert s2.io_s <= s1.io_s + 1e-12
        assert abs(s2.io_total_s
                   - sum(sh.io_s for sh in s2.shards)) < 1e-12
        print("OK two-level ep golden", len(rep2.stats))
    """, ndev=4, timeout=600)
    assert "OK two-level ep golden" in out


def test_data_parallel_replica_routing_token_identical_4dev():
    """The dp tentpole golden: over a (2, 1) mesh the engine routes
    the seeded arrival trace across two replicas and decodes
    token-identical to two independent dp=1 engines fed the routed
    sub-streams; over a (2, 2) mesh each replica additionally
    tensor-shards on its own mesh row, leaving tokens unchanged while
    the merged report shows per-replica per-shard accounting and a
    shared-timeline span that beats the single-replica drain."""
    out = run_in_subprocess("""
        from repro.configs import get_config
        from repro.core.planner import build_plan, permute_ffn_params
        from repro.core.clusters import make_plan, scale_plan_for_batch
        from repro.data.pipeline import DataConfig, SyntheticTokens
        from repro.models.model import build_model
        from repro.optim.adamw import AdamW
        from repro.train.steps import make_train_step
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.engine import ServeEngine

        cfg = get_config("smollm-135m").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        # brief training: real logit margins so greedy decode is
        # robust to the mesh's fp reassociation noise (~1e-5)
        opt = AdamW(lr=2e-3)
        step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
        state = opt.init(params)
        data = SyntheticTokens(DataConfig(cfg.vocab_size, 64, 4, seed=0))
        for _ in range(30):
            params, state, _ = step(params, state, data.batch())

        plan = build_plan(cfg)
        base = make_plan(cfg.d_ff, 0.25, 0.25, cfg.sparse_ffn.cluster_size,
                         groups=2)
        plan.plans = {b: scale_plan_for_batch(base, cfg.d_ff, b,
                                              cfg.sparse_ffn.cluster_size)
                      for b in (1, 2, 4, 8)}
        params = permute_ffn_params(params, plan.neuron_order)

        # near-simultaneous arrivals: the stream overlaps, so replica
        # concurrency actually shortens the drained span (with spaced
        # arrivals each request drains before the next one lands and
        # dp buys nothing on this tiny modeled workload)
        rng = np.random.default_rng(0)
        reqs = [(rng.integers(0, cfg.vocab_size, 16),
                 6, i * 1e-6) for i in range(4)]

        def make(mesh=None, dp=None, backend=None):
            return ServeEngine(cfg, params, plan, buckets=(1, 2),
                               ctx_budget=48, temperature=0.0, seed=0,
                               mesh=mesh, dp=dp, backend=backend)

        def serve(eng, stream):
            uids = [eng.submit(p, m, arrival_time=t) for p, m, t in stream]
            rep = eng.run_until_drained()
            toks = {u: list(eng.sched.sequences[u].generated)
                    for u in uids}
            return rep, toks

        # dp=2 over the mesh's 'data' axis (tp=1)
        dp_eng = make(mesh=make_serving_mesh(1, 2))
        assert dp_eng.replicas is not None and len(dp_eng.replicas) == 2
        rep_dp, toks_dp = serve(dp_eng, reqs)
        assignment = dict(dp_eng.router.assignment)
        clocks = [r.clock_s for r in dp_eng.replicas]
        dp_eng.close()
        assert {r for r, _ in assignment.values()} == {0, 1}
        assert rep_dp.span_s == max(clocks)
        assert {s.replica for s in rep_dp.stats} == {0, 1}

        # golden: two independent dp=1 engines fed the routed streams
        toks_ref = {}
        for r in (0, 1):
            sub = make()
            local = {}
            for g, (ri, _) in sorted(assignment.items()):
                if ri == r:
                    p, m, t = reqs[g]
                    local[sub.submit(p, m, arrival_time=t)] = g
            sub.run_until_drained()
            for lu, g in local.items():
                toks_ref[g] = list(sub.sched.sequences[lu].generated)
            sub.close()
        assert toks_dp == toks_ref, (toks_dp, toks_ref)
        assert all(len(t) == 6 for t in toks_dp.values())

        # dp=2 x tp=2 over a (2, 2) mesh: per-replica tensor sharding
        # must not change a single token, and each step carries the
        # per-shard breakdown of its replica's storage plane
        grid_eng = make(mesh=make_serving_mesh(2, 2))
        rep_grid, toks_grid = serve(grid_eng, reqs)
        grid_eng.close()
        assert toks_grid == toks_dp, (toks_grid, toks_dp)
        assert all(s.n_shards == 2 and len(s.shards) == 2
                   for s in rep_grid.stats)

        # the fused pallas cold path over the same (2, 2) grid:
        # replica routing x tensor sharding x kernel backend, still
        # token-identical (DESIGN.md §10)
        pal_eng = make(mesh=make_serving_mesh(2, 2), backend="pallas")
        _, toks_pal = serve(pal_eng, reqs)
        pal_eng.close()
        assert toks_pal == toks_dp, (toks_pal, toks_dp)

        # the shared-timeline span beats draining the same trace on a
        # single replica (replicas decode concurrently)
        single = make()
        rep_1, toks_1 = serve(single, reqs)
        single.close()
        assert rep_dp.span_s < rep_1.span_s, (rep_dp.span_s, rep_1.span_s)
        assert rep_dp.total_tokens == rep_1.total_tokens
        print("OK dp golden", len(rep_dp.stats),
              round(rep_1.span_s / rep_dp.span_s, 3))
    """, ndev=4, timeout=600)
    assert "OK dp golden" in out
