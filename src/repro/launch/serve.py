"""End-to-end serving driver — the paper's kind of workload.

Plan (offline §5) -> permute weights hot-first -> ServeEngine (online
§4) -> batched generation with Best-of-N and continuous batching.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --offload 0.5 --bon 4 --max-new 32

--reduced (the default) serves the 2-layer CPU-sized variant of the
config; --no-reduced serves the published config at full width.

Tensor-parallel serving (DESIGN.md §3): pass --tp N to run the engine
over an (1, N) device mesh — on CPU hosts force the devices first with
XLA_FLAGS=--xla_force_host_platform_device_count=N.

Data-parallel serving (DESIGN.md §5): pass --dp N to route requests
over N replicas (the mesh's 'data' axis). With --tp 1 the replicas are
scheduler-level and need no extra devices; with --tp > 1 each replica
owns its own (1, tp) row of a (dp, tp) mesh, so dp*tp devices must be
visible. A --dp run serves the Best-of-N prompts as a request stream
(submit/run_until_drained) instead of the static-batch generate().

Families (DESIGN.md §8): --family {dense,vlm,moe} serves that family's
default arch through the registry; for moe, --ep N is the
expert-parallel degree — the same mesh 'model' axis --tp sets for the
dense families (each shard owns E/N experts), so

  PYTHONPATH=src python -m repro.launch.serve --family moe --ep 2 --dp 2

Fleet serving (DESIGN.md §11): --fleet N stands up N complete
single-device engines behind the FleetGateway front door (weighted
least-loaded dispatch, circuit breakers, response LRU, heartbeats) and
serves the prompts as a request stream through it:

  PYTHONPATH=src python -m repro.launch.serve --fleet 2 --bon 8
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ModelConfig, get_config
from repro.core.baselines import POWERINFER2
from repro.core.io_model import UFS40, HOST_DMA
from repro.core.planner import profile_activations
from repro.launch.compile_cache import use_compile_cache
from repro.serving.engine import ServeEngine
from repro.serving.families import default_archs, serving_family

# default arch per servable family (--family shorthand), straight
# from the registry so a newly registered family appears here for free
FAMILY_ARCHS = default_archs()


def init_params(model, seed: int = 0, mesh=None):
    """Seeded random params. With a mesh they are initialised sharded
    in place by one jitted init (each device computes only its own
    shard), so a model too large for one device never lands whole on
    the first."""
    key = jax.random.key(seed)
    if mesh is None:
        return model.init(key)
    shapes = jax.eval_shape(model.init, key)
    shardings = ServeEngine.param_shardings(model, shapes, mesh)
    return jax.jit(model.init, out_shardings=shardings)(key)


def build_engine(arch: str | ModelConfig, reduced: bool = True,
                 offload: float = 0.5, spec=POWERINFER2, storage=UFS40,
                 profile: bool = False, seed: int = 0, tp: int = 1,
                 dp: int = 1, backend: str = "jnp",
                 storage_dtype: str = "fp16", **engine_kwargs):
    """Build one ServeEngine: seeded random params, the offline plan,
    hot-first weights. `arch` is a registry id, of which `reduced`
    picks the CPU-sized variant, or a ModelConfig served as given (a
    depth cut that keeps every width, another dtype)."""
    if isinstance(arch, ModelConfig):
        cfg = arch
    else:
        cfg = get_config(arch)
        if reduced:
            cfg = cfg.reduced()
    fam = serving_family(cfg)
    if tp > 1 and "mesh" not in engine_kwargs:
        from repro.launch.mesh import make_serving_mesh
        engine_kwargs["mesh"] = make_serving_mesh(tp, dp)
    model = fam.make_model(cfg)
    params = init_params(model, seed, engine_kwargs.get("mesh"))
    freqs = None
    if profile and not cfg.num_experts:
        # dense-layer activation profiling; the MoE router needs none
        # (routing is the predictor, experts are the clusters)
        batches = [jax.random.randint(jax.random.key(i), (4, 64), 0,
                                      cfg.vocab_size) for i in range(4)]
        counts, n_tok = profile_activations(params, cfg, batches)
        freqs = (counts / n_tok).astype(np.float32)
    plan = fam.build_plan(cfg, freqs, backend=backend,
                          storage_dtype=storage_dtype)
    params = fam.prepare_params(params, plan)
    if backend != "jnp":
        # the decoder also gets the override so per-bucket plans the
        # planner (or a bench) pinned later still trace the chosen
        # kernel path
        engine_kwargs.setdefault("backend", backend)
    if dp > 1:
        # always forward dp (tp=1 replicas are meshless — replica
        # routing is scheduler-level and needs no devices); with a
        # mesh, the engine verifies dp against the 'data' axis
        engine_kwargs.setdefault("dp", dp)
    return ServeEngine(cfg, params, plan, spec=spec, storage=storage,
                       offload_ratio=offload, seed=seed,
                       **engine_kwargs), cfg


def build_fleet(arch: str, n: int, reduced: bool = True,
                offload: float = 0.5, spec=POWERINFER2, storage=UFS40,
                seed: int = 0, backend: str = "jnp",
                storage_dtype: str = "fp16", **gateway_kwargs):
    """N complete single-device engines behind a FleetGateway — the
    --fleet front door (DESIGN.md §11). Engines share jit caches via
    local_fleet, so fleet size never multiplies trace time."""
    from repro.serving.gateway import FleetGateway, local_fleet
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    fam = serving_family(cfg)
    model = fam.make_model(cfg)
    params = model.init(jax.random.key(seed))
    plan = fam.build_plan(cfg, backend=backend,
                          storage_dtype=storage_dtype)
    params = fam.prepare_params(params, plan)
    engine_kwargs = {} if backend == "jnp" else {"backend": backend}
    backends = local_fleet(cfg, params, plan, n, spec=spec,
                           storage=storage, offload_ratio=offload,
                           seed=seed, **engine_kwargs)
    return FleetGateway(backends, **gateway_kwargs), cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture id (default: the --family arch)")
    ap.add_argument("--family", choices=sorted(FAMILY_ARCHS),
                    default="dense",
                    help="serving family; picks the default arch "
                         "unless --arch is given")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the 2-layer reduced variant (default); "
                         "--no-reduced serves the published config")
    ap.add_argument("--offload", type=float, default=0.5)
    ap.add_argument("--bon", type=int, default=1)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--host-dma", action="store_true",
                    help="use the TPU host-DMA tier instead of UFS 4.0")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (mesh 'model' axis)")
    ap.add_argument("--ep", type=int, default=0,
                    help="expert-parallel degree for the moe family — "
                         "the same mesh 'model' axis as --tp (each "
                         "shard owns E/ep experts)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replicas (mesh 'data' axis)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="serve through the fleet gateway over N "
                         "complete single-device engines (DESIGN.md "
                         "§11); mutually exclusive with --tp/--dp/--ep")
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp",
                    help="cold-path kernel backend: 'pallas' runs the "
                         "fused score->top-k->gather->FFN kernel "
                         "(interpret mode off-TPU; DESIGN.md §10); "
                         "decode is token-identical either way")
    ap.add_argument("--storage-dtype",
                    choices=("fp16", "int8", "int4-mixed"),
                    default="fp16",
                    help="cold-bundle storage dtype (§7.6): cold FFN "
                         "bundles are quantized at prepare time, both "
                         "cold paths dequantize at the gather boundary, "
                         "and the storage plane prices I/O + residency "
                         "at the declared bundle bytes (§4.4)")
    args = ap.parse_args()
    use_compile_cache()

    arch = args.arch or FAMILY_ARCHS[args.family]
    tp = args.tp
    if args.ep:
        if not get_config(arch).num_experts:
            ap.error(f"--ep is expert parallelism but {arch} has no "
                     f"experts; use --tp for tensor parallelism")
        if tp > 1 and tp != args.ep:
            ap.error(f"--tp {tp} and --ep {args.ep} both size the mesh "
                     f"'model' axis; pass one")
        tp = args.ep
    storage = HOST_DMA if args.host_dma else UFS40
    if args.backend == "pallas" and get_config(arch).num_experts:
        ap.error("--backend pallas is the dense-family fused cold-path "
                 "kernel; the moe family has no pallas backend")
    if args.fleet:
        if args.tp > 1 or args.dp > 1 or args.ep:
            ap.error("--fleet members are single-device engines; "
                     "mesh axes (--tp/--dp/--ep) don't apply")
        import time
        gw, cfg = build_fleet(arch, args.fleet, args.reduced,
                              args.offload, storage=storage,
                              backend=args.backend,
                              storage_dtype=args.storage_dtype)
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size,
                              (args.bon, args.prompt_len))
        t0 = time.perf_counter()
        for i in range(args.bon):
            gw.submit(prompt[i].astype(np.int32), max_new=args.max_new,
                      arrival_time=0.0)
        rep = gw.run_until_drained()
        wall = time.perf_counter() - t0
        miss = rep.ttft_percentiles("miss")
        print(f"arch={cfg.name} spec=powerinfer-2 storage={storage.name} "
              f"fleet={args.fleet}")
        print(f"modeled fleet serve: {rep.throughput_tok_s:.2f} tok/s "
              f"over the {rep.span_s:.2f}s span | "
              f"{rep.n_completed}/{rep.n_submitted} completed, "
              f"{rep.n_rejected} rejected, {rep.n_retries} retries | "
              f"cache {rep.cache_hits} hit / {rep.cache_misses} miss")
        print(f"ttft ms (miss): mean {miss['mean']*1e3:.2f} "
              f"p50 {miss['p50']*1e3:.2f} p99 {miss['p99']*1e3:.2f} | "
              f"per-backend "
              f"{[b['completed'] for b in rep.per_backend]} completed")
        print(f"wall time {wall:.1f}s for {rep.total_tokens} tokens "
              f"({jax.devices()[0].platform})")
        gw.close()
        return
    engine, cfg = build_engine(arch, args.reduced, args.offload,
                               storage=storage, profile=True, tp=tp,
                               dp=args.dp, backend=args.backend,
                               storage_dtype=args.storage_dtype)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size,
                          (args.bon, args.prompt_len)).astype(np.int32)
    if args.dp > 1:
        # replica-routed engines serve a stream, not a static batch
        import time
        t0 = time.perf_counter()
        for i in range(args.bon):
            engine.submit(prompt[i], max_new=args.max_new,
                          arrival_time=0.0)
        rep = engine.run_until_drained()
        wall = time.perf_counter() - t0
        pct = rep.latency_percentiles()
        hit = float(np.mean([s.cache_hit_rate for s in rep.stats]))
        io = sum(s.io_s for s in rep.stats)
        eff = sum(s.effective_s for s in rep.stats)
        print(f"arch={cfg.name} spec=powerinfer-2 storage={storage.name} "
              f"dp={args.dp} {'ep' if args.ep else 'tp'}={tp}")
        print(f"modeled serve: {rep.throughput_tok_s:.2f} tok/s over the "
              f"{rep.span_s:.2f}s span ({rep.tokens_per_s:.2f} tok/s "
              f"per-replica pipeline rate) | cache hit {hit:.1%} | "
              f"I/O share {io/max(eff,1e-12):.1%}")
        print(f"ttft ms: mean {float(rep.ttft().mean())*1e3:.2f} | "
              f"latency ms: p50 {pct['p50']*1e3:.2f} "
              f"p90 {pct['p90']*1e3:.2f} p99 {pct['p99']*1e3:.2f}")
        print(f"wall time {wall:.1f}s for {rep.total_tokens} tokens "
              f"({jax.devices()[0].platform})")
        engine.close()
        return
    res = engine.generate(prompt, max_new=args.max_new)
    pct = res.latency_percentiles()
    hit = float(np.mean([s.cache_hit_rate for s in res.stats]))
    io = sum(s.io_s for s in res.stats)
    eff = sum(s.effective_s for s in res.stats)
    print(f"arch={cfg.name} spec=powerinfer-2 storage={storage.name}")
    print(f"modeled decode: {res.tokens_per_s:.2f} tok/s | "
          f"cache hit {hit:.1%} | I/O share {io/max(eff,1e-12):.1%}")
    print(f"latency ms: mean {pct['mean']*1e3:.2f} p50 {pct['p50']*1e3:.2f} "
          f"p90 {pct['p90']*1e3:.2f} p99 {pct['p99']*1e3:.2f}")
    print(f"wall time {res.wall_s:.1f}s for "
          f"{int(np.sum(res.tokens >= 0))} tokens "
          f"({jax.devices()[0].platform})")
    engine.close()


if __name__ == "__main__":
    main()
