"""Chip smoke test: drive the serving main path once on a TPU.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # one host with four chips

One chip: smollm-135m at its published widths (30 layers, d_model 576,
9/3 heads, d_ff 1536, vocab 49152, bf16, 64-row clusters, CATS) with
seeded random weights, built by `repro.launch.serve.build_engine` on
the fused Pallas cold kernel and served 4 requests (prompts of 32-128
tokens, 16 new tokens each) through submit / run_until_drained. The
compiled decode step must contain the kernel (`tpu_custom_call`), and
every FFN layer must match the jnp cold path on identical inputs. The
same requests are then served greedily on the jnp backend, and both
backends once more on the same weights held in fp32 at full matmul
precision: there the first decode step's cluster ids, its logits and
every greedy token must agree end to end. The bf16 pallas-vs-jnp and
bf16-vs-fp32 distances are reported, not gated.

Four chips: deepseek-moe-16b at its published widths and all 28 layers
(about 34 GB of bf16 weights: more than one 16 GB chip holds) serves
4 requests expert-parallel over the four chips (ep=4), its weights
initialised sharded. Then the same widths cut to 4 layers serve the
same requests at ep=4 and on one chip in this process, on the same
weights, in bf16 and in fp32 at full matmul precision. In fp32 every
MoE layer of the two layouts must agree on identical inputs, and end
to end the routing, greedy tokens and first-step logits must agree.
The bf16 distances are reported.

Without a TPU the script names the platform it found and exits 1,
printing no result. On success the last line of standard output is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
Timings printed here are smoke timings (host wall clock after the
device finished), not benchmark metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

SEED = 0
SMOLLM_PROMPTS = (32, 64, 96, 128)
MOE_PROMPTS = (32, 32, 64, 64)
FP32 = {"param_dtype": "float32", "compute_dtype": "float32"}

# Pallas vs jnp, layer by layer: each of the served model's FFN layers
# under the served decode plan, fed the same bf16 inputs, must pick the
# same clusters and agree to a relative L2 of 2^-7. Both backends
# compute fp32 predictor scores and fp32 gate/up activations and round
# the layer output to bf16 once; their fp32 sums run in different
# orders, so an element can land one bf16 ulp (2^-8 relative) apart.
# A wrong cluster, a wrong gating or a stale DMA slot moves the layer
# output by tens of percent.
LAYER_REL_L2 = 2.0 ** -7
# End to end, two serving paths on the same weights held in fp32 at
# full matmul precision (pallas vs jnp; ep=4 vs one chip), first
# decode step: relative L2 of the logits (max |diff| / max |logit| for
# ep). The paths run the same math and differ only in the order of
# their fp32 sums, a few fp32 ulps (~1e-7) per op; with the same
# cluster or expert picks in every layer that stays near 1e-6 through
# the whole depth. 1e-3 is far above that and below one bf16 ulp
# (2^-8): a wrong layer's weights, a wrong bucket plan or a stale KV
# slot changes a pick or half an FFN and lands far above it. In bf16
# the same reorderings move elements by a bf16 ulp, which tips near-
# tied cluster, expert and token picks, so bf16 is reported, not gated
# (PERF.md, Findings).
E2E_MAX_REL = 1e-3


def log(msg: str):
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends in backend compiles, from its own events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def prompts_for(vocab: int, lengths) -> list:
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lengths]


class Served:
    """What one engine served: greedy tokens per request in prompt
    order, the first decode step's logits (n, V) and activation trace
    (per layer: cold cluster ids, or per-expert dispatch counts)."""

    def __init__(self, tokens, logits, trace):
        self.tokens, self.logits, self.trace = tokens, logits, trace


def serve(engine, prompts, max_new: int, tag: str) -> Served:
    """Serve `prompts` through submit / step / run_until_drained and
    check every request completed with finite first-step logits."""
    import jax
    uids = [engine.submit(p, max_new=max_new, arrival_time=0.0)
            for p in prompts]
    t0 = time.perf_counter()
    first = engine.step()               # admit + prefill + first decode
    logits = engine.next_logits(uids)   # host copy: the step has ended
    t1 = time.perf_counter()
    rep = engine.run_until_drained()
    jax.block_until_ready(engine.arena.cache)
    t2 = time.perf_counter()
    done = {r.uid: list(r.generated) for r in rep.requests}
    vocab = engine.cfg.vocab_size
    for u, p in zip(uids, prompts):
        toks = done.get(u)
        if toks is None or len(toks) != max_new:
            raise RuntimeError(f"[{tag}] request {u} (prompt {len(p)}) "
                               f"did not complete: {toks}")
        if not all(0 <= t < vocab for t in toks):
            raise RuntimeError(f"[{tag}] request {u} produced a token "
                               f"outside the vocabulary: {toks}")
    if not np.isfinite(logits).all():
        raise RuntimeError(f"[{tag}] non-finite first-step logits")
    n_rest = len(rep.stats)
    log(f"[{tag}] {len(uids)} requests x {max_new} tokens completed "
        f"(prompts {[len(p) for p in prompts]})")
    log(f"[{tag}] smoke timing: first step incl. prefill + compile "
        f"{t1 - t0:.3f} s; {n_rest} further steps "
        f"{(t2 - t1) / max(n_rest, 1) * 1e3:.2f} ms each "
        f"(wall per engine step, host pricing included)")
    return Served([done[u] for u in uids], logits, first.trace)


def decode_hlo(engine, n_active: int) -> str:
    """Optimized HLO text of the decode executable the engine ran."""
    import jax.numpy as jnp
    _, fn = engine.decoder.executable_for(n_active)
    n = engine.arena.n_slots
    return fn.lower(engine.params, jnp.zeros((n, 1), jnp.int32),
                    engine.arena.cache, jnp.ones((n,), bool)
                    ).compile().as_text()


def compare(a: Served, b: Served) -> dict:
    """End-to-end distance between two serves of the same requests."""
    d = a.logits.astype(np.float64) - b.logits
    same = [x == y for ra, rb in zip(a.tokens, b.tokens)
            for x, y in zip(ra, rb)]
    layers = [np.array_equal(x, y) for x, y in zip(a.trace, b.trace)]
    return {
        "rel_l2": float(np.linalg.norm(d) / np.linalg.norm(b.logits)),
        "max_rel": float(np.max(np.abs(d)) / np.max(np.abs(b.logits))),
        "tokens": sum(same) / len(same), "n_tokens": len(same),
        "first": sum(ra[0] == rb[0] for ra, rb in zip(a.tokens, b.tokens)),
        "n_requests": len(a.tokens),
        "layers": sum(layers), "n_layers": len(layers),
        "first_layer_diff": layers.index(False) if False in layers
        else None,
    }


def describe(c: dict) -> str:
    return (f"logits relative L2 {c['rel_l2']:.3g}, max |diff| / max "
            f"|logit| {c['max_rel']:.3g}; first-step trace equal in "
            f"{c['layers']}/{c['n_layers']} layers (first differing: "
            f"{c['first_layer_diff']}); greedy token agreement "
            f"{c['tokens']:.3f} of {c['n_tokens']}, first tokens equal "
            f"in {c['first']}/{c['n_requests']} requests")


def routing_overlap(a: Served, b: Served, layer: int) -> float:
    """Share of one layer's (token, expert) dispatches two MoE serves
    have in common in their first decode step."""
    ca, cb = a.trace[layer], b.trace[layer]
    return float(np.minimum(ca, cb).sum() / max(ca.sum(), 1))


def peak_bytes(devices) -> str:
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            out.append(f"{st['peak_bytes_in_use'] / 2**30:.2f}")
    return ", ".join(out) + " GiB" if out else "not reported"


def layer_check(engine, plan_p, n_rows: int = 4):
    """Every FFN layer of `engine`'s params, pallas vs jnp cold path
    under the same bucket plan, on identical random inputs. Returns
    (layers with identical cluster ids, worst relative L2)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.core.sparse_ffn import ffn_hybrid
    cfg = engine.cfg
    plan_j = dataclasses.replace(plan_p, backend="jnp")
    ffn = jax.jit(lambda p, x, plan: ffn_hybrid(
        p, x, cfg.activation, cfg.sparse_ffn.mode, plan,
        return_indices=True), static_argnums=2)
    same, worst = 0, 0.0
    for layer in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[layer], engine.params["layers"]["ffn"])
        x = jax.random.normal(jax.random.key(layer), (n_rows, cfg.d_model),
                              jnp.float32).astype(lp["w"].dtype)
        yp, cp = ffn(lp, x, plan_p)
        yj, cj = ffn(lp, x, plan_j)
        yp, yj = (np.asarray(y, np.float64) for y in (yp, yj))
        same += bool(np.array_equal(np.asarray(cp), np.asarray(cj)))
        worst = max(worst, float(np.linalg.norm(yp - yj)
                                 / np.linalg.norm(yj)))
    return same, worst


def moe_layer_check(eng4, cfg, host_params, n_rows: int = 64):
    """Every MoE layer of an ep=4 engine, its sharded weights vs the
    same weights whole on one chip, on identical random inputs (64
    rows: the capacity drops of a prefill group). Returns (layers with
    identical routing, worst relative L2)."""
    import jax
    import jax.numpy as jnp
    from repro.models.moe import apply_moe_ffn
    dev = jax.devices()[0]
    f4 = jax.jit(lambda p, x: apply_moe_ffn(p, x, eng4.cfg,
                                            collect_trace=True))
    f1 = jax.jit(lambda p, x: apply_moe_ffn(p, x, cfg,
                                            collect_trace=True))
    same, worst = 0, 0.0
    for layer in range(cfg.num_layers):
        lp4 = jax.tree.map(lambda a: a[layer], eng4.params["layers"]["moe"])
        lp1 = jax.device_put(jax.tree.map(
            lambda a: a[layer], host_params["layers"]["moe"]), dev)
        x = jax.random.normal(jax.random.key(layer), (n_rows, cfg.d_model),
                              jnp.float32).astype(cfg.compute_dtype)
        with jax.set_mesh(eng4.mesh):
            y4, _, t4 = f4(lp4, x)
        y1, _, t1 = f1(lp1, x)
        y4, y1 = (np.asarray(y, np.float64) for y in (y4, y1))
        same += bool(np.array_equal(np.asarray(t4), np.asarray(t1)))
        worst = max(worst, float(np.linalg.norm(y4 - y1)
                                 / np.linalg.norm(y1)))
        del lp1
    return same, worst


def serve_backend(cfg, backend: str, prompts, max_new: int,
                  check_layers: bool) -> Served:
    """Build `cfg` on `backend`, serve `prompts`, show the plan that
    ran and whether the kernel is in the decode step."""
    import jax
    from repro.launch.serve import build_engine
    tag = f"{backend}, {cfg.param_dtype}"
    t0 = time.perf_counter()
    engine, _ = build_engine(cfg, backend=backend, temperature=0.0,
                             seed=SEED)
    jax.block_until_ready(engine.params)
    log(f"[{tag}] {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, clusters of "
        f"{cfg.sparse_ffn.cluster_size}, mode {cfg.sparse_ffn.mode}; "
        f"built in {time.perf_counter() - t0:.2f} s")
    out = serve(engine, prompts, max_new, tag)
    live = engine.decoder.live_plans()
    ran = {b: (p.n_hot, p.clusters_per_group, p.groups)
           for b, p in sorted(live.items())}
    log(f"[{tag}] plan that ran: bucket -> (n_hot, kc, groups) {ran}")
    n_kernel = decode_hlo(engine, len(prompts)).count("tpu_custom_call")
    log(f"[{tag}] tpu_custom_call in the decode step: {n_kernel}")
    if backend == "pallas" and n_kernel == 0 \
            and jax.default_backend() == "tpu":
        raise RuntimeError("the pallas decode step holds no kernel")
    if check_layers:
        same, worst = layer_check(engine, live[max(live)])
        log(f"[{tag}] per layer vs the jnp cold path, identical inputs: "
            f"cluster ids equal in {same}/{cfg.num_layers} layers, "
            f"worst relative L2 {worst:.3g} (limit {LAYER_REL_L2:.3g})")
        if same != cfg.num_layers or not worst <= LAYER_REL_L2:
            raise RuntimeError("the pallas cold path disagrees with "
                               "the jnp path on identical inputs")
    engine.close()
    del engine
    gc.collect()
    return out


def one_chip(cfg=None, max_new=16, lengths=SMOLLM_PROMPTS):
    """Full-width dense serve on the fused kernel vs the jnp path, in
    bf16 as served and in fp32 at full matmul precision."""
    import jax
    from repro import kernels
    from repro.configs import get_config

    interp = kernels.default_interpret()
    log(f"kernels.default_interpret() = {interp}")
    if jax.default_backend() == "tpu" and interp:
        raise RuntimeError("kernels would run in interpret mode on a TPU")
    cfg = cfg or get_config("smollm-135m")
    prompts = prompts_for(cfg.vocab_size, lengths)
    bf = {b: serve_backend(cfg, b, prompts, max_new, b == "pallas")
          for b in ("pallas", "jnp")}
    with jax.default_matmul_precision("highest"):
        f32 = {b: serve_backend(cfg.replace(**FP32), b, prompts, max_new,
                                False)
               for b in ("pallas", "jnp")}
    log(f"peak bytes in use: {peak_bytes(jax.devices()[:1])}")
    log(f"bf16, pallas vs jnp (reported): "
        f"{describe(compare(bf['pallas'], bf['jnp']))}")
    log(f"jnp, bf16 vs fp32 weights (reported): "
        f"{describe(compare(bf['jnp'], f32['jnp']))}")
    c = compare(f32["pallas"], f32["jnp"])
    log(f"fp32, pallas vs jnp (gated, limit relative L2 "
        f"{E2E_MAX_REL:.3g}): {describe(c)}")
    if c["layers"] != c["n_layers"] or c["tokens"] != 1.0 \
            or not c["rel_l2"] <= E2E_MAX_REL:
        raise RuntimeError("pallas and jnp serving disagree in fp32")


def four_chips(arch="deepseek-moe-16b", reduced=False, max_new=8,
               lengths=MOE_PROMPTS, cut_layers=4):
    """Expert-parallel MoE at ep=4, then ep=4 vs one chip."""
    import jax
    from repro.launch.serve import build_engine

    devices = jax.devices()[:4]
    t0 = time.perf_counter()
    engine, cfg = build_engine(arch, reduced=reduced, tp=4,
                               temperature=0.0, seed=SEED)
    jax.block_until_ready(engine.params)
    w = engine.params["layers"]["moe"]["experts"]
    per_dev = {s.device: s.data.nbytes for s in w.addressable_shards}
    log(f"[ep=4] {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_experts} routed + "
        f"{cfg.num_shared_experts} shared experts of d_ff {cfg.d_ff}, "
        f"top-{cfg.experts_per_token}, vocab {cfg.vocab_size}, "
        f"{cfg.param_dtype}; {cfg.param_count() / 1e9:.2f} B params; "
        f"built in {time.perf_counter() - t0:.2f} s")
    log(f"[ep=4] routed experts {w.nbytes / 2**30:.2f} GiB in all, per "
        f"device {[round(b / 2**30, 2) for b in per_dev.values()]} GiB")
    if len(per_dev) != 4 or max(per_dev.values()) * 4 != w.nbytes:
        raise RuntimeError("expert weights are not split over 4 chips")
    prompts = prompts_for(cfg.vocab_size, lengths)
    serve(engine, prompts, max_new, "ep=4")
    log(f"[ep=4] peak bytes in use per device: {peak_bytes(devices)}")
    engine.close()
    del engine, w
    gc.collect()

    # The same widths cut in depth to what one chip holds, served at
    # ep=4 and on one chip, in bf16 (reported) and fp32 (gated).
    cut = cfg.replace(num_layers=cut_layers)
    bf = ep_pair(cut, prompts, max_new)
    f32 = ep_pair(cut.replace(**FP32), prompts, max_new)
    for what, a, b in (("ep=4 vs 1 chip, bf16", bf["ep4"], bf["one"]),
                       ("1 chip, bf16 vs fp32 weights", bf["one"],
                        f32["one"])):
        log(f"{what} ({cut_layers} layers, reported): "
            f"{describe(compare(a, b))}; layer-0 routing overlap "
            f"{routing_overlap(a, b, 0):.3f}")
    c = compare(f32["ep4"], f32["one"])
    log(f"ep=4 vs 1 chip, fp32 ({cut_layers} layers, gated, limit max "
        f"|diff| / max |logit| {E2E_MAX_REL:.3g}): {describe(c)}; "
        f"layer-0 routing overlap "
        f"{routing_overlap(f32['ep4'], f32['one'], 0):.3f}")
    same, worst = f32["layers"]
    if same != cut_layers or not worst <= E2E_MAX_REL:
        raise RuntimeError("ep=4 and one-chip MoE layers disagree on "
                           "identical inputs")
    if c["layers"] != c["n_layers"] or c["tokens"] != 1.0 \
            or not c["max_rel"] <= E2E_MAX_REL:
        raise RuntimeError("ep=4 and one-chip serving disagree in fp32")


def ep_pair(cfg, prompts, max_new: int) -> dict:
    """Serve `cfg` at ep=4 from weights initialised sharded, then the
    same weights whole on one chip, moved through the host (in fp32 the
    first chip holds a 4-layer cut, but not that and its ep=4 shard at
    once). fp32 runs at full matmul precision and also checks every
    MoE layer of the two layouts on identical inputs ("layers")."""
    import jax
    from repro.launch.serve import build_engine
    from repro.serving.engine import ServeEngine
    fp32 = cfg.param_dtype == "float32"
    tag = f"{cfg.num_layers} layers, {cfg.param_dtype}"
    out = {}
    with jax.default_matmul_precision("highest" if fp32 else None):
        eng4, _ = build_engine(cfg, tp=4, temperature=0.0, seed=SEED)
        out["ep4"] = serve(eng4, prompts, max_new, f"ep=4, {tag}")
        params = jax.device_get(eng4.params)
        plan = eng4.plan
        if fp32:
            out["layers"] = same, worst = moe_layer_check(eng4, cfg, params)
            log(f"[ep=4 vs 1 chip, {tag}] per layer, identical inputs: "
                f"routing equal in {same}/{cfg.num_layers} layers, worst "
                f"relative L2 {worst:.3g} (limit {E2E_MAX_REL:.3g})")
        eng4.close()
        del eng4
        gc.collect()
        eng1 = ServeEngine(cfg, jax.device_put(params, jax.devices()[0]),
                           plan, temperature=0.0, seed=SEED)
        del params
        out["one"] = serve(eng1, prompts, max_new, f"1 chip, {tag}")
        eng1.close()
        del eng1
        gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the expert-parallel path and its "
                         "one-chip comparison, and nothing else")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if jax.device_count() < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {jax.device_count()}", file=sys.stderr)
        return 1
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: the repro package is missing ({src})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import use_compile_cache

    log(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"count={jax.device_count()} jax={jax.__version__} "
        f"compile cache {use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f"backend compile {clock.seconds:.2f} s; total "
        f"{time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
