"""Build the served engine the way `launch.serve.build_engine` does,
on the benchmark's own weights, and warm every shape a mix uses.

build_engine makes its own weights (`init_params`), which the plain
reference could then not make without taking them from the program;
so the same four steps run here on weights from `model.make_weights`:
the family's offline plan (with a profile passed in), the family's
weight transform, and `ServeEngine`.

The dense plan is built from the program's synthetic Zipf profile
with each layer's frequencies in descending neuron order: the planner
then sizes exactly the plans it builds by default (the same sorted
profile), and its hot-first permutation is the identity, so the
served neuron i is the benchmark's neuron i. Hot neurons are
[0, n_hot) and cold cluster c is [n_hot + c*cs, n_hot + (c+1)*cs).
"""
from __future__ import annotations

import numpy as np

from chipbench.loop import SPAN_STORAGE, Timer


def build(cfg: dict, pc, weights, mix: dict, seed: int):
    """-> (engine, plans {bucket: (n_hot, kc, cluster_size)}, Timer)."""
    from repro.core.planner import synthetic_frequencies
    from repro.serving.engine import ServeEngine
    from repro.serving.families import serving_family
    fam = serving_family(pc)
    s = cfg["serve"]
    backend = s.get("backend", "jnp")
    freqs = None
    if not pc.num_experts:
        freqs = -np.sort(-synthetic_frequencies(pc), axis=1)
    plan = fam.build_plan(pc, freqs, backend=backend)
    params = fam.prepare_params(weights, plan)
    eng = mix["engine"]
    engine = ServeEngine(
        pc, params, plan, seed=int(seed) & 0x7FFFFFFF,
        buckets=tuple(eng["buckets"]), ctx_budget=int(eng["ctx_budget"]),
        temperature=0.0, backend=None if backend == "jnp" else backend)
    timer = Timer()
    storage = getattr(engine, "storage", None)
    if storage is not None and callable(getattr(storage, "step", None)):
        storage.step = timer.wrap(storage.step, SPAN_STORAGE)
    else:
        timer = None
    plans = {}
    for b in eng["buckets"]:
        p = plan.plan_for_batch(b)
        plans[int(b)] = (int(p.n_hot), int(p.clusters_per_group),
                         int(p.cluster_size))
    return engine, plans, timer


def warm_up(engine, mix: dict, shapes: list, vocab: int) -> int:
    """Run every shape the mix can reach once, through submit / step /
    cancel: the largest decode bucket (the closed loop keeps it full),
    and a prefill group of every size 1..bucket at every prompt length
    in `shapes`,
    admitted beside filler requests so the batch stays in its bucket
    and the KV arena never resizes. Returns the steps run."""
    top = max(mix["engine"]["buckets"])
    T = int(mix["engine"]["ctx_budget"])
    lens = sorted(set(int(x) for x in shapes))
    rng = np.random.default_rng(0)

    def submit(n, S):
        return [engine.submit(rng.integers(0, vocab, S).astype(np.int32),
                              max_new=T - S) for _ in range(n)]

    running = submit(top, lens[0])
    engine.step()
    steps = 1
    # pack the other groups into as few steps as fit the bucket, groups
    # of one step all of different lengths, so each stays its own group
    todo = sorted(((B, S) for S in lens for B in range(1, top + 1)
                   if (B, S) != (top, lens[0])), reverse=True)
    packs = []
    for B, S in todo:
        for p in packs:
            if sum(b for b, _ in p) + B <= top and S not in {s for _, s in p}:
                p.append((B, S))
                break
        else:
            packs.append([(B, S)])
    for p in packs:
        n = sum(B for B, _ in p)
        engine.cancel(running[:n])
        running = running[n:]
        for B, S in p:
            running += submit(B, S)
        engine.step()
        steps += 1
    if mix["loop"] != "closed":
        # arrivals move the batch between any two buckets: decode at
        # each, and the arena resize of every ordered pair
        buckets = sorted(mix["engine"]["buckets"])
        for a in buckets:
            for b in buckets:
                for n in (a, b):
                    if n < len(running):
                        engine.cancel(running[n:])
                        running = running[:n]
                    elif n > len(running):
                        running += submit(n - len(running), lens[0])
                    engine.step()
                    steps += 1
    engine.cancel(running)
    return steps
