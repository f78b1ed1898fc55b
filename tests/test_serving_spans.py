"""The serving engine's host spans and step counts in a profiler trace,
and the named scopes its traced code carries into compiled HLO."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.serve import build_engine
from repro.serving import spans
from repro.serving.sampler import sample_tokens

# the phases of one decode step, in the order the engine runs them
PHASES = [spans.ADMIT, spans.SAMPLE, spans.PULL, spans.DECODE, spans.PULL,
          spans.PRICE, spans.FINISH]
DECODE_SCOPES = {"attention", "kv_write", "ffn_hot", "predictor",
                 "cold_layout", "cold_kernel", "lm_head"}


def _serve_spans(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats)))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _scopes(hlo_text: str) -> set:
    """Every name-stack component of the ops' op_name metadata."""
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in name.split("/")}


@pytest.fixture(scope="module")
def engine():
    eng, cfg = build_engine("smollm-135m", reduced=True, buckets=(1, 2, 4),
                            ctx_budget=40, temperature=0.0)
    yield eng, cfg
    eng.close()


def test_traced_steps_record_every_span_nested_and_counted(engine, tmp_path):
    eng, cfg = engine
    rng = np.random.default_rng(0)

    def submit(n, S):
        return [eng.submit(rng.integers(0, cfg.vocab_size, S), max_new=6)
                for _ in range(n)]

    submit(2, 16)
    eng.step()                                # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    results = [eng.step()]                    # two rows, no admission
    submit(1, 8)
    submit(1, 16)
    results.append(eng.step())                # 2 -> 4 rows, two groups
    results.append(eng.step())
    jax.profiler.stop_trace()

    got = _serve_spans(next(tmp_path.rglob("*.xplane.pb")))
    steps = [s for s in got if s[2] == spans.STEP]
    assert len(steps) == 3
    for (t0, t1, _, args), r in zip(steps, results):
        inner = [s for s in got if s[2] != spans.STEP and t0 <= s[0]
                 and s[1] <= t1]
        admit = next(s for s in inner if s[2] == spans.ADMIT)
        prefills = [s for s in inner if s[2] == spans.PREFILL]
        assert all(admit[0] <= s[0] and s[1] <= admit[1] for s in prefills)
        phases = [s for s in inner if s[2] != spans.PREFILL]
        assert [s[2] for s in phases] == PHASES
        assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))
        assert [s[3]["what"] for s in phases if s[2] == spans.PULL] == [
            "tokens", "clusters"]
        assert set(r.counts) == set(spans.COUNTS)
        assert {k: args[k] for k in spans.COUNTS} == r.counts
        assert r.counts["prefill_groups"] == len(prefills)
        assert sum(s[3]["group"] for s in prefills) == len(r.admitted)
        assert admit[3]["admitted"] == len(r.admitted)

    cs = cfg.sparse_ffn.cluster_size
    for (_, _, _, args), r in zip(steps, results):
        # whole clusters in every layer: one cold LRU key per layer's
        # looked-up cluster and one per cluster admitted on a miss
        assert args["price_keys"] == r.counts["price_keys"] \
            == cfg.num_layers + r.stats.n_miss // cs
    a, b, c = (r.counts for r in results)
    assert a == dict(rows=2, live=2, prefill_groups=0, prefill_tokens=0,
                     pulls=2, built=0, resized=0, switched=0,
                     price_keys=a["price_keys"])
    # two prompt lengths admitted: two prefill groups, a new bucket,
    # its decode executable and one prefill executable per group shape
    assert b == dict(rows=4, live=4, prefill_groups=2, prefill_tokens=24,
                     pulls=2, built=3, resized=1, switched=1,
                     price_keys=b["price_keys"])
    assert c == dict(a, rows=4, live=4, price_keys=c["price_keys"])
    assert [s[3]["step"] for s in steps] == [1, 2, 3]


def test_counts_pass_through_replica_routing():
    eng, cfg = build_engine("smollm-135m", reduced=True, dp=2,
                            buckets=(1, 2), ctx_budget=32, temperature=0.0)
    try:
        for _ in range(2):
            eng.submit(np.arange(8) % cfg.vocab_size, max_new=2)
        r = eng.step()
        assert r.counts["pulls"] == 2 and r.counts["live"] == 1
        assert r.counts["switched"] == 1
        assert r.counts["price_keys"] >= cfg.num_layers
    finally:
        eng.close()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_compiled_decode_and_prefill_carry_the_scopes(backend):
    eng, cfg = build_engine("smollm-135m", reduced=True, buckets=(2,),
                            ctx_budget=24, temperature=0.0, backend=backend)
    try:
        eng.submit(np.arange(8) % cfg.vocab_size, max_new=4)
        eng.step()
        _, decode = eng.decoder.executable_for(2)
        feed = jnp.zeros((2, 1), jnp.int32)
        mask = jnp.ones((2,), bool)
        text = decode.lower(eng.params, feed, eng.arena.cache,
                            mask).compile().as_text()
        assert DECODE_SCOPES <= _scopes(text)
        (B, S, _), prefill = next(iter(eng._prefill_fns.items()))
        text = prefill.lower(eng.params, {"tokens": jnp.zeros(
            (B, S), jnp.int32)}).compile().as_text()
        assert {"prefill", "attention", "lm_head"} <= _scopes(text)
    finally:
        eng.close()


def test_sampling_carries_its_scope():
    logits = jnp.zeros((2, 16))
    for temperature in (0.0, 0.8):
        text = jax.jit(sample_tokens, static_argnums=2).lower(
            jax.random.key(0), logits, temperature).compile().as_text()
        assert "sample" in _scopes(text)


@pytest.mark.parametrize("arch,scopes", [
    ("qwen2-vl-2b", DECODE_SCOPES),
    ("deepseek-moe-16b", {"attention", "kv_write", "experts", "lm_head"}),
])
def test_family_decode_carries_the_scopes(arch, scopes):
    from repro.configs import get_config
    from repro.serving.families import serving_family
    cfg = get_config(arch).reduced()
    fam = serving_family(cfg)
    model = fam.make_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    plan = fam.build_plan(cfg).plan_for_batch(2)
    step = fam.make_decode_step(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    text = jax.jit(lambda p, t, c, m: step(p, t, c, plan, m)).lower(
        params, jax.ShapeDtypeStruct((2, 1), jnp.int32), cache,
        jax.ShapeDtypeStruct((2,), jnp.bool_)).compile().as_text()
    assert scopes <= _scopes(text)


def test_every_scope_name_is_listed():
    assert DECODE_SCOPES | {"prefill", "sample", "experts"} == set(
        spans.SCOPES)
    assert len(set(spans.SPANS)) == len(spans.SPANS)
    assert all(s.startswith("serve.") for s in spans.SPANS)
