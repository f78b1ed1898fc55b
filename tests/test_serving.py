"""Serving engine integration: system ordering (paper Fig 7/14),
cache behavior (Fig 10), Best-of-N batch adaptation (Fig 13),
bucketed-executable swaps (§4.1.3)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.adaptation import BatchTracker, bucket_for
from repro.core.baselines import (ABLATION_LADDER, LLAMACPP, LLMFLASH,
                                  POWERINFER2)
from repro.core.planner import build_plan, permute_ffn_params
from repro.models.dense import make_model
from repro.serving.engine import ServeEngine
from repro.serving.sampler import sample_tokens, sequence_logprob


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-135m").reduced()
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    plan = build_plan(cfg)
    params = permute_ffn_params(params, plan.neuron_order)
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 16)).astype(np.int32)
    return cfg, params, plan, prompt


def _run(cfg, params, plan, prompt, spec, offload=0.5, **kw):
    eng = ServeEngine(cfg, params, plan, spec=spec, offload_ratio=offload)
    return eng.generate(prompt, max_new=8, **kw), eng


def test_system_ordering(setup):
    """PowerInfer-2 >= LLMFlash-analogue >> llama.cpp-analogue."""
    cfg, params, plan, prompt = setup
    r_pi2, _ = _run(cfg, params, plan, prompt, POWERINFER2)
    r_lf, _ = _run(cfg, params, plan, prompt, LLMFLASH)
    r_lc, _ = _run(cfg, params, plan, prompt, LLAMACPP)
    assert r_pi2.tokens_per_s >= r_lf.tokens_per_s
    assert r_lf.tokens_per_s > r_lc.tokens_per_s
    assert r_pi2.tokens_per_s / r_lc.tokens_per_s > 3.0


def test_ablation_ladder_monotone(setup):
    """Fig 14: each added mechanism must not hurt throughput."""
    cfg, params, plan, prompt = setup
    speeds = []
    for spec in ABLATION_LADDER:
        r, _ = _run(cfg, params, plan, prompt, spec)
        speeds.append(r.tokens_per_s)
    # allow small non-monotonicity only between adjacent rungs
    assert speeds[-1] > speeds[0] * 2
    for a, b in zip(speeds, speeds[1:]):
        assert b >= a * 0.9


def test_cache_size_scaling(setup):
    """Fig 10: more resident memory -> faster decode (less I/O)."""
    cfg, params, plan, prompt = setup
    speeds = []
    for offload in (0.9, 0.5, 0.1):
        r, _ = _run(cfg, params, plan, prompt, POWERINFER2, offload=offload)
        speeds.append(r.tokens_per_s)
    assert speeds == sorted(speeds), speeds


def test_generated_tokens_valid(setup):
    cfg, params, plan, prompt = setup
    r, _ = _run(cfg, params, plan, prompt, POWERINFER2)
    toks = r.tokens[r.tokens >= 0]
    assert toks.size > 0
    assert (toks < cfg.vocab_size).all()


def test_bon_batch_decay_swaps_executables(setup):
    """Fig 13: sequences completing -> smaller batches -> executable
    swaps (the pre-built NPU graph analogue)."""
    cfg, params, plan, prompt = setup
    eng = ServeEngine(cfg, params, plan, spec=POWERINFER2,
                      offload_ratio=0.5)
    steps, step = [], eng.step

    def recording_step():
        r = step()
        if r is not None:
            steps.append(r)
        return r

    eng.step = recording_step
    res = eng.generate(prompt, max_new=12,
                       completion_schedule={3: 1, 6: 1, 9: 1})
    batches = [s.batch for s in res.stats]
    assert batches[0] == 4
    assert batches[-1] == 1
    assert [r.stats.batch for r in steps] == batches
    assert sum(r.counts["switched"] for r in steps) >= 4


def test_deterministic_greedy(setup):
    cfg, params, plan, prompt = setup
    r1, _ = _run(cfg, params, plan, prompt, POWERINFER2, temperature=0.0)
    r2, _ = _run(cfg, params, plan, prompt, POWERINFER2, temperature=0.0)
    assert np.array_equal(r1.tokens, r2.tokens)


def test_bucket_for():
    assert bucket_for(1) == 1
    assert bucket_for(3) == 4
    assert bucket_for(33) == 32     # capped at largest bucket


def test_batch_tracker():
    t = BatchTracker()
    t.start(4)
    t.finish(1)
    t.finish(1)
    assert t.active == 2
    assert t.history == [4, 3, 2]


def test_sampler_topk_restricts():
    import jax.numpy as jnp
    logits = jnp.asarray(np.array([[0.0, 5.0, 4.0, -3.0]]))
    for seed in range(10):
        t = sample_tokens(jax.random.key(seed), logits, temperature=1.0,
                          top_k=2)
        assert int(t[0]) in (1, 2)


def test_sequence_logprob_ranks_confident_sequences_higher():
    import jax.numpy as jnp
    V = 8
    conf = jnp.full((1, 4, V), -10.0).at[:, :, 3].set(10.0)
    unif = jnp.zeros((1, 4, V))
    toks = jnp.full((1, 4), 3, jnp.int32)
    assert float(sequence_logprob(conf, toks)[0]) > \
        float(sequence_logprob(unif, toks)[0])
