"""Best-of-N sampling with dynamic batch adaptation (paper Fig 1b/13).

Generates N=4 candidate continuations through the continuous-batching
API: the four candidates are submitted with staggered generation
budgets, so they finish at different steps, the effective batch
shrinks, and the engine swaps pre-jitted executables (the paper's
per-batch NPU graphs) + hot/cold plans live — no forced completion
schedule needed. The best candidate is picked by mean token log-prob.

  PYTHONPATH=src python examples/best_of_n.py
"""
import jax
import numpy as np

from repro.launch.serve import build_engine
from repro.serving.sampler import sequence_logprob


def main():
    engine, cfg = build_engine("smollm-135m", reduced=True, offload=0.5,
                               ctx_budget=32, temperature=1.0)
    rng = np.random.default_rng(1)
    base = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)

    # N=4 candidates of the same prompt, staggered budgets 4/8/12/16
    max_new = 16
    uids = [engine.submit(base, max_new=n) for n in (4, 8, 12, max_new)]
    rep = engine.run_until_drained()
    batches = [s.batch for s in rep.stats]
    print("batch timeline:", batches)
    print("decode executables:", len(engine.decoder.live_plans()))
    print(f"modeled {rep.tokens_per_s:.1f} tok/s; "
          f"ttft {rep.ttft().mean() * 1e3:.2f} ms")

    # rank candidates (pad short/finished ones)
    toks = np.zeros((len(uids), max_new), np.int32)
    for i, u in enumerate(uids):
        gen = engine.sched.sequences[u].generated
        toks[i, :len(gen)] = gen
    # score with the model's own logits via a fresh forward
    import jax.numpy as jnp
    from repro.models.dense import make_model
    model = make_model(cfg)
    prompt = np.repeat(base[None], len(uids), axis=0)
    full = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(toks)], 1)
    logits = jax.jit(lambda p, b: model.forward(p, b))(
        engine.params, {"tokens": full})
    scores = sequence_logprob(logits[:, 15:-1], jnp.asarray(toks))
    best = int(np.argmax(np.asarray(scores)))
    print("candidate scores:", [round(float(s), 3) for s in scores])
    print(f"best-of-4 winner: candidate {best}: {toks[best].tolist()}")


if __name__ == "__main__":
    main()
