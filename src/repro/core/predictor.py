"""Online activation predictor (PowerInfer-2 §3.2 / PowerInfer §4).

A low-rank two-matrix MLP per FFN layer scores each neuron's activation
probability for the current hidden state:

    score(x) = x @ A @ B          A: (d_model, r)   B: (r, n_neurons)

The predictor is the gate of the *cold* path: only top-k-scored cold
neurons are gathered and computed. The offline planner (core/planner.py)
trains/It calibrates it against observed activations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.modules import dense_init
from repro.sharding import constrain


def init_predictor(key, d_model: int, n_neurons: int, rank: int, dtype):
    ka, kb = jax.random.split(key)
    return {
        "A": dense_init(ka, (d_model, rank), dtype),
        "B": dense_init(kb, (rank, n_neurons), dtype),
    }


def predictor_spec():
    # B's neuron dim is sharded over 'model', matching the FFN weights,
    # so each shard scores exactly the neurons it owns.
    return {"A": P(None, None), "B": P(None, "model")}


# Scores decide which clusters run, so they are computed at full fp32
# precision on every backend: the chip's default fp32 matmul rounds
# its inputs to bf16, which would let the jnp and pallas cold paths
# pick different clusters from the same hidden state.
SCORE_PRECISION = jax.lax.Precision.HIGHEST


def predict_scores(params, x):
    """x (..., d_model) -> neuron scores (..., n_neurons), fp32."""
    h = jnp.einsum("...d,dr->...r", x.astype(jnp.float32),
                   params["A"].astype(jnp.float32),
                   precision=SCORE_PRECISION)
    s = jnp.einsum("...r,rn->...n", h, params["B"].astype(jnp.float32),
                   precision=SCORE_PRECISION)
    return constrain(s, P(None, "model")) if s.ndim == 2 else s


def predict_proba(params, x):
    return jax.nn.sigmoid(predict_scores(params, x))
