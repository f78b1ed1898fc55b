"""Token sampling: greedy / temperature / top-k, plus Best-of-N scoring."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sample_tokens(key, logits, temperature: float = 1.0, top_k: int = 0):
    """logits (B, V) -> (B,) int32."""
    with jax.named_scope("sample"):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits.astype(jnp.float32) / temperature
        if top_k:
            v, _ = jax.lax.top_k(logits, top_k)
            cutoff = v[:, -1:]
            logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def sequence_logprob(logits_seq, tokens_seq):
    """Mean token log-prob — the Best-of-N ranking score (paper Fig 1b)."""
    logp = jax.nn.log_softmax(logits_seq.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, tokens_seq[..., None], axis=-1)[..., 0]
    return ll.mean(axis=-1)
