"""Dense decoder-only transformer (llama / nemotron / qwen families).

Provides the generic embed->scan(blocks)->logits machinery reused by the
VLM (custom embeddings + M-RoPE angles) and the hybrid model's attention
blocks. Layer params are stacked (leading L dim) and scanned.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.clusters import HybridPlan
from repro.models import blocks
from repro.models.attention import rope_angles
from repro.models.kv_cache import init_full_cache, init_ring_cache, write_pos
from repro.models.modules import (
    dtype_of, dense_init, embed_init, rms_norm, stack_layer_params)
from repro.sharding import constrain, BATCH


@dataclass(frozen=True)
class Model:
    """Uniform model API used by tests, the launcher and the engine."""
    cfg: ModelConfig
    init: Callable                 # (key) -> params
    param_spec: Callable           # () -> pytree of PartitionSpec
    forward: Callable              # (params, batch, plan=None) -> logits
    prefill: Callable              # (params, batch) -> (logits, cache)
    decode_step: Callable          # (params, tokens, cache, plan) -> (logits, cache)
    init_cache: Callable           # (batch, seq_len) -> cache
    cache_spec: Callable           # (batch, seq_len) -> pytree of PartitionSpec


# ----------------------------------------------------------------- init ----

def init_layer(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": blocks.init_attn(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "ffn": blocks.init_ffn_block(k2, cfg, dtype),
    }


def layer_spec(cfg: ModelConfig):
    return {"ln1": P(None), "attn": blocks.attn_spec(cfg),
            "ln2": P(None), "ffn": blocks.ffn_block_spec(cfg)}


def init_params(key, cfg: ModelConfig):
    dtype = dtype_of(cfg.param_dtype)
    ke, kl, kh = jax.random.split(key, 3)
    params = {
        "embed": embed_init(ke, cfg.vocab_padded, cfg.d_model, dtype),
        "out_norm": jnp.zeros((cfg.d_model,), dtype),
        "layers": stack_layer_params(kl, cfg.num_layers,
                                     lambda k: init_layer(k, cfg, dtype)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kh, (cfg.d_model, cfg.vocab_padded), dtype)
    return params


def params_spec(cfg: ModelConfig):
    ls = jax.tree.map(lambda s: P(None, *s), layer_spec(cfg),
                      is_leaf=lambda s: isinstance(s, P))
    spec = {"embed": P("model", None), "out_norm": P(None), "layers": ls}
    if not cfg.tie_embeddings:
        spec["lm_head"] = P(None, "model")
    return spec


# -------------------------------------------------------------- forward ----

def embed_tokens(params, cfg, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return constrain(x, P(BATCH, None, None)).astype(dtype_of(cfg.compute_dtype))


def lm_logits(params, cfg, x):
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["out_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        if cfg.vocab_padded != cfg.vocab_size:
            # mask the padding classes (vocab padded for shardability)
            invalid = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
            logits = jnp.where(invalid, jnp.asarray(-1e30, logits.dtype),
                               logits)
        return constrain(logits, P(BATCH, None, "model"))


def forward_from_embeds(params, cfg: ModelConfig, x, angles, *,
                        window=0, plan=None, collect_kv=False):
    """Scan the layer stack over full-sequence embeddings."""

    def body(h, lp):
        a, kv = blocks.attn_full(lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps),
                                 cfg, angles, causal=True, window=window)
        h = h + a
        f = blocks.apply_ffn_block(lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps),
                                   cfg, plan)
        h = h + f
        return h, (kv if collect_kv else None)

    x, kvs = blocks.scan_layers(body, x, params["layers"], remat=cfg.remat)
    return x, kvs


def make_forward(cfg: ModelConfig, angles_fn=None, embed_fn=None):
    dh_half = cfg.d_head // 2

    def forward(params, batch, plan: Optional[HybridPlan] = None):
        x = (embed_fn(params, cfg, batch) if embed_fn
             else embed_tokens(params, cfg, batch["tokens"]))
        S = x.shape[1]
        angles = (angles_fn(batch, S) if angles_fn
                  else rope_angles(jnp.arange(S), dh_half, cfg.rope_theta))
        x, _ = forward_from_embeds(params, cfg, x, angles,
                                   window=cfg.sliding_window, plan=plan)
        return lm_logits(params, cfg, x)

    return forward


# -------------------------------------------------------- prefill/decode ----

def make_cache_fns(cfg: ModelConfig):
    kv, dh = cfg.num_kv_heads, cfg.d_head
    W = cfg.sliding_window

    def init_cache(batch, seq_len, dtype=None):
        dtype = dtype or dtype_of(cfg.param_dtype)
        if W and W < seq_len:
            return init_ring_cache(cfg.num_layers, batch, W, kv, dh, dtype)
        return init_full_cache(cfg.num_layers, batch, seq_len, kv, dh, dtype)

    def cache_spec(batch=None, seq_len=None):
        # k/v: (L, B, T, KV, dh) — batch over data, cache seq over model.
        return {"k": P(None, BATCH, "model", None, None),
                "v": P(None, BATCH, "model", None, None),
                "kv_pos": P(BATCH, "model"),
                "length": P(BATCH)}

    return init_cache, cache_spec


def make_prefill(cfg: ModelConfig, forward_embed=None, angles_fn=None):
    dh_half = cfg.d_head // 2
    init_cache, _ = make_cache_fns(cfg)
    W = cfg.sliding_window

    def prefill(params, batch, max_len=None):
        tokens = batch["tokens"]
        B = tokens.shape[0]
        x = embed_tokens(params, cfg, tokens)
        S = x.shape[1]
        angles = (angles_fn(batch, S) if angles_fn
                  else rope_angles(jnp.arange(S), dh_half, cfg.rope_theta))
        x, kvs = forward_from_embeds(params, cfg, x, angles,
                                     window=W, plan=None, collect_kv=True)
        k, v = kvs                                     # (L, B, S, KV, dh)
        if W and W < S:
            assert S % W == 0, "prefill length must align the ring window"
            k, v = k[:, :, S - W:], v[:, :, S - W:]
            kv_pos = jnp.broadcast_to(jnp.arange(S - W, S), (B, W)).astype(jnp.int32)
        else:
            T = max_len or S
            pad = T - S
            if pad:
                zeros = jnp.zeros(k.shape[:2] + (pad,) + k.shape[3:], k.dtype)
                k = jnp.concatenate([k, zeros], axis=2)
                v = jnp.concatenate([v, zeros], axis=2)
            kv_pos = jnp.where(jnp.arange(T) < S, jnp.arange(T), -1)
            kv_pos = jnp.broadcast_to(kv_pos, (B, T)).astype(jnp.int32)
        cache = {"k": k, "v": v, "kv_pos": kv_pos,
                 "length": jnp.full((B,), S, jnp.int32)}
        return lm_logits(params, cfg, x[:, -1:]), cache

    return prefill


def make_decode_step(cfg: ModelConfig, angles_decode_fn=None,
                     collect_indices: bool = False):
    """collect_indices=True additionally returns the per-layer selected
    cold cluster ids (L, G, kc) — the real activation trace consumed by
    the serving engine's neuron cache / cold store / pipeline."""
    dh_half = cfg.d_head // 2
    W = cfg.sliding_window

    def decode_step(params, tokens, cache, plan: Optional[HybridPlan] = None,
                    active_mask=None):
        """tokens (B,1) -> (logits (B,1,V), cache'[, cluster_ids]).

        active_mask (B,) bool: live rows for the sparse-FFN batch-union
        selection; None = all rows live (the static-batch path)."""
        pos = cache["length"]                          # (B,)
        x = embed_tokens(params, cfg, tokens)
        angles = (angles_decode_fn(pos, dh_half) if angles_decode_fn
                  else rope_angles(pos[:, None], dh_half, cfg.rope_theta))
        kv_pos = write_pos(cache["kv_pos"], pos)

        def body(h, xs):
            lp, kc, vc = xs
            a, kc, vc = blocks.attn_decode(
                lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps), cfg, angles,
                kc, vc, kv_pos, pos, window=W)
            h = h + a
            f = blocks.apply_ffn_block(
                lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps), cfg, plan,
                return_indices=collect_indices, active_mask=active_mask)
            if collect_indices:
                f, cidx = f
            h = h + f
            return h, ((kc, vc, cidx) if collect_indices else (kc, vc))

        x, ys = blocks.scan_over(body, x, (params["layers"],
                                           cache["k"], cache["v"]))
        if collect_indices:
            k, v, cidx = ys
        else:
            k, v = ys
            cidx = None
        new_cache = dict(cache, k=k, v=v, kv_pos=kv_pos, length=pos + 1)
        logits = lm_logits(params, cfg, x)
        if collect_indices:
            return logits, new_cache, cidx
        return logits, new_cache

    return decode_step


def make_model(cfg: ModelConfig) -> Model:
    init_cache, cache_spec = make_cache_fns(cfg)
    return Model(
        cfg=cfg,
        init=lambda key: init_params(key, cfg),
        param_spec=lambda: params_spec(cfg),
        forward=make_forward(cfg),
        prefill=make_prefill(cfg),
        decode_step=make_decode_step(cfg),
        init_cache=init_cache,
        cache_spec=cache_spec,
    )
