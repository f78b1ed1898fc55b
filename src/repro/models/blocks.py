"""Shared transformer building blocks: attention block (full + decode),
FFN block wiring (dense or PowerInfer-2 hybrid), layer-scan helpers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.sparse_ffn import init_ffn, ffn_spec, ffn_apply
from repro.models.attention import (
    apply_rotary, decode_attention, flash_attention, maybe_qk_norm)
from repro.models.modules import dense_init
from repro.sharding import constrain, BATCH


# ------------------------------------------------------------ attention ----

def init_attn(key, cfg: ModelConfig, dtype, kv_heads=None, q_dim=None):
    h, dh = cfg.num_heads, cfg.d_head
    kv = kv_heads if kv_heads is not None else cfg.num_kv_heads
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * dh), dtype),
        "wk": dense_init(ks[1], (d, kv * dh), dtype),
        "wv": dense_init(ks[2], (d, kv * dh), dtype),
        "wo": dense_init(ks[3], (h * dh, d), dtype),
    }
    if cfg.qk_norm:
        p["qk"] = {"q_norm": jnp.zeros((dh,), dtype),
                   "k_norm": jnp.zeros((dh,), dtype)}
    return p


def attn_spec(cfg: ModelConfig):
    s = {"wq": P(None, "model"), "wk": P(None, "model"),
         "wv": P(None, "model"), "wo": P("model", None)}
    if cfg.qk_norm:
        s["qk"] = {"q_norm": P(None), "k_norm": P(None)}
    return s


def _qkv(p, x, cfg: ModelConfig, angles, k_angles=None):
    """Project + rope. x (B,S,D) -> q (B,S,H,dh), k/v (B,S,KV,dh)."""
    B, S, _ = x.shape
    h, dh = cfg.num_heads, cfg.d_head
    kv = p["wk"].shape[1] // dh
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(B, S, h, dh)
    k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(B, S, kv, dh)
    v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(B, S, kv, dh)
    q, k = maybe_qk_norm(q, k, p.get("qk"), cfg.norm_eps)
    if angles is not None:
        q = apply_rotary(q, angles)
        k = apply_rotary(k, k_angles if k_angles is not None else angles)
    q = constrain(q, P(BATCH, None, "model", None))
    k = constrain(k, P(BATCH, None, None, None))
    return q, k, v


def attn_full(p, x, cfg: ModelConfig, angles, *, causal=True, window=0):
    """Full-sequence self attention. Returns (out, (k, v)) for caching."""
    with jax.named_scope("attention"):
        q, k, v = _qkv(p, x, cfg, angles)
        o = flash_attention(q, k, v, causal=causal, window=window)
        B, S = x.shape[:2]
        out = jnp.einsum("bse,ed->bsd", o.reshape(B, S, -1), p["wo"])
        return constrain(out, P(BATCH, None, None)), (k, v)


def attn_decode(p, x, cfg: ModelConfig, angles, k_cache, v_cache, kv_pos,
                pos, *, window=0):
    """One-token self attention vs cache. x (B,1,D); pos (B,) absolute.

    Writes the new token's k/v (RoPE pre-applied) into its slot, then
    attends over the updated cache. `kv_pos` must already include the
    current position (updated once per step by the model, not per layer).
    Returns (out, k_cache', v_cache').
    """
    from repro.models.kv_cache import write_kv
    with jax.named_scope("attention"):
        q, k_new, v_new = _qkv(p, x, cfg, angles)
        with jax.named_scope("kv_write"):
            k_cache, v_cache = write_kv(k_cache, v_cache, k_new, v_new, pos)
        o = decode_attention(q, k_cache, v_cache, kv_pos, pos, window=window)
        out = jnp.einsum("bse,ed->bsd", o.reshape(*x.shape[:2], -1), p["wo"])
        return constrain(out, P(BATCH, None, None)), k_cache, v_cache


def cross_attn(p, x, mem_k, mem_v, cfg: ModelConfig):
    """Cross attention to precomputed encoder memory (B,Sm,KV,dh)."""
    B, S, _ = x.shape
    h, dh = cfg.num_heads, cfg.d_head
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(B, S, h, dh)
    o = flash_attention(q, mem_k, mem_v, causal=False)
    out = jnp.einsum("bse,ed->bsd", o.reshape(B, S, -1), p["wo"])
    return constrain(out, P(BATCH, None, None))


# ------------------------------------------------------------------ FFN ----

def init_ffn_block(key, cfg: ModelConfig, dtype):
    rank = cfg.sparse_ffn.predictor_rank if cfg.sparse_ffn.enabled else 0
    return init_ffn(key, cfg.d_model, cfg.d_ff, cfg.activation, dtype,
                    predictor_rank=rank)


def ffn_block_spec(cfg: ModelConfig):
    return ffn_spec(cfg.sparse_ffn.enabled)


def apply_ffn_block(params, x, cfg: ModelConfig, plan, return_indices=False,
                    active_mask=None):
    return ffn_apply(params, x, cfg.activation, cfg.sparse_ffn, plan,
                     return_indices=return_indices, active_mask=active_mask)


# ------------------------------------------------------------- scanning ----

# When True, layer scans unroll into Python loops. Used ONLY by the
# roofline cost probe (launch/dryrun --probe): XLA's cost analysis
# counts a while-loop body once regardless of trip count, so the probe
# lowers unrolled reduced-depth variants and extrapolates linearly.
UNROLL = False


def scan_over(body, carry, xs, length=None):
    """lax.scan, or an unrolled Python loop when UNROLL is set."""
    if not UNROLL:
        return jax.lax.scan(body, carry, xs, length=length)
    n = length if length is not None else jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a, i=i: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        ys = jax.tree.map(lambda *ts: jnp.stack(ts), *ys)
    else:
        ys = None
    return carry, ys


def scan_layers(body, carry, layer_params, *per_layer_xs, remat=False,
                length=None):
    """Scan over stacked layer params (leaves have leading L dim)."""
    fn = jax.checkpoint(body) if remat else body
    xs = (layer_params,) + per_layer_xs if per_layer_xs else layer_params
    return scan_over(fn, carry, xs, length=length)
