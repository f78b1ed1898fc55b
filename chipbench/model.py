"""A configuration file made into what the program serves: its
`ModelConfig`, and seeded bf16 weights made on the device in one
jitted call.

The weights are the benchmark's, not the program's: every leaf is
drawn here from the seed (uniform, with the variance of a
1/sqrt(fan-in) init; RMSNorm gains 0, which the program's (1 + w)
norm reads as 1), so the plain reference can make the same arrays
without taking anything the program made. They are checked against
the shapes of the program's own `init` before they are served.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key32(seed: int):
    """A JAX key for any whole seed, also one wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    import dataclasses
    from repro.configs import get_config
    s = cfg["serve"]
    kw = dict(num_layers=cfg["num_hidden_layers"],
              d_model=cfg["hidden_size"],
              num_heads=cfg["num_attention_heads"],
              num_kv_heads=cfg["num_key_value_heads"],
              d_head=cfg.get("head_dim", cfg["hidden_size"]
                             // cfg["num_attention_heads"]),
              vocab_size=cfg["vocab_size"],
              tie_embeddings=bool(cfg["tie_word_embeddings"]),
              norm_eps=float(cfg["rms_norm_eps"]),
              rope_theta=float(cfg["rope_theta"]),
              activation=cfg["hidden_act"],
              param_dtype=s["dtype"], compute_dtype=s["dtype"])
    if cfg.get("n_routed_experts"):
        if cfg.get("first_k_dense_replace", 0):
            raise ValueError("the program's MoE has no leading dense "
                             "layer; state first_k_dense_replace 0")
        kw.update(d_ff=cfg["moe_intermediate_size"],
                  num_experts=cfg["n_routed_experts"],
                  num_shared_experts=cfg["n_shared_experts"],
                  experts_per_token=cfg["num_experts_per_tok"])
    else:
        kw["d_ff"] = cfg["intermediate_size"]
    base = get_config(s["program_arch"])
    sp = {k: s[k] for k in ("mode", "cluster_size", "predictor_rank",
                            "hot_ratio", "cold_active_ratio") if k in s}
    if sp:
        kw["sparse_ffn"] = dataclasses.replace(base.sparse_ffn, **sp)
    return base.replace(**kw)


def _leaves(pc) -> dict:
    """path -> (shape, per-row std on axis -2 or one std, zero?)."""
    D, L, Vp = pc.d_model, pc.num_layers, pc.vocab_padded
    H, KV, dh = pc.num_heads, pc.num_kv_heads, pc.d_head
    s = 1.0 / math.sqrt(D)
    out = {("embed",): ((Vp, D), s), ("out_norm",): ((D,), 0.0),
           ("layers", "ln1"): ((L, D), 0.0),
           ("layers", "ln2"): ((L, D), 0.0),
           ("layers", "attn", "wq"): ((L, D, H * dh), s),
           ("layers", "attn", "wk"): ((L, D, KV * dh), s),
           ("layers", "attn", "wv"): ((L, D, KV * dh), s),
           ("layers", "attn", "wo"): ((L, H * dh, D), 1 / math.sqrt(H * dh))}
    if not pc.tie_embeddings:
        out[("lm_head",)] = ((D, Vp), s)
    if pc.num_experts:
        E, f = pc.num_experts, pc.d_ff
        fs = f * pc.num_shared_experts
        out[("layers", "moe", "router")] = ((L, D, E), s)
        out[("layers", "moe", "experts")] = (
            (L, E, f, 3, D), (s, s, 1 / math.sqrt(f)))
        if fs:
            out[("layers", "moe", "shared", "w")] = (
                (L, fs, 3, D), (s, s, 1 / math.sqrt(fs)))
    else:
        N, r = pc.d_ff, pc.sparse_ffn.predictor_rank
        out[("layers", "ffn", "w")] = ((L, N, 3, D),
                                       (s, s, 1 / math.sqrt(N)))
        if pc.sparse_ffn.enabled:
            out[("layers", "ffn", "pred", "A")] = ((L, D, r), s)
            out[("layers", "ffn", "pred", "B")] = ((L, r, N),
                                                   1 / math.sqrt(r))
    return out


def make_weights(pc, seed: int, check_against_program: bool = True):
    """Every leaf from the seed, in the served dtype, on the device."""
    dtype = jnp.dtype(pc.param_dtype)
    leaves = _leaves(pc)
    paths = sorted(leaves)

    def gen(key):
        vals = []
        for i, p in enumerate(paths):
            shape, std = leaves[p]
            if std == 0.0:
                vals.append(jnp.zeros(shape, dtype))
                continue
            std = jnp.asarray(std, jnp.float32)
            if std.ndim:
                std = std[:, None]
            u = jax.random.uniform(jax.random.fold_in(key, i), shape,
                                   jnp.float32, -1.0, 1.0)
            vals.append((u * (std * math.sqrt(3.0))).astype(dtype))
        return vals

    vals = jax.jit(gen)(key32(seed))
    tree = {}
    for p, v in zip(paths, vals):
        node = tree
        for k in p[:-1]:
            node = node.setdefault(k, {})
        node[p[-1]] = v
    if check_against_program:
        from repro.serving.families import serving_family
        want = jax.eval_shape(serving_family(pc).make_model(pc).init,
                              jax.random.key(0))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
        exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
        if got != exp:
            raise ValueError(f"benchmark weights do not match the "
                             f"program's parameter tree:\n{got}\n{exp}")
    return tree


def nbytes(tree) -> int:
    return int(sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree)))
