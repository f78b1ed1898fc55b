"""A configuration file made into what the program serves: its
`ModelConfig`, and seeded bf16 weights made on the device in one
jitted call.

The weights are the benchmark's, not the program's: every leaf is
drawn here from the seed (uniform, with the variance of a
1/sqrt(fan-in) init; RMSNorm gains 0, which the program's (1 + w)
norm reads as 1), so the plain reference can make the same arrays
without taking anything the program made. They are checked against
the shapes of the program's own `init` before they are served.

The tree drawn is the cell's reference's `leaves(pc, cfg)` where it
declares one (a layer kind with weights of its own: a leading dense
FFN, latent attention's projections), else `default_leaves(pc)`, the
Llama-style dense block or the plain MoE block.
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


def program_config(cfg: dict, reference=None):
    """The program's ModelConfig for a configuration file: its keys
    mapped to the program's fields, then `serve.program`, an object of
    ModelConfig field -> value, for fields no key maps. A file with
    leading dense layers needs a `reference` that declares their
    weights (`leaves`)."""
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
    if cfg.get("first_k_dense_replace", 0) and \
            not hasattr(reference, "leaves"):
        raise ValueError(
            "first_k_dense_replace is "
            f"{cfg['first_k_dense_replace']}: the configuration's "
            "reference must declare the leading dense layers' weights "
            "in leaves(pc, cfg)")
    if cfg.get("n_routed_experts"):
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
    extra = s.get("program", {})
    unknown = sorted(set(extra) - {f.name for f in dataclasses.fields(base)})
    if unknown:
        raise ValueError(f"serve.program names no ModelConfig field: "
                         f"{', '.join(unknown)}")
    mapped = sorted(set(extra) & set(kw))
    if mapped:
        raise ValueError(f"serve.program sets what the file's keys set: "
                         f"{', '.join(mapped)}")
    return base.replace(**kw, **extra)


def default_leaves(pc) -> dict:
    """path -> (shape, std): one std, a tuple of per-row stds on axis
    -2, or 0.0 for zeros."""
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


def make_weights(pc, seed: int, check_against_program: bool = True,
                 reference=None, cfg: dict = None):
    """Every leaf from the seed, in the served dtype, on the device:
    the tree `reference.leaves(pc, cfg)` declares, or the default one.
    Paths are drawn in sorted order, each from its own fold of the
    key, so a leaf's values depend on the set of paths alone."""
    dtype = jnp.dtype(pc.param_dtype)
    if hasattr(reference, "leaves"):
        leaves = reference.leaves(pc, cfg)
    else:
        leaves = default_leaves(pc)
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
        diff = tree_diff(tree, want)
        if diff:
            raise ValueError("benchmark weights do not match the "
                             "program's parameter tree:\n" + diff)
    return tree


def _shapes(tree) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            f"{tuple(a.shape)} {a.dtype}"
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


def tree_diff(got, want) -> str:
    """The paths only one of two trees has, and those whose shape or
    dtype differ; empty where they agree."""
    g, w = _shapes(got), _shapes(want)
    lines = [f"  only the benchmark's: {p} {g[p]}"
             for p in sorted(g.keys() - w.keys())]
    lines += [f"  only the program's: {p} {w[p]}"
              for p in sorted(w.keys() - g.keys())]
    lines += [f"  differs: {p} benchmark {g[p]}, program {w[p]}"
              for p in sorted(g.keys() & w.keys()) if g[p] != w[p]]
    return "\n".join(lines)


def nbytes(tree) -> int:
    return int(sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree)))
