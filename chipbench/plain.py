"""Plain pieces of the references: float32 at full matmul precision,
whole sequences, no cache, no kernels, nothing of the program.

The block is the program's model definition as a formula: RMSNorm
x * rsqrt(mean(x^2) + eps) * (1 + w); rotary embedding on the two
halves of each head with inverse frequencies theta^(-i/(dh/2));
grouped-query causal attention (query head h reads key/value head
h // (H/KV)), softmax scaled by dh^-0.5; the LM head over the final
RMSNorm (the embedding, transposed, when tied).

The control runs the same formulas on weights rounded to float8
(e4m3, one scale per output channel), the precision step below the
configuration's bfloat16 that a later change would be tempted by.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def fp8_round(w, axis):
    """Round w to float8 e4m3 with one absmax scale per slice along
    `axis` (the contraction axis), back in float32."""
    w = w.astype(F32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(F32))


def rotary(x, pos, theta):
    """x (T, heads, dh) at absolute positions pos (T,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, lw, cfg, pos, valid_k=None):
    """Causal self attention of one sequence x (T, D); lw holds wq,
    wk, wv, wo as float32. valid_k (T,) marks keys that exist."""
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"]
    dh = D // H
    T = x.shape[0]
    q = (x @ lw["wq"]).reshape(T, H, dh)
    k = (x @ lw["wk"]).reshape(T, KV, dh)
    v = (x @ lw["wv"]).reshape(T, KV, dh)
    q, k = rotary(q, pos, cfg["rope_theta"]), rotary(k, pos, cfg["rope_theta"])
    G = H // KV
    q = q.reshape(T, KV, G, dh)
    s = jnp.einsum("tkgd,ukd->kgtu", q, k) * dh ** -0.5
    mask = pos[None, :] <= pos[:, None]
    if valid_k is not None:
        mask = mask & valid_k[None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgtu,ukd->tkgd", p, v).reshape(T, H * dh)
    return o @ lw["wo"]


def swiglu_rows(x, w):
    """Per-neuron outputs of a bundled (N, 3, D) FFN: h (T, N) with
    h_n = silu(x . gate_n) * (x . up_n)."""
    g = x @ w[:, 0].T
    u = x @ w[:, 1].T
    return jax.nn.silu(g) * u


def head_table(weights, cfg):
    """The LM head as a (V, D) table: the embedding when tied, else the
    head transposed."""
    if cfg["tie_word_embeddings"]:
        return weights["embed"]
    return weights["lm_head"].T


def head_logits(h, out_norm, table, cfg, ctrl: bool, chunk: int = 4096):
    """Logits of rows h (n, D) over the vocabulary; the table's rows are
    taken (and, for the control, rounded, one scale per row) a slice at
    a time, so no float32 copy of the whole head is made."""
    x = rms_norm(h, out_norm, cfg["rms_norm_eps"])
    V = table.shape[0]
    c = math.gcd(V, chunk)

    def part(wc):
        wc = fp8_round(wc, -1) if ctrl else wc.astype(F32)
        return x @ wc.T

    out = jax.lax.map(part, table.reshape(V // c, c, table.shape[1]))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def attn_weights(layers, l, ctrl: bool) -> dict:
    out = {}
    for k in ("wq", "wk", "wv", "wo"):
        w = layers["attn"][k][l]
        out[k] = fp8_round(w, 0) if ctrl else w.astype(F32)
    return out


def gaps(logits, target, valid):
    """How far each row's target token's logit lies below the row's
    best, where `valid`."""
    best = logits.max(-1)
    tgt = jnp.take_along_axis(logits, target[:, None], -1)[:, 0]
    return jnp.where(valid, best - tgt, 0.0)


@dataclass
class Served:
    """What the timed path served, as the references read it."""
    requests: list                        # loop.ReqRec with >= 1 token
    steps: list                           # loop.StepRec, in order
    plans: dict                           # bucket -> (n_hot, kc, cs)
    buckets: tuple
    ctx_budget: int

    def bucket(self, step: int) -> int:
        n = len(self.steps[step].tokens)
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]


BATCH, BATCH_TOKENS = 8, 8192


def length_batches(requests, ctx_budget: int):
    """(length, batch, real) per batch of BATCH requests, or of
    BATCH_TOKENS // length where that is fewer, of one padded length (the smallest of 256, 512, 1024 and
    the context budget that holds prompt and served tokens); a short
    last batch is padded with repeats, of which only the first `real`
    count, so a reference compiles one program per length."""
    by = {}
    for r in requests:
        by.setdefault(padded_length(len(r.prompt) + len(r.tokens),
                                    ctx_budget), []).append(r)
    for T, rs in sorted(by.items()):
        n = max(1, min(BATCH, BATCH_TOKENS // T))
        for i in range(0, len(rs), n):
            part = rs[i:i + n]
            yield T, part + [part[-1]] * (n - len(part)), len(part)


def padded_length(need: int, ctx_budget: int) -> int:
    sizes = sorted({min(t, ctx_budget) for t in (256, 512, 1024)}
                   | {ctx_budget})
    return next(t for t in sizes if t >= need)


def summary(name: str, parts) -> dict:
    """The widest, the mean and the 99th percentile of a set of gaps,
    and the share of them that are exactly 0."""
    x = np.concatenate([np.ravel(p) for p in parts]) if parts else \
        np.zeros(0)
    if not x.size:
        return {name: float("nan")}
    return {name: float(x.max()), name + "_mean": float(x.mean()),
            name + "_p99": float(np.percentile(x, 99)),
            name + "_zero_share": float((x == 0).mean())}


def as_np_tree(x):
    return jax.tree.map(np.asarray, jax.device_get(x))
