"""Plain reference of a dense model served with PowerInfer-2's hybrid
FFN (a Llama-style block; for smollm-135m).

What the served path computes, as formulas:

* prefill: every prompt position through the whole dense SwiGLU FFN;
* decode: each new position through the hot prefix [0, n_hot) of the
  FFN plus the kc cold clusters the step picked, where cluster c is
  neurons [n_hot + c*cs, n_hot + (c+1)*cs) and a picked neuron counts
  only where the token's own predictor score (x A B) is above 0 (in the
  CATS mode; in any other mode every picked neuron counts);
* the pick: per layer, the kc clusters with the highest cluster
  maximum of the batch union (max over the step's live rows) of the
  predictor scores.

Each request is run once over its prompt and served tokens (teacher
forcing), with the clusters each step reported it picked. Two numbers:

* token_gap: the widest gap by which a served token's logit lies
  below the reference's best at that position;
* pick_gap: the widest gap by which a picked cluster's union score
  lies below the kc-th best union score of its step and layer, the
  union taken over every request live in that step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import plain

F32 = jnp.float32


def _forward(weights, cfg, seq, S, n_hot, ids, ctrl):
    """One request: logits, targets seq[p+1], and each position's
    score maxima over the grid of clusters (T, L, N / cs)."""
    T = seq.shape[0]
    L = cfg["num_hidden_layers"]
    eps = cfg["rms_norm_eps"]
    lay = weights["layers"]
    N = lay["ffn"]["w"].shape[1]
    cs = cfg["serve"]["cluster_size"]
    pos = jnp.arange(T)
    emb = weights["embed"]
    h = (plain.fp8_round(emb, -1) if ctrl else emb.astype(F32))[seq]
    decode = pos >= S
    col = jnp.arange(N)
    cats = cfg["serve"]["mode"] == "cats"

    def body(h, l):
        lw = plain.attn_weights(lay, l, ctrl)
        x = plain.rms_norm(h, lay["ln1"][l], eps)
        h = h + plain.attention(x, lw, cfg, pos)
        x = plain.rms_norm(h, lay["ln2"][l], eps)
        w = lay["ffn"]["w"][l]
        A, B = lay["ffn"]["pred"]["A"][l], lay["ffn"]["pred"]["B"][l]
        if ctrl:
            w = jnp.stack([plain.fp8_round(w[:, 0], -1),
                           plain.fp8_round(w[:, 1], -1),
                           plain.fp8_round(w[:, 2], 0)], 1)
            A, B = plain.fp8_round(A, 0), plain.fp8_round(B, 0)
        else:
            w, A, B = w.astype(F32), A.astype(F32), B.astype(F32)
        score = (x @ A) @ B                                   # (T, N)
        cl = (col[None, :] - n_hot[:, None]) // cs            # (T, N)
        picked = (cl[:, :, None] == ids[:, l][:, None, :]).any(-1)
        cold = (col[None, :] >= n_hot[:, None]) & picked
        if cats:
            cold = cold & (score > 0)
        keep = jnp.where(decode[:, None],
                         (col[None, :] < n_hot[:, None]) | cold, True)
        hn = plain.swiglu_rows(x, w) * keep
        h = h + hn @ w[:, 2]
        # cluster maxima on the grid of cs neurons: n_hot is a whole
        # number of clusters, so the cold clusters are grid cells
        return h, score.reshape(T, N // cs, cs).max(-1)

    h, cmax = jax.lax.scan(body, h, jnp.arange(L))
    logits = plain.head_logits(h, weights["out_norm"],
                               plain.head_table(weights, cfg), cfg, ctrl)
    target = jnp.concatenate([seq[1:], seq[:1]])
    return logits, target, jnp.swapaxes(cmax, 0, 1)


def _cold(grid, first, nc):
    """Per position, the cold clusters' maxima from the grid's (n, L,
    N/cs): cells first[i] on, padded with -inf to nc."""
    n, L, G = grid.shape
    out = np.full((n, L, nc), -np.inf, np.float32)
    for i in range(n):
        k = G - first[i]
        out[i, :, :k] = grid[i, :, first[i]:]
    return out


def _make(cfg, ctrl: bool):
    def one(weights, seq, S, n_hot, ids, valid):
        lg, tgt, cm = _forward(weights, cfg, seq, S, n_hot, ids, False)
        out = {"gap": plain.gaps(lg, tgt, valid), "cm": cm}
        if ctrl:
            lc, _, cmc = _forward(weights, cfg, seq, S, n_hot, ids, True)
            out["ctrl_gap"] = plain.gaps(lg, jnp.argmax(lc, -1), valid)
            out["ctrl_cm"] = cmc
        return out

    def run(weights, *batch):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0))(weights,
                                                                 *batch)
    return jax.jit(run)


def readings(cfg: dict, weights, served: plain.Served,
             control: bool = False) -> dict:
    L = cfg["num_hidden_layers"]
    cs = cfg["serve"]["cluster_size"]
    N = weights["layers"]["ffn"]["w"].shape[1]
    if any(p[0] % cs for p in served.plans.values()):
        raise ValueError("a hot prefix that is not whole clusters")
    nc = (N - min(p[0] for p in served.plans.values())) // cs
    kmax = max(p[1] for p in served.plans.values())
    fn = _make(cfg, control)
    n_steps = len(served.steps)
    union = np.full((n_steps, L, nc), -np.inf, np.float32)
    cunion = np.full((n_steps, L, nc), -np.inf, np.float32) \
        if control else None
    tok_gaps, ctrl_gaps = [], []

    def inputs(r, T):
        S, n = len(r.prompt), len(r.tokens)
        seq = np.zeros(T, np.int32)
        seq[:S], seq[S:S + n] = r.prompt, r.tokens
        n_hot = np.zeros(T, np.int32)
        ids = np.full((T, L, kmax), -1, np.int32)
        for j in range(n):
            s = r.first_step + j
            nh, kc, _ = served.plans[served.bucket(s)]
            n_hot[S + j] = nh
            ids[S + j, :, :kc] = np.asarray(served.steps[s].trace)[:, 0, :kc]
        valid = np.zeros(T, bool)
        valid[S - 1:S + n - 1] = True
        return seq, np.int32(S), n_hot, ids, valid

    for T, reqs, real in plain.length_batches(served.requests,
                                              served.ctx_budget):
        cols = list(zip(*[inputs(r, T) for r in reqs]))
        out = plain.as_np_tree(fn(weights, *[np.stack(c) for c in cols]))
        for b, r in enumerate(reqs[:real]):
            S, n = len(r.prompt), len(r.tokens)
            valid = cols[4][b]
            tok_gaps.append(out["gap"][b][valid])
            st = r.first_step + np.arange(n)
            first = cols[2][b][S:S + n] // cs          # n_hot in clusters
            np.maximum.at(union, st, _cold(out["cm"][b][S:S + n], first, nc))
            if control:
                ctrl_gaps.append(out["ctrl_gap"][b][valid])
                np.maximum.at(cunion, st,
                              _cold(out["ctrl_cm"][b][S:S + n], first, nc))
    pick = []
    ctrl_pick = []
    for s, rec in enumerate(served.steps):
        if not np.isfinite(union[s]).any():
            continue
        _, kc, _ = served.plans[served.bucket(s)]
        tr = np.asarray(rec.trace)[:, 0, :kc]                 # (L, kc)
        tau = -np.sort(-union[s], axis=1)[:, kc - 1]          # (L,)
        got = np.take_along_axis(union[s], tr, 1).min(1)
        pick.append(np.maximum(tau - got, 0.0))
        if control:
            cp = np.argsort(-cunion[s], axis=1)[:, :kc]
            cg = np.take_along_axis(union[s], cp, 1).min(1)
            ctrl_pick.append(np.maximum(tau - cg, 0.0))
    out = dict(plain.summary("token_gap", tok_gaps),
               **plain.summary("pick_gap", pick),
               tokens=int(sum(len(r.tokens) for r in served.requests)),
               requests=len(served.requests), picks=len(pick) * L)
    if control:
        out["control"] = dict(plain.summary("token_gap", ctrl_gaps),
                              **plain.summary("pick_gap", ctrl_pick))
    return out
