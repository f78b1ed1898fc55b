"""Mixture-of-Experts FFN + MoE decoder model (grok-1 / deepseek-moe /
turbosparse-mixtral).

The paper's neuron-cluster abstraction maps onto MoE at two levels
(DESIGN.md §Arch-applicability):
  * expert level — shared experts (deepseek) are *hot clusters*
    (always-dense), routed experts are *cold clusters* gated by the
    router (which plays the predictor's role);
  * neuron level — inside each expert the hybrid hot/cold FFN applies
    (the paper's TurboSparse-Mixtral-47B case).

Dispatch is sort-based (fully jittable, capacity-dropped):
tokens -> top-k experts -> rank within expert via stable argsort ->
(E, C, D) dispatch buffer -> batched expert GEMMs -> weighted combine.

Sharding: 'ep' shards the expert dim over the mesh 'model' axis
(deepseek: 64/16 = 4 per shard); 'tp' shards d_ff inside every expert
(grok: 8 experts < 16 shards). Both selectable per config; roofline
hillclimb compares. For grouped training dispatch the pjit/constrain
formulation below lets XLA insert the all-to-alls; the serving decode
shape (one replica-local group) takes `_moe_ep_shard_map` instead —
replicated routing, strictly shard-local dispatch/combine, one psum
per layer — which is what makes ep=N decode token-identical to ep=1
(DESIGN.md §8).

Serving (DESIGN.md §8): `make_decode_step(cfg, collect_indices=True)`
is the family registry's traced decode — it accepts the engine's
`active_mask` (freed KV-arena lanes never consume expert capacity)
and returns the per-layer kept-dispatch counts (L, E), the expert
activation trace the storage plane prices as cold-cluster residency.
With `cfg.moe_intra_expert` (DESIGN.md §9, the TurboSparse-Mixtral
case) the trace refines to (L, E, 1+ncc): real per-cold-cluster
activation counts *inside* each expert, thresholded off the unchanged
dense expert GEMMs — decode stays token-identical while the storage
plane prices hot/cold clusters within each routed expert.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.clusters import HybridPlan
from repro.models import blocks, dense
from repro.models.attention import rope_angles
from repro.models.kv_cache import write_pos
from repro.models.modules import (
    dtype_of, dense_init, rms_norm, stack_layer_params)
from repro.core.sparse_ffn import init_ffn, ffn_spec, ffn_dense
from repro.sharding import constrain, BATCH


# ------------------------------------------------------------- MoE FFN ----

def init_moe_ffn(key, cfg: ModelConfig, dtype):
    from repro.core.sparse_ffn import ffn_rows
    E, f, d = cfg.num_experts, cfg.d_ff, cfg.d_model
    R = ffn_rows(cfg.activation)
    kr, ke, ks = jax.random.split(key, 3)
    p = {
        "router": dense_init(kr, (d, E), dtype),
        "experts": dense_init(ke, (E, f, R, d), dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_ffn(ks, d, f * cfg.num_shared_experts,
                               cfg.activation, dtype)
    return p


def moe_ffn_spec(cfg: ModelConfig):
    ep = cfg.moe_shard_mode == "ep"
    s = {"router": P(None, None),
         "experts": P("model", None, None, None) if ep
         else P(None, "model", None, None)}
    if cfg.num_shared_experts:
        s["shared"] = ffn_spec(False)
    return s


def _capacity(T: int, k: int, E: int, factor: float) -> int:
    c = int(T * k / E * factor)
    return max(8, ((c + 7) // 8) * 8)


def moe_dispatch(gates, k: int, capacity: int, active=None):
    """gates (T, E) router probs -> dispatch metadata.

    Returns (expert_idx (T,k), combine_w (T,k), slot (T,k), keep (T,k))
    where slot indexes a flat (E*C) buffer.

    active (T,) bool, optional: rows excluded from dispatch entirely —
    they never occupy a capacity slot, so a dead row (a freed KV-arena
    lane decoding garbage) can neither evict a live token past capacity
    nor shift any live token's slot. Inactive entries route to a
    sentinel expert bucket E that sorts after every real expert, which
    keeps capacity ranking for the live tokens *identical* to a
    dispatch over the live tokens alone.
    """
    T, E = gates.shape
    topv, tope = jax.lax.top_k(gates, k)                    # (T, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    flat_e = tope.reshape(-1)                               # (T*k,)
    if active is not None:
        flat_e = jnp.where(jnp.repeat(active, k), flat_e, E)
    order = jnp.argsort(flat_e, stable=True)
    ranks = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    counts = jnp.zeros((E + 1,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.cumsum(counts) - counts                   # exclusive
    pos_in_e = ranks - offsets[flat_e]                      # (T*k,)
    keep = (pos_in_e < capacity) & (flat_e < E)
    slot = jnp.where(keep, flat_e * capacity + pos_in_e, 0)
    return (tope, topv, slot.reshape(T, k), keep.reshape(T, k))


def _dispatch_group(xt, router, cfg, C, active=None):
    """One dispatch group: xt (T, D) -> (buf (E,C,D), combine metadata,
    aux loss, per-expert kept counts). Vmapped over data-local groups
    by apply_moe_ffn."""
    T, D = xt.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    gates = jax.nn.softmax(
        jnp.einsum("td,de->te", xt.astype(jnp.float32),
                   router.astype(jnp.float32)), axis=-1)
    tope, topv, slot, keep = moe_dispatch(gates, k, C, active)
    xk = jnp.broadcast_to(xt[:, None], (T, k, D)).reshape(T * k, D)
    wgt = jnp.where(keep.reshape(-1), 1.0, 0.0).astype(xt.dtype)
    buf = jnp.zeros((E * C, D), xt.dtype)
    buf = buf.at[slot.reshape(-1)].add(xk * wgt[:, None])
    # router load-balance aux loss (Switch-style)
    me = gates.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[tope.reshape(-1)].add(1.0 / (T * k))
    aux = E * jnp.sum(me * ce)
    counts = _expert_counts(tope, keep, E)
    return buf.reshape(E, C, D), (slot, keep, topv), aux, counts


def _expert_counts(tope, keep, E: int):
    """Kept dispatch entries per expert, (E,) int32 — the MoE
    activation trace the storage plane consumes (experts == clusters:
    an expert with count > 0 was activated this step)."""
    flat = jnp.where(keep.reshape(-1), tope.reshape(-1), E)
    return jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]


def _two_level_trace(cfg: ModelConfig, plan) -> bool:
    """True when the decode trace is the two-level (E, 1+ncc) form:
    intra-expert sparsity enabled and the stepped plan carries a
    per-expert hot prefix (DESIGN.md §9)."""
    return (cfg.moe_intra_expert and plan is not None
            and getattr(plan, "n_expert_hot", 0) > 0)


def _cold_cluster_counts(h, cfg: ModelConfig, n_hot_e: int, cs: int):
    """h (..., e_slice, C, f) real expert activations -> (e_slice, ncc)
    int32 active-(slot, neuron) counts per intra-expert *cold* cluster
    (rows are hot-first permuted, so the cold suffix starts at
    n_hot_e and groups into (f - n_hot_e)/cs clusters).

    The expert GEMMs are computed densely (numerics untouched), so the
    trace is the TRUE activation set: empty capacity slots and dropped
    dispatch entries contribute exact zeros (relu/silu of 0 is 0) and
    never mark a cluster active. With relu-family activations skipping
    an inactive cold cluster is lossless — exactly why the paper's
    TurboSparse models ReLUfy — which is what lets the storage plane
    price only the traced clusters while decode stays token-identical
    to dense-expert decode."""
    from repro.core.planner import _act_threshold
    tau = _act_threshold(cfg.sparse_ffn.mode)
    f = h.shape[-1]
    active = (jnp.abs(h) > tau).astype(jnp.int32)
    na = active.reshape((-1,) + h.shape[-3:]).sum(axis=(0, 2))  # (e, f)
    ncc = (f - n_hot_e) // cs
    return na[:, n_hot_e:].reshape(-1, ncc, cs).sum(axis=-1)


def _combine_group(yb, slot, keep, topv):
    """yb (E*C, D) expert outputs -> (T, D) fp32 weighted combine (the
    expert-parallel path sums in fp32 too, so both layouts round to
    the compute dtype once, after the whole sum)."""
    T, k = slot.shape
    yk = jnp.take(yb, slot.reshape(-1), axis=0).reshape(T, k, yb.shape[-1])
    yk = yk.astype(jnp.float32) * (topv * keep)[..., None]
    return yk.sum(axis=1)


def _use_ep_shard_map(cfg: ModelConfig, G: int) -> bool:
    """Shard-local expert parallelism applies when the mesh 'model'
    axis evenly splits the experts, sharding mode is 'ep', and the
    token block is a single replica-local group (the serving decode
    shape — grouped training dispatch keeps the pjit formulation)."""
    from repro.sharding import current_mesh
    m = current_mesh()
    if m is None or "model" not in m.axis_names or G != 1:
        return False
    if cfg.moe_shard_mode != "ep":
        return False
    n = dict(m.shape).get("model", 1)
    return n > 1 and cfg.num_experts % n == 0


def _moe_ep_shard_map(params, xt, cfg: ModelConfig, C: int, active_mask,
                      plan=None, collect_trace: bool = False):
    """Shard-local expert-parallel dispatch (DESIGN.md §8), mirroring
    the cold-group scheme of core/sparse_ffn._cold_path_shard_map: the
    mesh 'model' axis (size n) owns E/n whole experts per shard.

    Routing is computed *replicated* (the router weights replicate, so
    gates/top-k/capacity ranking are exactly the single-device math on
    every shard); dispatch and combine are strictly shard-local — each
    shard scatters only the (token, expert) entries whose expert it
    owns into its (E/n, C, D) buffer, runs its expert GEMMs, and
    combines a partial (T, D) output. One fp32 psum per layer crosses
    shards, so expert selection — and decoded tokens — are identical
    at every mesh size. Returns ((T, D) output, trace, aux).

    The trace is the (E,) kept counts, or — when the stepped plan
    enables two-level sparsity (DESIGN.md §9) — the (E, 1+ncc) form:
    each shard thresholds its own experts' real activations (the
    per-expert cold gathers stay strictly shard-local) and the local
    (E/n, 1+ncc) blocks are all_gather'd in expert order, the same
    id-only collective the dense cold path uses for its cluster ids.
    """
    from jax.sharding import PartitionSpec as PS
    from repro.sharding import current_mesh

    mesh = current_mesh()
    n = dict(mesh.shape)["model"]
    E, k = cfg.num_experts, cfg.experts_per_token
    e_loc = E // n
    w = params["experts"]                                   # (E, f, R, D)
    R = w.shape[2]
    from repro.models.modules import activation_fn
    act = activation_fn(cfg.activation)
    two_level = collect_trace and _two_level_trace(cfg, plan)
    n_hot_e = plan.n_expert_hot if two_level else 0
    cs = plan.cluster_size if two_level else 0

    def local(xl, wl, rl, ml):
        # xl (T, D) replicated; wl (e_loc, f, R, D) this shard's
        # experts; rl (D, E) replicated router; ml (T,) live-row mask.
        T, D = xl.shape
        gates = jax.nn.softmax(
            jnp.einsum("td,de->te", xl.astype(jnp.float32),
                       rl.astype(jnp.float32)), axis=-1)
        tope, topv, slot, keep = moe_dispatch(gates, k, C, ml)
        e0 = jax.lax.axis_index("model") * e_loc
        flat_e = tope.reshape(-1)
        sel = keep.reshape(-1) & (flat_e >= e0) & (flat_e < e0 + e_loc)
        lslot = jnp.where(sel, slot.reshape(-1) - e0 * C, 0)
        xk = jnp.broadcast_to(xl[:, None], (T, k, D)).reshape(T * k, D)
        wgt = jnp.where(sel, 1.0, 0.0).astype(xl.dtype)
        buf = jnp.zeros((e_loc * C, D), xl.dtype)
        buf = buf.at[lslot].add(xk * wgt[:, None]).reshape(e_loc, C, D)
        g = jnp.einsum("ecd,efd->ecf", buf, wl[:, :, 0])
        if R == 3:
            u = jnp.einsum("ecd,efd->ecf", buf, wl[:, :, 1])
            h = act(g) * u
        else:
            h = act(g)
        yb = jnp.einsum("ecf,efd->ecd", h, wl[:, :, -1])
        yk = jnp.take(yb.reshape(e_loc * C, D), lslot, axis=0)
        yk = yk.reshape(T, k, D).astype(jnp.float32) \
            * (topv * sel.reshape(T, k))[..., None]
        # psum in f32 (same rationale as _cold_path_shard_map); the
        # kept counts and aux loss are replicated global math — no
        # collective beyond the one output reduction (plus, for the
        # two-level trace, the id-only all_gather below).
        y = jax.lax.psum(yk.sum(axis=1).astype(jnp.float32), "model")
        me = gates.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[tope.reshape(-1)].add(
            1.0 / (T * k))
        aux = E * jnp.sum(me * ce)
        counts = _expert_counts(tope, keep, E)
        if two_level:
            # this shard's experts' real activations -> local
            # (e_loc, 1+ncc) block, gathered in expert-block order
            cold = _cold_cluster_counts(h, cfg, n_hot_e, cs)
            loc = jax.lax.dynamic_slice_in_dim(counts, e0, e_loc)
            blk = jnp.concatenate([loc[:, None], cold], axis=1)
            trace = jax.lax.all_gather(blk, "model").reshape(
                E, blk.shape[1]).astype(jnp.int32)
        else:
            trace = counts
        return y, trace, aux

    if active_mask is None:
        active_mask = jnp.ones((xt.shape[0],), bool)
    tr_spec = PS(None, None) if two_level else PS(None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(PS(None, None), PS("model", None, None, None),
                  PS(None, None), PS(None)),
        out_specs=(PS(None, None), tr_spec, PS()),
        axis_names={"model"}, check_vma=False)
    y, counts, aux = fn(xt, w, params["router"], active_mask)
    return y.astype(xt.dtype), counts, aux


def apply_moe_ffn(params, x, cfg: ModelConfig,
                  plan: Optional[HybridPlan] = None,
                  active_mask=None, collect_trace: bool = False):
    """x (..., D) -> ((..., D), aux[, trace]). Train (T=B*S) and
    decode (T=B).

    active_mask (T,) bool: rows excluded from dispatch (the serving
    engine's freed KV-arena lanes) — they must neither consume expert
    capacity nor appear in the activation trace. collect_trace=True
    additionally returns the activation trace the serving storage
    plane consumes: the per-expert kept-entry counts (E,) int32, or —
    when `cfg.moe_intra_expert` and the stepped plan carries a
    per-expert hot prefix — the two-level (E, 1+ncc) form whose first
    column is the kept counts and whose remaining columns count real
    activations per intra-expert cold cluster (DESIGN.md §9). The
    expert compute itself never changes: the trace thresholds the
    dense GEMMs' activations, so two-level decode is token-identical
    to whole-expert decode by construction.

    Hierarchical dispatch (§Perf iteration, EXPERIMENTS.md): tokens are
    routed within `moe_dispatch_groups` data-local groups (group dim
    sharded over batch axes, experts over 'model'), so the dispatch
    buffer is (G, E, C_local, D) — per-device E_local*C_local*D —
    instead of a replicated global (E, C_global, D). Per-token top-k is
    unchanged; only capacity dropping becomes group-local, which is
    *more* faithful to EP systems (capacity is per-device there too).
    """
    shape = x.shape
    D = shape[-1]
    xt = x.reshape(-1, D)                                   # (T, D)
    T = xt.shape[0]
    E, k = cfg.num_experts, cfg.experts_per_token
    G = cfg.moe_dispatch_groups \
        if cfg.moe_dispatch_groups > 0 and T % cfg.moe_dispatch_groups == 0 \
        else 1
    Tg = T // G
    C = _capacity(Tg, k, E, cfg.moe_capacity_factor)
    w = params["experts"]                                   # (E, f, R, D)

    if _use_ep_shard_map(cfg, G):
        with jax.named_scope("experts"):
            y, trace, aux = _moe_ep_shard_map(
                params, xt, cfg, C, active_mask, plan=plan,
                collect_trace=collect_trace)
        if "shared" in params:                              # hot clusters
            with jax.named_scope("ffn_hot"):
                y = y + ffn_dense(params["shared"], xt, cfg.activation)
        y = y.reshape(shape)
        return (y, aux, trace) if collect_trace else (y, aux)

    with jax.named_scope("experts"):
        xg = constrain(xt.reshape(G, Tg, D), P(BATCH, None, None))
        mask = jnp.ones((T,), bool) if active_mask is None \
            else active_mask.reshape(-1)
        buf, meta, auxg, cnts = jax.vmap(
            lambda xx, mm: _dispatch_group(xx, params["router"], cfg, C, mm)
        )(xg, mask.reshape(G, Tg))

        # explicit all-to-all: the dispatch buffer reshards from
        # batch-sharded groups to expert-sharded slots — tokens move to the
        # experts' shards instead of XLA all-gathering every expert's
        # weights onto every data shard (§Perf iteration 3).
        ep = cfg.moe_shard_mode == "ep"
        espec = P(BATCH, "model", None, None) if ep \
            else P(BATCH, None, None, None)
        buf = constrain(buf, espec)

        from repro.models.modules import activation_fn
        act = activation_fn(cfg.activation)
        R = w.shape[2]
        g = jnp.einsum("gecd,efd->gecf", buf, w[:, :, 0])
        g = constrain(g, P(BATCH, "model", None, None) if ep
                      else P(BATCH, None, None, "model"))
        if R == 3:
            u = jnp.einsum("gecd,efd->gecf", buf, w[:, :, 1])
            h = act(g) * u
        else:
            h = act(g)
        yb = jnp.einsum("gecf,efd->gecd", h, w[:, :, -1])
        # all-to-all back: expert-sharded outputs return to their groups
        yb = constrain(yb, P(BATCH, None, None, None))
        slot, keep, topv = meta
        yg = jax.vmap(_combine_group)(
            yb.reshape(G, E * C, D), slot, keep, topv)
        yg = constrain(yg, P(BATCH, None, None))
        y = yg.reshape(T, D).astype(xt.dtype)
        aux = auxg.mean()

    if "shared" in params:                                  # hot clusters
        with jax.named_scope("ffn_hot"):
            y = y + ffn_dense(params["shared"], xt, cfg.activation)
    y = y.reshape(shape)
    if collect_trace:
        counts = cnts.sum(axis=0)                           # (E,) counts
        if _two_level_trace(cfg, plan):
            cold = _cold_cluster_counts(h, cfg, plan.n_expert_hot,
                                        plan.cluster_size)
            return y, aux, jnp.concatenate(
                [counts[:, None], cold], axis=1).astype(jnp.int32)
        return y, aux, counts
    return y, aux


# ------------------------------------------------------------- model ----

def init_layer(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": blocks.init_attn(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "moe": init_moe_ffn(k2, cfg, dtype),
    }


def layer_spec(cfg: ModelConfig):
    return {"ln1": P(None), "attn": blocks.attn_spec(cfg),
            "ln2": P(None), "moe": moe_ffn_spec(cfg)}


def init_params(key, cfg: ModelConfig):
    dtype = dtype_of(cfg.param_dtype)
    ke, kl, kh = jax.random.split(key, 3)
    from repro.models.modules import embed_init
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


def make_model(cfg: ModelConfig) -> dense.Model:
    dh_half = cfg.d_head // 2
    init_cache, cache_spec = dense.make_cache_fns(cfg)
    W = cfg.sliding_window

    def forward(params, batch, plan=None):
        tokens = batch["tokens"]
        x = dense.embed_tokens(params, cfg, tokens)
        S = x.shape[1]
        angles = rope_angles(jnp.arange(S), dh_half, cfg.rope_theta)

        def body(h, lp):
            a, _ = blocks.attn_full(lp["attn"],
                                    rms_norm(h, lp["ln1"], cfg.norm_eps),
                                    cfg, angles, causal=True, window=W)
            h = h + a
            f, aux = apply_moe_ffn(lp["moe"],
                                   rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
            return h + f, aux

        x, auxs = blocks.scan_layers(body, x, params["layers"],
                                     remat=cfg.remat)
        logits = dense.lm_logits(params, cfg, x)
        return logits

    def prefill(params, batch, max_len=None):
        tokens = batch["tokens"]
        B = tokens.shape[0]
        x = dense.embed_tokens(params, cfg, tokens)
        S = x.shape[1]
        angles = rope_angles(jnp.arange(S), dh_half, cfg.rope_theta)

        def body(h, lp):
            a, kv = blocks.attn_full(lp["attn"],
                                     rms_norm(h, lp["ln1"], cfg.norm_eps),
                                     cfg, angles, causal=True, window=W)
            h = h + a
            f, _ = apply_moe_ffn(lp["moe"],
                                 rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
            return h + f, kv

        x, (k, v) = blocks.scan_layers(body, x, params["layers"],
                                       remat=cfg.remat)
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
        return dense.lm_logits(params, cfg, x[:, -1:]), cache

    return dense.Model(
        cfg=cfg,
        init=lambda key: init_params(key, cfg),
        param_spec=lambda: params_spec(cfg),
        forward=forward,
        prefill=prefill,
        decode_step=make_decode_step(cfg),
        init_cache=init_cache,
        cache_spec=cache_spec,
    )


def make_decode_step(cfg: ModelConfig, collect_indices: bool = False):
    """Serving decode step with the uniform family signature
    (params, tokens, cache, plan, active_mask) -> (logits, cache[,
    trace]). The router plays the predictor's role (DESIGN.md §8);
    the hybrid plan never alters the expert compute, it only shapes
    the trace: collect_indices=True returns the per-layer
    kept-dispatch counts (L, E), or the two-level (L, E, 1+ncc) trace
    when the plan carries a per-expert hot prefix
    (cfg.moe_intra_expert, DESIGN.md §9) — the activation trace the
    storage plane prices exactly like dense cold-cluster selections."""
    dh_half = cfg.d_head // 2
    W = cfg.sliding_window

    def decode_step(params, tokens, cache, plan=None, active_mask=None):
        pos = cache["length"]
        x = dense.embed_tokens(params, cfg, tokens)
        angles = rope_angles(pos[:, None], dh_half, cfg.rope_theta)
        kv_pos = write_pos(cache["kv_pos"], pos)

        def body(h, xs):
            lp, kc, vc = xs
            a, kc, vc = blocks.attn_decode(
                lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps), cfg,
                angles, kc, vc, kv_pos, pos, window=W)
            h = h + a
            out = apply_moe_ffn(lp["moe"],
                                rms_norm(h, lp["ln2"], cfg.norm_eps), cfg,
                                plan=plan, active_mask=active_mask,
                                collect_trace=collect_indices)
            if collect_indices:
                f, _, tr = out
                h = h + f
                return h, (kc, vc, tr)
            f, _ = out
            return h + f, (kc, vc)

        x, ys = blocks.scan_over(body, x, (params["layers"],
                                           cache["k"], cache["v"]))
        if collect_indices:
            k, v, trace = ys
        else:
            k, v = ys
        new_cache = dict(cache, k=k, v=v, kv_pos=kv_pos, length=pos + 1)
        logits = dense.lm_logits(params, cfg, x)
        if collect_indices:
            return logits, new_cache, trace
        return logits, new_cache

    return decode_step
