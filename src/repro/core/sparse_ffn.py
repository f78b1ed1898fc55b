"""Hybrid hot/cold FFN — the paper's technique as a composable JAX module.

Weight layout (paper §4.4 "flexible neuron loading"): one bundled tensor
`w` of shape (N, R, D) — neuron-major so that neuron *i*'s Gate row,
Up row and Down column are contiguous (R=3 for gated FFNs, R=2 for
ungated: [fc1, fc2]). This is exactly the paper's position-major
Gate-Up-Down bundle: one fetch per neuron brings all of it.

Three compute paths:
  * ffn_dense   — full dense FFN; train / prefill ("NPU-centric", §4.1.1)
                  and the hot prefix of decode.
  * ffn_hybrid  — decode: dense hot prefix + predictor-gated gathered
                  cold clusters (§4.1.2). Cold neurons are re-densified
                  into MXU-aligned gathered tiles (TPU adaptation of the
                  paper's CPU sparse path — see DESIGN.md §2).
  * Pallas backend — plan.backend='pallas' routes the WHOLE cold path
                  (predictor score -> batch-union top-k -> cluster
                  gather -> gated FFN, incl. CATS token gating) through
                  one fused kernel, kernels/cluster_gather_ffn.
                  fused_cold_ffn: in-kernel selection drives
                  double-buffered HBM->VMEM cluster DMA — the paper's
                  neuron-cluster-level I/O pipeline at VMEM granularity
                  (DESIGN.md §10). Composes with the shard_map cold
                  path below (each shard runs the kernel over its local
                  groups) and selects the same clusters as the jnp
                  backend bit-for-bit, so decode is token-identical.

Distribution: the neuron dim is grouped as (groups, N/groups) with the
group dim sharded over the mesh 'model' axis; predictor scoring, top-k
selection and gathering are all per-group, so the cold path needs *no*
collective beyond the FFN's usual output reduction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.clusters import HybridPlan
from repro.core.predictor import (
    SCORE_PRECISION, init_predictor, predictor_spec, predict_scores)
from repro.models.modules import dense_init, activation_fn
from repro.sharding import constrain, BATCH


def ffn_rows(activation: str) -> int:
    return 2 if activation == "gelu" else 3


def init_ffn(key, d_model: int, d_ff: int, activation: str, dtype,
             predictor_rank: int = 0):
    """Bundled FFN params (+ optional activation predictor)."""
    kw, kp = jax.random.split(key)
    R = ffn_rows(activation)
    w = dense_init(kw, (d_ff, R, d_model), dtype)
    params = {"w": w}
    if predictor_rank:
        params["pred"] = init_predictor(kp, d_model, d_ff, predictor_rank, dtype)
    return params


def ffn_spec(has_predictor: bool):
    spec = {"w": P("model", None, None)}
    if has_predictor:
        spec["pred"] = predictor_spec()
    return spec


def _apply_bundle(w, x, activation: str):
    """Dense FFN over a (n, R, D) bundle slice. x (..., D) -> (..., D)."""
    act = activation_fn(activation)
    g = jnp.einsum("...d,nd->...n", x, w[:, 0])
    if w.shape[1] == 3:
        u = jnp.einsum("...d,nd->...n", x, w[:, 1])
        h = act(g) * u
    else:
        h = act(g)
    return jnp.einsum("...n,nd->...d", h, w[:, -1])


def ffn_dense(params, x, activation: str):
    """Full dense FFN (the prefill/train path; paper §4.1.1)."""
    w = params["w"]
    act = activation_fn(activation)
    g = jnp.einsum("...d,nd->...n", x, w[:, 0])
    g = constrain(g, P(BATCH, *([None] * (g.ndim - 2)), "model"))
    if w.shape[1] == 3:
        u = jnp.einsum("...d,nd->...n", x, w[:, 1])
        h = act(g) * u
    else:
        h = act(g)
    y = jnp.einsum("...n,nd->...d", h, w[:, -1])
    return constrain(y, P(BATCH, *([None] * (y.ndim - 1))))


def _gather_quant(wq, wsc, wout, cidx):
    """Gather selected cold clusters from the stored quantized
    representation and dequantize at the gather boundary (§7.6):
    int8 codes * per-row scale (+ fp16 outlier sidecar for
    int4-mixed) — the exact formula the pallas fused kernel applies
    after its int8 DMA, so backends stay token-identical.

    wq (G, nc_g, cs, R, D) int8; wsc (G, nc_g, cs, R) f32;
    wout same shape as wq or None; cidx (G, kc) -> (G, kc, cs, R, D).
    """
    q = jnp.take_along_axis(wq, cidx[:, :, None, None, None], axis=1)
    sc = jnp.take_along_axis(wsc, cidx[:, :, None, None], axis=1)
    deq = q.astype(jnp.float32) * sc[..., None]
    if wout is not None:
        o = jnp.take_along_axis(wout, cidx[:, :, None, None, None],
                                axis=1)
        deq = deq + o.astype(jnp.float32)
    return deq


def _quant_operands(params, n_hot: int, shape) -> dict:
    """Cold slices of the stored quantized containers, shaped for the
    fused kernel ((G, nc_g, cs, R, D) codes / (G, nc_g, cs, R) scales);
    empty for fp16 plans."""
    if "wq" not in params:
        return {}
    ops = {"wq": params["wq"][n_hot:].reshape(shape),
           "wsc": params["wsc"][n_hot:].reshape(shape[:-1])}
    if "wout" in params:
        ops["wout"] = params["wout"][n_hot:].reshape(shape)
    return ops


def _use_shard_map(groups: int) -> bool:
    from repro.sharding import current_mesh
    m = current_mesh()
    if m is None or "model" not in m.axis_names or groups <= 1:
        return False
    n = dict(m.shape).get("model", 1)
    return n > 1 and groups % n == 0


def _cold_path_shard_map(params, x, activation: str, mode: str,
                         plan: HybridPlan, n_hot: int, n_cold: int,
                         active_mask=None):
    """Shard-local cold path: each 'model' shard scores its own neuron
    slice, picks each local group's top clusters, gathers them locally,
    computes the partial FFN output and psums once per layer.
    x (B, D) -> ((B, D), (G, kc)).

    The mesh 'model' axis (size n) owns G/n whole groups per shard —
    group-granular selection is therefore *exactly* the single-device
    math, shard-decomposed: no cross-shard candidate ever competes in a
    top-k, so 1-, 2-, 4- and 8-way runs pick identical clusters.

    active_mask (B,) bool: rows excluded from the batch-union predictor
    scoring (free KV-arena slots decode garbage lanes; they must not
    steer cluster selection for live requests)."""
    from jax.sharding import PartitionSpec as PS
    from repro.sharding import current_mesh

    mesh = current_mesh()
    G, cs, kc = plan.groups, plan.cluster_size, plan.clusters_per_group
    n_model = dict(mesh.shape)["model"]
    g_loc = G // n_model                              # groups per shard
    nc_g = n_cold // G // cs
    w = params["w"]
    R, D = w.shape[1], w.shape[2]
    act = activation_fn(activation)
    wc = w[n_hot:].reshape(G * nc_g, cs, R, D)        # row-sharded 'model'
    A = params["pred"]["A"]
    Bp = params["pred"]["B"][:, n_hot:]               # (r, Nc) col-sharded
    quant = "wq" in params

    def _local_quant(qops):
        """Shard-local quantized cold containers, kernel-shaped."""
        q = {"wq": qops[0].reshape(g_loc, nc_g, cs, R, D),
             "wsc": qops[1].reshape(g_loc, nc_g, cs, R)}
        if len(qops) == 3:
            q["wout"] = qops[2].reshape(g_loc, nc_g, cs, R, D)
        return q

    def local(xl, wcl, Al, Bl, maskl, *qops):
        # xl (B, D) replicated over model; wcl (g_loc*nc_g, cs, R, D)
        # local clusters; Bl (r, Nc_local) local predictor columns;
        # qops: the shard-local quantized containers when the plan
        # stores int8/int4-mixed bundles.
        if plan.backend == "pallas":
            # the fused kernel IS the shard-local math: selection never
            # crosses groups, so running it over the shard's g_loc
            # groups (same psum / id all_gather) keeps every mesh size
            # token-identical to the jnp backend.
            from repro.kernels import ops as kops
            y, idx = kops.fused_cold_ffn(
                xl, wcl.reshape(g_loc, nc_g, cs, R, D), Al, Bl,
                activation=activation, mode=mode, kc=kc,
                active_mask=maskl,
                **(_local_quant(qops) if quant else {}))
            return (jax.lax.psum(y.astype(jnp.float32), "model"),
                    jax.lax.all_gather(idx, "model").reshape(G, kc))
        with jax.named_scope("predictor"):
            h = jnp.einsum("bd,dr->br", xl.astype(jnp.float32),
                           Al.astype(jnp.float32), precision=SCORE_PRECISION)
            scores = jnp.einsum("br,rn->bn", h, Bl.astype(jnp.float32),
                                precision=SCORE_PRECISION)
            union = jnp.where(maskl[:, None], scores,
                              -jnp.inf).max(axis=0)   # (Nc_local,)
            cscore = union.reshape(g_loc * nc_g, cs).max(axis=-1)
            _, idx = jax.lax.top_k(cscore.reshape(g_loc, nc_g),
                                   kc)                # (g_loc, kc)
        with jax.named_scope("cold_layout"):
            if quant:
                lq = _local_quant(qops)
                gath = _gather_quant(lq["wq"], lq["wsc"], lq.get("wout"),
                                     idx).astype(w.dtype)
            else:
                gath = jnp.take_along_axis(
                    wcl.reshape(g_loc, nc_g, cs, R, D),
                    idx[:, :, None, None, None], axis=1)  # (g_loc,kc,cs,R,D)
            gath = gath.reshape(g_loc * kc * cs, R, D)
        with jax.named_scope("cold_kernel"):
            g = jnp.einsum("bd,kd->bk", xl, gath[:, 0],
                           preferred_element_type=jnp.float32)
            if R == 3:
                u = jnp.einsum("bd,kd->bk", xl, gath[:, 1],
                               preferred_element_type=jnp.float32)
                hh = act(g) * u
            else:
                hh = act(g)
            if mode == "cats":
                tok = scores.reshape(-1, g_loc, nc_g, cs)
                tok = jnp.take_along_axis(tok, idx[None, :, :, None], axis=2)
                hh = hh * (tok.reshape(hh.shape) > 0.0).astype(hh.dtype)
            y = jnp.einsum("bk,kd->bd", hh.astype(w.dtype), gath[:, -1],
                           preferred_element_type=jnp.float32)
        # psum in f32: XLA:CPU's AllReducePromotion pass crashes on
        # bf16 all-reduce inside partial-manual shard_map (and f32
        # reduction is numerically better anyway).
        return (jax.lax.psum(y.astype(jnp.float32), "model"),
                jax.lax.all_gather(idx, "model").reshape(G, kc))

    if active_mask is None:
        active_mask = jnp.ones((x.shape[0],), bool)
    operands = [x, wc, A, Bp, active_mask]
    in_specs = [PS(None, None), PS("model", None, None, None),
                PS(None, None), PS(None, "model"), PS(None)]
    if quant:
        # stored containers shard exactly like the fp cold rows
        operands += [params["wq"][n_hot:].reshape(G * nc_g, cs, R, D),
                     params["wsc"][n_hot:].reshape(G * nc_g, cs, R)]
        in_specs += [PS("model", None, None, None),
                     PS("model", None, None)]
        if "wout" in params:
            operands.append(
                params["wout"][n_hot:].reshape(G * nc_g, cs, R, D))
            in_specs.append(PS("model", None, None, None))
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(PS(None, None), PS(None, None)),
        axis_names={"model"}, check_vma=False)
    return fn(*operands)


def ffn_hybrid(params, x, activation: str, mode: str, plan: HybridPlan,
               return_indices: bool = False, active_mask=None):
    """Decode-phase hybrid FFN (paper §4.1.2). x: (B, D).

    hot prefix  -> dense matmul (MXU; the NPU engine analogue)
    cold suffix -> predictor scores -> batch-union -> per-group top-k
                   clusters -> gathered dense tiles (the CPU engine
                   analogue, re-densified for the MXU).

    active_mask (B,) bool, optional: rows excluded from the batch-union
    selection (the serving engine's free KV-arena slots). Masked rows
    still produce an output but never steer which clusters activate.
    """
    w = params["w"]                                   # (N, R, D)
    N, R, D = w.shape
    B = x.shape[0]
    n_hot, G, kg = plan.n_hot, plan.groups, plan.k_cold
    y = jnp.zeros((B, D), jnp.float32)

    if n_hot > 0:
        with jax.named_scope("ffn_hot"):
            y += _apply_bundle(w[:n_hot], x, activation).astype(jnp.float32)

    n_cold = N - n_hot
    cs = plan.cluster_size
    kc = plan.clusters_per_group                      # active clusters/group
    cidx = jnp.zeros((G, max(kc, 1)), jnp.int32)
    if n_cold > 0 and kc > 0 and "pred" in params and _use_shard_map(G):
        # §Perf iteration C4: the grouped-pjit formulation below lowers
        # to a per-shard materialize-and-select chain (each layer read
        # the full local cold weights several times in f32). shard_map
        # keeps predictor scoring, top-k and the cluster gather strictly
        # shard-local; only the output psum crosses shards.
        y_cold, cidx = _cold_path_shard_map(
            params, x, activation, mode, plan, n_hot, n_cold, active_mask)
        y += y_cold.astype(jnp.float32)
    elif n_cold > 0 and kc > 0 and "pred" in params:
        nc_g = n_cold // G // cs                      # cold clusters per group
        if plan.backend == "pallas":
            # the fused kernel computes scoring, batch-union top-k,
            # gather, FFN and CATS token gating itself — same math as
            # the jnp chain below (selection bit-identical, output
            # within fp tolerance), one pallas_call per layer.
            from repro.kernels import ops as kops
            with jax.named_scope("cold_layout"):
                wc = w[n_hot:].reshape(G, nc_g, cs, R, D)
            with jax.named_scope("predictor"):
                Bp = params["pred"]["B"][:, n_hot:]
            y_cold, cidx = kops.fused_cold_ffn(
                x, wc, params["pred"]["A"], Bp,
                activation=activation, mode=mode, kc=kc,
                active_mask=active_mask,
                **_quant_operands(params, n_hot, (G, nc_g, cs, R, D)))
            y += y_cold.astype(jnp.float32)
            y = constrain(y.astype(x.dtype), P(BATCH, None))
            if return_indices:
                return y, cidx
            return y
        with jax.named_scope("predictor"):
            scores = predict_scores(params["pred"], x)[:, n_hot:]  # (B, Nc)
            # Batch union (paper fn.1: a neuron is active if any token
            # in the batch triggers it), then *cluster*-granular
            # selection — the neuron cluster is the basic unit (§3.1).
            if active_mask is not None:
                union = jnp.where(active_mask[:, None], scores,
                                  -jnp.inf).max(axis=0)         # (Nc,)
            else:
                union = scores.max(axis=0)                      # (Nc,)
            cscore = union.reshape(G, nc_g, cs).max(axis=-1)    # (G, nc_g)
            cscore = constrain(cscore, P("model", None))
            _, cidx = jax.lax.top_k(cscore, kc)                 # (G, kc)
        with jax.named_scope("cold_layout"):
            if "wq" in params:
                # gather the *stored* int8 codes and dequantize right at
                # the gather boundary (cast back to w.dtype so downstream
                # compute matches the in-place roundtrip held by w)
                wq = params["wq"][n_hot:].reshape(G, nc_g, cs, R, D)
                wq = constrain(wq, P("model", None, None, None, None))
                wsc = params["wsc"][n_hot:].reshape(G, nc_g, cs, R)
                wout = params.get("wout")
                if wout is not None:
                    wout = wout[n_hot:].reshape(G, nc_g, cs, R, D)
                gath = _gather_quant(wq, wsc, wout, cidx).astype(w.dtype)
            else:
                wc = w[n_hot:].reshape(G, nc_g, cs, R, D)
                wc = constrain(wc, P("model", None, None, None, None))
                gath = jnp.take_along_axis(
                    wc, cidx[:, :, None, None, None], axis=1)  # (G,kc,cs,R,D)
            gath = gath.reshape(G, kc * cs, R, D)
        with jax.named_scope("cold_kernel"):
            act = activation_fn(activation)
            # fp32 gate/up activations, bf16 operands for the down
            # projection: the fused kernel's numerics, so the backends
            # differ only in accumulation order
            g = jnp.einsum("bd,gkd->bgk", x, gath[:, :, 0],
                           preferred_element_type=jnp.float32)
            if R == 3:
                u = jnp.einsum("bd,gkd->bgk", x, gath[:, :, 1],
                               preferred_element_type=jnp.float32)
                h = act(g) * u
            else:
                h = act(g)
            if mode == "cats":
                # CATS-style (§7.2.5): gate each token's contribution by
                # its own predicted activation for the selected neurons.
                tok = scores.reshape(B, G, nc_g, cs)
                tok = jnp.take_along_axis(
                    tok, cidx[None, :, :, None], axis=2)    # (B,G,kc,cs)
                h = h * (tok.reshape(B, G, kc * cs) > 0.0).astype(h.dtype)
            y_cold = jnp.einsum("bgk,gkd->bd", h.astype(w.dtype),
                                gath[:, :, -1],
                                preferred_element_type=jnp.float32)
            y += y_cold.astype(jnp.float32)

    y = constrain(y.astype(x.dtype), P(BATCH, None))
    if return_indices:
        return y, cidx       # (G, kc) selected cold cluster ids per group
    return y


def ffn_apply(params, x, activation: str, sparse_cfg, plan: HybridPlan | None,
              return_indices: bool = False, active_mask=None):
    """Uniform entry: dense when plan is None (train/prefill) else hybrid."""
    if plan is None or not sparse_cfg.enabled:
        y = ffn_dense(params, x, activation)
        return (y, None) if return_indices else y
    squeeze = x.ndim == 3
    xx = x.reshape(-1, x.shape[-1]) if squeeze else x
    out = ffn_hybrid(params, xx, activation, sparse_cfg.mode, plan,
                     return_indices=return_indices, active_mask=active_mask)
    if return_indices:
        y, cidx = out
        return (y.reshape(x.shape) if squeeze else y), cidx
    return out.reshape(x.shape) if squeeze else out
