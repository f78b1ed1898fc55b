"""Offline execution planner (paper §5).

Pipeline: profile -> classify -> plan.

1. `profile_activations` runs the model over a profiling corpus and
   tracks per-neuron activation frequencies (the paper uses 10M+ tokens
   of Wikipedia/RefinedWeb; our corpus is the synthetic data pipeline).
2. `classify_neurons` sorts neurons by frequency into a hot-first
   permutation and sizes the hot prefix per batch-size bucket:
   the batch-b activation probability of a neuron with per-token
   frequency f is 1-(1-f)^b (the Fig 2 union effect), and the hot set
   is additionally capped by I/O-aware sizing — hot neurons are
   prefetched during the previous attention block, so
   n_hot <= seq_bw * t_attn / bytes_per_neuron (§5 "carefully balances").
3. `build_plan` emits an ExecutionPlan: the permutation, per-bucket
   HybridPlans, and the hardware profile used.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, asdict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.clusters import HybridPlan, make_plan, round_down
from repro.models.modules import rms_norm, activation_fn


@dataclass(frozen=True)
class HardwareProfile:
    """Target-device characteristics consumed by the planner."""
    name: str = "tpu-v5e-host"
    seq_bw: float = 4e9            # bytes/s sequential (slow-tier read)
    rand_bw: float = 1e9           # bytes/s random
    attn_time_s: float = 2e-3      # per-layer attention time (prefetch window)
    dense_engine_flops: float = 197e12   # MXU ("NPU analogue")
    sparse_engine_flops: float = 20e12   # gathered path effective


# The paper's device (OnePlus 12, Snapdragon 8 Gen 3 + UFS 4.0).
# NPU ~11 TFLOP/s effective (§2.3.1: 770 tok/s prefill on a 7B ~ 2*7G*770);
# 6 CPU cores ~60 GFLOP/s fp16 NEON (12 tok/s in-memory decode on ~3B
# active params). Used by benchmarks that reproduce the paper's figures.
PHONE = HardwareProfile(
    name="snapdragon-8gen3",
    seq_bw=4e9, rand_bw=1e9, attn_time_s=2e-3,
    dense_engine_flops=11e12, sparse_engine_flops=60e9)


@dataclass
class ExecutionPlan:
    arch: str
    n_neurons: int
    cluster_size: int
    # hot-first neuron permutation per layer, (L, N) int32
    neuron_order: np.ndarray
    # per-token activation frequency per layer, (L, N) float32 (permuted)
    frequencies: np.ndarray
    # batch-bucket -> HybridPlan
    plans: dict
    hardware: HardwareProfile

    def plan_for_batch(self, batch: int) -> HybridPlan:
        buckets = sorted(self.plans)
        for b in buckets:
            if batch <= b:
                return self.plans[b]
        return self.plans[buckets[-1]]

    def save(self, path):
        obj = {
            "arch": self.arch, "n_neurons": self.n_neurons,
            "cluster_size": self.cluster_size,
            "neuron_order": self.neuron_order.tolist(),
            "frequencies": self.frequencies.tolist(),
            "plans": {str(b): asdict(p) for b, p in self.plans.items()},
            "hardware": asdict(self.hardware),
        }
        with open(path, "w") as f:
            json.dump(obj, f)

    @staticmethod
    def load(path) -> "ExecutionPlan":
        with open(path) as f:
            obj = json.load(f)
        return ExecutionPlan(
            arch=obj["arch"], n_neurons=obj["n_neurons"],
            cluster_size=obj["cluster_size"],
            neuron_order=np.asarray(obj["neuron_order"], np.int32),
            frequencies=np.asarray(obj["frequencies"], np.float32),
            plans={int(b): HybridPlan(**p) for b, p in obj["plans"].items()},
            hardware=HardwareProfile(**obj["hardware"]),
        )


# ------------------------------------------------------------ profiling ----

def _act_threshold(mode: str) -> float:
    # relu-family: exact zeros; cats: |h| below tau contributes ~nothing
    return 0.0 if mode == "relu" else 0.1


def ffn_activation_counts(ffn_params, x, activation: str, mode: str):
    """x (B,S,D) -> per-neuron activation counts (N,) over B*S tokens."""
    w = ffn_params["w"]
    act = activation_fn(activation)
    g = jnp.einsum("bsd,nd->bsn", x, w[:, 0])
    h = act(g)
    if w.shape[1] == 3:
        u = jnp.einsum("bsd,nd->bsn", x, w[:, 1])
        h = h * u
    tau = _act_threshold(mode)
    active = jnp.abs(h) > tau
    return active.sum(axis=(0, 1)).astype(jnp.int32)


def profile_activations(params, cfg: ModelConfig, token_batches):
    """Dense-family profiling forward: returns (counts (L,N), n_tokens).

    Re-implements the dense layer walk with an activation tap; works for
    any model whose layers are {ln1, attn, ln2, ffn} stacks (dense, vlm
    backbone). Other families use family-specific adapters or the
    synthetic profile (see `synthetic_frequencies`).
    """
    from repro.models import blocks as B
    from repro.models import dense as D
    from repro.models.attention import rope_angles

    @jax.jit
    def run(params, tokens):
        x = D.embed_tokens(params, cfg, tokens)
        S = x.shape[1]
        angles = rope_angles(jnp.arange(S), cfg.d_head // 2, cfg.rope_theta)

        def body(h, lp):
            a, _ = B.attn_full(lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps),
                               cfg, angles, causal=True,
                               window=cfg.sliding_window)
            h = h + a
            xin = rms_norm(h, lp["ln2"], cfg.norm_eps)
            cnt = ffn_activation_counts(lp["ffn"], xin, cfg.activation,
                                        cfg.sparse_ffn.mode)
            from repro.core.sparse_ffn import ffn_dense
            h = h + ffn_dense(lp["ffn"], xin, cfg.activation)
            return h, cnt

        _, counts = jax.lax.scan(body, x, params["layers"])
        return counts                                   # (L, N)

    total = np.zeros((cfg.num_layers, cfg.d_ff), np.int64)
    n_tokens = 0
    for tokens in token_batches:
        total += np.asarray(run(params, tokens))
        n_tokens += tokens.shape[0] * tokens.shape[1]
    return total, n_tokens


def profile_ffn_inputs(params, cfg: ModelConfig, token_batches):
    """Collect per-layer FFN inputs and activation indicators.

    Returns (X (L, T, D), H (L, T, N) bool) over all profiling tokens —
    the training set for predictor calibration (PowerInfer trains its
    online predictors offline; §3.2)."""
    from repro.models import blocks as B
    from repro.models import dense as D
    from repro.models.attention import rope_angles
    from repro.core.sparse_ffn import ffn_dense
    from repro.models.modules import activation_fn

    tau = _act_threshold(cfg.sparse_ffn.mode)

    @jax.jit
    def run(params, tokens):
        x = D.embed_tokens(params, cfg, tokens)
        S = x.shape[1]
        angles = rope_angles(jnp.arange(S), cfg.d_head // 2, cfg.rope_theta)

        def body(h, lp):
            a, _ = B.attn_full(lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps),
                               cfg, angles, causal=True,
                               window=cfg.sliding_window)
            h = h + a
            xin = rms_norm(h, lp["ln2"], cfg.norm_eps)
            w = lp["ffn"]["w"]
            act = activation_fn(cfg.activation)
            g = jnp.einsum("bsd,nd->bsn", xin, w[:, 0])
            hh = act(g)
            if w.shape[1] == 3:
                hh = hh * jnp.einsum("bsd,nd->bsn", xin, w[:, 1])
            active = jnp.abs(hh) > tau
            h = h + ffn_dense(lp["ffn"], xin, cfg.activation)
            return h, (xin, active)

        _, (xs, acts) = jax.lax.scan(body, x, params["layers"])
        return xs, acts                            # (L,B,S,D), (L,B,S,N)

    Xs, Hs = [], []
    for tokens in token_batches:
        xs, acts = run(params, tokens)
        L = xs.shape[0]
        Xs.append(np.asarray(xs).reshape(L, -1, cfg.d_model))
        Hs.append(np.asarray(acts).reshape(L, -1, cfg.d_ff))
    return np.concatenate(Xs, 1), np.concatenate(Hs, 1)


def calibrate_predictor(params, cfg: ModelConfig, token_batches,
                        ridge: float = 1e-2):
    """Fit each layer's low-rank activation predictor by ridge
    regression on real (FFN input, activation indicator) pairs, then
    truncate to rank r via SVD. Returns params with trained predictors.
    """
    rank = cfg.sparse_ffn.predictor_rank
    X, H = profile_ffn_inputs(params, cfg, token_batches)
    L, T, Dm = X.shape
    A_l, B_l = [], []
    for l in range(L):
        Xl = X[l].astype(np.float64)
        Yl = (H[l].astype(np.float64) * 2.0 - 1.0)     # ±1 targets
        G = Xl.T @ Xl + ridge * T * np.eye(Dm)
        W = np.linalg.solve(G, Xl.T @ Yl)              # (D, N)
        U, S, Vt = np.linalg.svd(W, full_matrices=False)
        r = min(rank, len(S))
        A_l.append((U[:, :r] * np.sqrt(S[:r])))
        B_l.append((np.sqrt(S[:r])[:, None] * Vt[:r]))
    ffn = params["layers"]["ffn"]
    dtype = ffn["pred"]["A"].dtype
    pad_r = ffn["pred"]["A"].shape[-1]

    def pad(mats, axis):
        out = []
        for m in mats:
            if m.shape[axis] < pad_r:
                w = [(0, 0), (0, 0)]
                w[axis] = (0, pad_r - m.shape[axis])
                m = np.pad(m, w)
            out.append(m)
        return np.stack(out)

    new_pred = {"A": jnp.asarray(pad(A_l, 1), dtype),
                "B": jnp.asarray(pad(B_l, 0), dtype)}
    new_ffn = dict(ffn, pred=new_pred)
    return dict(params, layers=dict(params["layers"], ffn=new_ffn))


def predictor_quality(params, cfg: ModelConfig, token_batches) -> float:
    """Recall of the predictor's top-k vs true active neurons (layer 0)."""
    from repro.core.predictor import predict_scores
    X, H = profile_ffn_inputs(params, cfg, token_batches)
    pred = jax.tree.map(lambda a: a[0], params["layers"]["ffn"]["pred"])
    scores = np.asarray(predict_scores(pred, jnp.asarray(X[0])))
    recalls = []
    for t in range(min(64, X.shape[1])):
        k = max(int(H[0, t].sum()), 1)
        top = np.argsort(-scores[t])[:k]
        recalls.append(H[0, t][top].mean())
    return float(np.mean(recalls))


def synthetic_frequencies(cfg: ModelConfig, seed: int = 0,
                          zipf_a: float = 1.2) -> np.ndarray:
    """Zipf-shaped activation frequencies for families without a
    profiling adapter (the paper's Fig 2 skew: <1% of neurons are hot
    at batch 1, hot spots dominate)."""
    rng = np.random.default_rng(seed)
    L, N = cfg.num_layers, max(cfg.d_ff, 1)
    rank = np.arange(1, N + 1, dtype=np.float64)
    base = 1.0 / rank ** zipf_a
    base = base / base.max() * 0.95
    freqs = np.stack([rng.permutation(base) for _ in range(L)])
    return freqs.astype(np.float32)


# --------------------------------------------------------- classification ----

def classify_neurons(freqs: np.ndarray, cfg: ModelConfig,
                     hw: HardwareProfile,
                     batch_buckets=(1, 2, 4, 8, 16, 32),
                     groups: int = 1, backend: str = "jnp",
                     storage_dtype: str = "fp16"):
    """freqs (L, N) per-token activation frequency -> (order, plans).

    Hot threshold: union activation probability at the bucket's batch
    size exceeds 0.5. I/O cap: the hot prefix must be prefetchable
    within one attention block at sequential bandwidth — priced at the
    declared storage dtype, so int4 bundles shift the hot/cold boundary
    outward (more neurons fit the same prefetch window, §7.6).
    """
    L, N = freqs.shape
    order = np.argsort(-freqs, axis=1).astype(np.int32)     # hot-first
    sorted_f = np.take_along_axis(freqs, order, axis=1)
    mean_f = sorted_f.mean(axis=0)                          # (N,) layer-avg

    sc = cfg.sparse_ffn
    io_cap = hot_io_cap(cfg, hw, storage_dtype)

    plans = {}
    for b in batch_buckets:
        union = 1.0 - (1.0 - mean_f) ** b
        n_hot = int((union > 0.5).sum())
        n_hot = min(n_hot, io_cap, N)
        hot_ratio = n_hot / N
        # cold budget: expected active cold fraction at this batch size
        cold_union = union[n_hot:] if n_hot < N else np.array([0.0])
        cold_ratio = float(np.clip(cold_union.mean() * 2.0, 0.02, 1.0))
        plans[b] = make_plan(N, hot_ratio, cold_ratio, sc.cluster_size,
                             groups=groups, backend=backend,
                             storage_dtype=storage_dtype)
    return order, np.ascontiguousarray(sorted_f), plans


def _bundle_bytes(cfg: ModelConfig, storage_dtype: str = "fp16") -> int:
    from repro.core.sparse_ffn import ffn_rows
    from repro.quant.quantize import bundle_nbytes
    R = ffn_rows(cfg.activation)
    itemsize = 2 if cfg.param_dtype == "bfloat16" else 4
    return bundle_nbytes(cfg.d_model, storage_dtype, rows=R,
                         itemsize=itemsize)


def hot_io_cap(cfg: ModelConfig, hw: HardwareProfile,
               storage_dtype: str = "fp16") -> int:
    """I/O-aware hot-prefix cap (§5 "carefully balances"): the pinned
    hot region must be prefetchable within one attention block at
    sequential bandwidth. Shared by the dense classifier and the
    two-level MoE plan (there the cap bounds the *total* pinned
    prefix: shared experts + every routed expert's hot rows).
    The prefetch stream is priced at `storage_dtype` bundle bytes —
    int4-mixed bundles are 3x smaller at deployment d_model, so the
    same attention window prefetches ~3x more neurons."""
    return int(hw.seq_bw * hw.attn_time_s
               / max(_bundle_bytes(cfg, storage_dtype), 1))


# ------------------------------------------------------------- assembly ----

def permute_ffn_params(params, order: np.ndarray):
    """Reorder each layer's FFN bundle rows (and predictor columns)
    hot-first, matching the plan. params['layers']['ffn'] leaves are
    stacked (L, ...). The gather runs where the weights live."""
    layers = params["layers"]
    ffn = layers["ffn"]
    idx = jnp.asarray(order)                                # (L, N)
    w = jnp.take_along_axis(ffn["w"], idx[:, :, None, None],
                            axis=1)                         # (L, N, R, D)
    new_ffn = dict(ffn, w=w)
    if "pred" in ffn:
        Bm = jnp.take_along_axis(ffn["pred"]["B"], idx[:, None, :],
                                 axis=2)                    # (L, r, N)
        new_ffn["pred"] = dict(ffn["pred"], B=Bm)
    new_layers = dict(layers, ffn=new_ffn)
    return dict(params, layers=new_layers)


def build_plan(cfg: ModelConfig, freqs: np.ndarray = None,
               hw: HardwareProfile = None, groups: int = 1,
               backend: str = "jnp",
               storage_dtype: str = "fp16") -> ExecutionPlan:
    hw = hw or HardwareProfile()
    if freqs is None:
        freqs = synthetic_frequencies(cfg)
    order, sorted_f, plans = classify_neurons(freqs, cfg, hw,
                                              groups=groups, backend=backend,
                                              storage_dtype=storage_dtype)
    return ExecutionPlan(
        arch=cfg.name, n_neurons=freqs.shape[1],
        cluster_size=cfg.sparse_ffn.cluster_size,
        neuron_order=order, frequencies=sorted_f, plans=plans, hardware=hw)


def moe_synthetic_frequencies(cfg: ModelConfig, seed: int = 0,
                              zipf_a: float = 1.2) -> np.ndarray:
    """Within-expert per-token activation frequencies (L, E*f),
    *conditional on the expert being routed* — the MoE analogue of
    `synthetic_frequencies`, used when no profiled frequencies are
    supplied to the two-level `build_moe_plan`.

    Shape: a hot band of ~1.5*hot_ratio*f neurons whose frequency
    ramps 0.95 -> 0.3 (so the >0.5 union threshold lands near the
    config's declared per-expert hot share at batch 1 and the hot
    prefix *grows* with the per-expert batch, Fig 2), then a zipf
    cold tail."""
    rng = np.random.default_rng(seed)
    L, E, f = cfg.num_layers, cfg.num_experts, max(cfg.d_ff, 1)
    band = int(np.clip(round(1.5 * cfg.sparse_ffn.hot_ratio * f), 1, f))
    hot = np.linspace(0.95, 0.3, band)
    rank = np.arange(1, f - band + 1, dtype=np.float64)
    tail = 0.25 / rank ** zipf_a
    base = np.concatenate([hot, tail])
    freqs = np.stack([np.concatenate([rng.permutation(base)
                                      for _ in range(E)])
                      for _ in range(L)])
    return freqs.astype(np.float32)


def permute_moe_params(params, order: np.ndarray):
    """Per-expert hot-first reorder of the stacked expert bundles
    (L, E, f, R, D) — the MoE half of `permute_ffn_params`. Only the
    routed experts' rows move (the router is per-expert, the shared
    experts keep the identity prefix the flat order assigns them), so
    MoE layer outputs are unchanged up to fp reassociation."""
    layers = params["layers"]
    moe = layers["moe"]
    ex = np.asarray(moe["experts"])                         # (L, E, f, R, D)
    L, E, f = ex.shape[:3]
    S = order.shape[1] - E * f
    ro = (order[:, S:].reshape(L, E, f) - S
          - (np.arange(E, dtype=np.int32) * f)[None, :, None])
    ex = np.take_along_axis(ex, ro[..., None, None], axis=2)
    new_moe = dict(moe, experts=jnp.asarray(ex))
    return dict(params, layers=dict(layers, moe=new_moe))


def build_moe_plan(cfg: ModelConfig, freqs: np.ndarray = None,
                   hw: HardwareProfile = None,
                   batch_buckets=(1, 2, 4, 8, 16, 32),
                   storage_dtype: str = "fp16") -> ExecutionPlan:
    """Execution plan for the MoE family.

    Whole-expert mode (DESIGN.md §8, `cfg.moe_intra_expert=False`):
    the flat serving neuron space is [shared experts | routed experts]
    with one cluster per routed expert (cluster_size = d_ff), so the
    storage plane prices expert residency exactly like dense
    cold-cluster residency. Per batch bucket, the cold budget is the
    *expected batch union* of routed experts — 1-(1-k/E)^b per expert,
    the Fig 2 union effect at expert granularity — clamped to [k, E]
    experts. No neuron permutation is needed: the architecture already
    makes the clusters explicit, so `neuron_order` is the identity.

    Two-level mode (DESIGN.md §9, the paper's TurboSparse-Mixtral
    case): expert gating *composes with* intra-expert hot/cold
    clusters. The flat space keeps each routed expert contiguous but
    permutes its d_ff rows hot-first (`freqs` (L, E*f) within-expert
    activation frequencies; synthetic zipf when None). Per bucket, the
    expert union above picks n_act experts; the per-expert hot prefix
    is then sized by the same Fig-2 union math `classify_neurons`
    applies — at the per-active-expert token count b_e = ceil(b*k /
    n_act) — and capped by the shared `hot_io_cap` budget (the total
    pinned prefix, shared + E hot prefixes, must prefetch within one
    attention block). The plan prices hot compute per *activated*
    expert (n_hot = S + n_act*n_hot_e) while pinning every expert's
    hot prefix (n_pinned = S + E*n_hot_e)."""
    hw = hw or HardwareProfile()
    f, E, k = cfg.d_ff, cfg.num_experts, cfg.experts_per_token
    if not E or not k:
        raise ValueError(f"{cfg.name} is not a MoE config "
                         f"(num_experts={E}, experts_per_token={k})")
    S = cfg.num_shared_experts * f
    N = cfg.moe_flat_neurons
    L = cfg.num_layers

    def expert_union(b):
        union = 1.0 - (1.0 - k / E) ** b
        return min(max(int(round(E * union)), min(k, E)), E)

    if not cfg.moe_intra_expert:
        plans = {b: HybridPlan(n_hot=S, k_cold=expert_union(b) * f,
                               groups=1, cluster_size=f,
                               storage_dtype=storage_dtype)
                 for b in batch_buckets}
        # shared experts always fire; each routed expert at rate ~k/E
        fr = np.concatenate([np.ones((S,), np.float32),
                             np.full((E * f,), k / E, np.float32)])
        fr = np.tile(fr, (L, 1))
        order = np.tile(np.arange(N, dtype=np.int32), (L, 1))
        return ExecutionPlan(
            arch=cfg.name, n_neurons=N, cluster_size=f,
            neuron_order=order, frequencies=fr, plans=plans, hardware=hw)

    # ---- two-level: expert union x intra-expert hot/cold ----
    cs = cfg.sparse_ffn.cluster_size
    if f % cs:
        raise ValueError(
            f"{cfg.name}: d_ff={f} must be a multiple of the "
            f"intra-expert cluster size {cs}")
    if freqs is None:
        freqs = moe_synthetic_frequencies(cfg)
    freqs = np.asarray(freqs, np.float32)
    if freqs.shape != (L, E * f):
        raise ValueError(
            f"two-level MoE frequencies must be (L, E*f) = "
            f"({L}, {E * f}); got {freqs.shape}")
    per_exp = freqs.reshape(L, E, f)
    order_e = np.argsort(-per_exp, axis=2).astype(np.int32)  # hot-first
    sorted_f = np.take_along_axis(per_exp, order_e, axis=2)
    mean_f = sorted_f.mean(axis=(0, 1))         # (f,) layer+expert profile
    cap_e = max((hot_io_cap(cfg, hw, storage_dtype) - S) // E, 0)

    plans = {}
    for b in batch_buckets:
        n_act = expert_union(b)
        b_e = max(int(np.ceil(b * k / n_act)), 1)  # tokens/active expert
        union = 1.0 - (1.0 - mean_f) ** b_e
        n_hot_e = int((union > 0.5).sum())
        n_hot_e = max(min(round_down(n_hot_e, cs),
                          round_down(cap_e, cs), f - cs), 0)
        cold_union = union[n_hot_e:]
        cold_ratio = float(np.clip(cold_union.mean() * 2.0, 0.02, 1.0))
        k_cold_e = max(round_down(int((f - n_hot_e) * cold_ratio), cs), cs)
        plans[b] = HybridPlan(
            n_hot=S + n_act * n_hot_e, k_cold=n_act * k_cold_e,
            groups=1, cluster_size=cs,
            n_expert_hot=n_hot_e, n_pinned=S + E * n_hot_e,
            storage_dtype=storage_dtype)

    # flat order: identity shared prefix, then each expert's rows
    # hot-first within its contiguous block (prepare_params applies
    # this with permute_moe_params, so flat id == physical row)
    routed = (order_e + (np.arange(E, dtype=np.int32) * f)[None, :, None]
              + S).reshape(L, E * f)
    shared = np.tile(np.arange(S, dtype=np.int32), (L, 1))
    order = np.concatenate([shared, routed], axis=1).astype(np.int32)
    fr = np.concatenate([np.ones((L, S), np.float32),
                         sorted_f.reshape(L, E * f)], axis=1)
    return ExecutionPlan(
        arch=cfg.name, n_neurons=N, cluster_size=cs,
        neuron_order=order, frequencies=fr, plans=plans, hardware=hw)
