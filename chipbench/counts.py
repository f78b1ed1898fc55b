"""Operations and bytes, counted from shapes, and the chip's peaks.

These are the yardstick's arithmetic: model FLOPs per decode token
from a configuration file, the fused cold kernel's operations and HBM
bytes per call from a served plan, and the published peaks of the
device kind JAX reports. Nothing here reads the program.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of `device_kind`; an unknown kind
    is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def _dims(cfg: dict):
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim", D // H)
    return D, H, KV, dh


def attn_flops_token(cfg: dict, ctx: int) -> int:
    """One layer's attention for one token attending over `ctx`
    positions: q/k/v/o projections plus QK^T and PV."""
    D, H, KV, dh = _dims(cfg)
    proj = 2 * D * H * dh + 2 * 2 * D * KV * dh + 2 * H * dh * D
    return proj + 4 * H * dh * ctx


def dense_ffn_flops_token(cfg: dict) -> int:
    """One layer's whole dense gated FFN of `intermediate_size` for
    one token. A hot/cold plan's skipped neurons are not subtracted."""
    return 3 * 2 * cfg["hidden_size"] * cfg["intermediate_size"]


def ffn_flops_token(cfg: dict) -> int:
    """One layer's FFN for one token, as the model defines it past its
    leading dense layers: the whole dense FFN, or router + shared
    experts + top-k routed experts."""
    if not cfg.get("n_routed_experts"):
        return dense_ffn_flops_token(cfg)
    D = cfg["hidden_size"]
    f = cfg["moe_intermediate_size"]
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    ns = cfg.get("n_shared_experts", 0)
    return 2 * D * E + (ns + k) * 3 * 2 * D * f


def model_flops_token(cfg: dict, ctx: int) -> int:
    """Model FLOPs to decode one token whose attention spans `ctx`
    positions (its own included): every layer, then the LM head.
    Layers [0, first_k_dense_replace) of an expert model have a dense
    FFN of `intermediate_size`; the rest, `ffn_flops_token`."""
    L = cfg["num_hidden_layers"]
    lead = min(cfg.get("first_k_dense_replace", 0), L) \
        if cfg.get("n_routed_experts") else 0
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    dense = dense_ffn_flops_token(cfg) if lead else 0
    return (L * attn_flops_token(cfg, ctx) + lead * dense
            + (L - lead) * ffn_flops_token(cfg) + head)


def cold_kernel_call(*, rows: int, d_model: int, rank: int,
                     n_cold: int, cluster_size: int, kc: int,
                     groups: int = 1, bundle_rows: int = 3,
                     itemsize: int = 2) -> dict:
    """Operations and HBM bytes that one fused cold-FFN call needs:
    predictor scoring of every cold neuron (x @ A @ B), and the gated
    FFN over the kc selected clusters of each group. Bytes: x, A, the
    cold predictor columns, the live-row mask, the selected cluster
    bundles, the fp32 output and the selected ids. The CATS score
    columns the kernel picks out with a 0/1 matmul are a slice, not
    work the algorithm needs, so they are not counted."""
    B, D, r = rows, d_model, rank
    ops = (2 * B * D * r + 2 * B * r * n_cold
           + groups * kc * bundle_rows * 2 * B * D * cluster_size)
    nbytes = (B * D * itemsize + D * r * itemsize + r * n_cold * itemsize
              + B * 4 + groups * kc * bundle_rows * cluster_size * D
              * itemsize + B * D * 4 + groups * kc * 4)
    return {"ops": ops, "bytes": nbytes}


def roofline_seconds(ops: float, nbytes: float, peak: dict):
    """(least time the chip could take, the bound that sets it)."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
