"""Pallas TPU kernels: gathered neuron-cluster FFN (the paper's cold path).

The TPU-native form of PowerInfer-2's neuron-cluster pipeline (§4.3),
in two tiers:

* `cluster_gather_ffn` — gather-only: a scalar-prefetched index vector
  drives each BlockSpec's index_map, so the Pallas pipeline DMA-streams
  exactly the activated clusters from HBM ("flash" analogue) into VMEM
  ("DRAM" analogue) while the MXU computes the previous cluster.
  Selection (predictor score -> top-k) still happens outside, in XLA.

* `fused_cold_ffn` — the whole cold path in ONE pallas_call: predictor
  scoring, batch-union top-k cluster selection, cluster gather and the
  gated FFN GEMMs. Selection has to live *inside* the kernel here, so
  the automatic scalar-prefetch pipeline can't drive the gather;
  instead the kernel keeps the selected ids in SMEM and issues its own
  double-buffered `make_async_copy` fetches from HBM-resident weights —
  the DMA for cluster c+1 is started before the MXU computes cluster c
  (wait -> compute -> already-running copy), which is exactly Fig 6(b)
  one level down the memory hierarchy and the kernel analogue of the
  storage plane's PrefetchExecutor. The grid walks neuron groups, so
  under shard_map each 'model' shard runs the same kernel over its
  local groups.

Weight layout matches the cold store: bundled (N, R, D) with R rows per
neuron (Gate/Up/Down) so one block fetch brings a whole cluster bundle
(§4.4 position-major bundling).

Blocks: w block (cluster_size, R, D) — cluster_size is a multiple of
128 in production configs, so the (B, D) x (D, cs) matmuls are
MXU-aligned. Output (B, D) accumulates in fp32 across grid steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.predictor import SCORE_PRECISION
from repro.kernels import default_interpret
from repro.models.modules import activation_fn


def _kernel(idx_ref, x_ref, w_ref, o_ref, *, activation: str, gated: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                   # (B, D)
    wg = w_ref[:, 0, :]                              # (cs, D)
    g = jax.lax.dot_general(x, wg, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (B, cs)
    if activation == "silu":
        h = jax.nn.silu(g)
    elif activation == "relu2":
        h = jnp.square(jnp.maximum(g, 0.0))
    else:                                            # gelu / geglu
        h = jax.nn.gelu(g, approximate=True)
    if gated:
        u = jax.lax.dot_general(x, w_ref[:, 1, :], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = h * u
    wd = w_ref[:, -1, :]                             # (cs, D)
    y = jax.lax.dot_general(h.astype(wd.dtype), wd, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (B, D)
    o_ref[...] += y


@functools.partial(jax.jit, static_argnames=("activation", "cluster_size",
                                             "interpret"))
def cluster_gather_ffn(x, w, cluster_idx, *, activation: str,
                       cluster_size: int,
                       interpret: bool | None = None):
    """x (B, D); w (N, R, D) in HBM; cluster_idx (K,) int32 cluster ids.

    Returns (B, D) = sum over selected clusters of the bundled FFN.
    """
    if interpret is None:
        interpret = default_interpret()
    B, D = x.shape
    N, R, _ = w.shape
    K = cluster_idx.shape[0]
    assert N % cluster_size == 0
    gated = R == 3

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((B, D), lambda i, idx: (0, 0)),
            # the gather: block row = the i-th *active* cluster id
            pl.BlockSpec((cluster_size, R, D),
                         lambda i, idx: (idx[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((B, D), lambda i, idx: (0, 0)),
    )
    w_blocked = w.reshape(N // cluster_size * cluster_size, R, D)
    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation, gated=gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(cluster_idx, x, w_blocked)
    return out.astype(x.dtype)


# --------------------------------------------------- fused cold path ----

# Masked rows must lose every batch-union max without poisoning the
# degenerate all-masked case: with -inf the iterative argmax below would
# keep re-selecting index 0, while jax.lax.top_k over an all--inf
# vector yields the distinct ids [0, 1, ...]. finfo.min sits below any
# finite score yet above the -inf a selected entry is knocked down to,
# so both paths pick identical ids in every case.
_NEG = float(jnp.finfo(jnp.float32).min)


def _kernel_layout(w, dtype=None):
    """(N, R, D) bundles -> the fused kernel's HBM layout (R, N, Dp).

    The chip's DMA engine moves whole (sublane, lane) tiles, so a
    cluster slice must cover full tiles in the two minor dimensions:
    an R=3 second-minor and a D that is not a multiple of 128 both
    fall inside a tile. Matrix-major rows with D padded to the lane
    width make one cluster a (R, cs, Dp) slab of whole tiles. The pad
    columns are zero and are never read back (the kernel loads [:D]).
    """
    N, R, D = w.shape
    Dp = -(-D // 128) * 128
    wk = jnp.swapaxes(w, 0, 1)
    if dtype is not None:
        wk = wk.astype(dtype)
    if Dp != D:
        wk = jnp.pad(wk, ((0, 0), (0, 0), (0, Dp - D)))
    return wk


def _fused_kernel(*refs, activation: str, gated: bool, cats: bool,
                  kc: int, nc_g: int, cs: int, quant: bool, mixed: bool):
    """One grid step = one neuron group: score -> top-k -> gathered FFN.

    x_ref (B, D) VMEM; w_hbm (R, G*nc_g*cs, Dp) stays in HBM (ANY) —
    clusters are pulled in by explicit double-buffered DMA; a_ref
    (D, r) / b_ref (r, nc_g*cs) the predictor slice for this group;
    mask_ref (B, 1) live-row mask; y_ref (B, D) fp32 accumulator over
    groups; idx_ref (G, kc) SMEM selected-cluster output.

    Quantized storage (§7.6, plan.storage_dtype != 'fp16'): w_hbm
    holds the *stored* int8 codes — the cluster DMA moves int8 (3-4x
    fewer HBM bytes per bundle) and dequantize happens in VMEM right
    before the gated FFN dots: codes * per-row scale (wsc_ref, this
    group's (nc_g*cs, R) block) plus, for int4-mixed, the outlier
    sidecar (wout_hbm, fp32, double-buffered alongside the codes). The
    formula matches sparse_ffn._gather_quant exactly, so jnp and
    pallas decode stay token-identical.
    """
    if quant and mixed:
        (x_ref, w_hbm, a_ref, b_ref, mask_ref, wsc_ref, wout_hbm,
         y_ref, idx_ref) = refs
    elif quant:
        (x_ref, w_hbm, a_ref, b_ref, mask_ref, wsc_ref,
         y_ref, idx_ref) = refs
        wout_hbm = None
    else:
        x_ref, w_hbm, a_ref, b_ref, mask_ref, y_ref, idx_ref = refs
        wsc_ref = wout_hbm = None
    g = pl.program_id(0)
    R = w_hbm.shape[0]
    D = x_ref.shape[1]
    n_cols = nc_g * cs

    @pl.when(g == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(buf, sem, obuf=None, osem=None):
        x = x_ref[...]                                    # (B, D)
        # -- predictor scoring (fp32, matching core.predictor) --
        h = jax.lax.dot_general(
            x.astype(jnp.float32), a_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32)
        scores = jax.lax.dot_general(
            h, b_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32)           # (B, nc_g*cs)
        # -- batch-union scores (paper fn.1 + §3.1), kept 2-D --
        union = jnp.where(mask_ref[...] > 0.0, scores,
                          _NEG).max(axis=0, keepdims=True)  # (1, n_cols)
        col = jax.lax.broadcasted_iota(jnp.int32, union.shape, 1)

        # -- top-k over cluster maxima without a per-cluster reshape:
        #    the cluster holding the first element equal to the global
        #    max is the lowest-indexed cluster with the highest cluster
        #    max, i.e. exactly jax.lax.top_k's pick (ties -> lowest
        #    index); knocking that cluster's column window down to -inf
        #    exposes the next one. --
        for k in range(kc):
            top = jnp.max(union)
            first = jnp.min(jnp.where(union == top, col, n_cols))
            c = first // cs
            idx_ref[g, k] = c
            lo = c * cs
            union = jnp.where((col >= lo) & (col < lo + cs), -jnp.inf,
                              union)

        # -- double-buffered gather + gated FFN --
        def code_dma(slot, k):
            c = idx_ref[g, k]
            row = pl.multiple_of((g * nc_g + c) * cs, cs)
            return pltpu.make_async_copy(
                w_hbm.at[:, pl.ds(row, cs)], buf.at[slot], sem.at[slot])

        def sidecar_dma(slot, k):
            # the outlier sidecar rides its own DMA pair so the int8
            # code fetch stays a single burst
            c = idx_ref[g, k]
            row = pl.multiple_of((g * nc_g + c) * cs, cs)
            return pltpu.make_async_copy(
                wout_hbm.at[:, pl.ds(row, cs)], obuf.at[slot],
                osem.at[slot])

        def dma_start(slot, k):
            code_dma(slot, k).start()
            if mixed:
                sidecar_dma(slot, k).start()

        def dma_wait(slot, k):
            code_dma(slot, k).wait()
            if mixed:
                sidecar_dma(slot, k).wait()

        dma_start(0, 0)                                   # warm-up fetch
        act = activation_fn(activation)
        if cats:
            ncol = jax.lax.broadcasted_iota(jnp.int32, (n_cols, cs), 0)
            jcol = jax.lax.broadcasted_iota(jnp.int32, (n_cols, cs), 1)

        def compute(k, _):
            slot = jax.lax.rem(k, 2)

            @pl.when(k + 1 < kc)
            def _prefetch():                              # overlap: c+1 DMA
                dma_start(jax.lax.rem(k + 1, 2), k + 1)

            dma_wait(slot, k)
            c = idx_ref[g, k]
            if quant:
                sc = wsc_ref[pl.ds(pl.multiple_of(c * cs, cs), cs), :]

            def matrix(r):
                # one (cs, D) matrix of the cluster bundle; quantized
                # storage dequantizes in VMEM, before the FFN dots:
                # stored int8 codes * per-row scale (+ outliers)
                wr = buf[slot, r, :, :D]
                if not quant:
                    return wr
                wr = wr.astype(jnp.float32) * sc[:, r:r + 1]
                if mixed:
                    wr = wr + obuf[slot, r, :, :D]
                return wr.astype(x.dtype)

            gg = jax.lax.dot_general(
                x, matrix(0), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (B, cs)
            hh = act(gg)
            if gated:
                u = jax.lax.dot_general(
                    x, matrix(1), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                hh = hh * u
            if cats:
                # CATS token gating: each token keeps only neurons its
                # OWN predicted activation marks positive (§7.2.5) —
                # the batch union steers selection, not computation.
                # The cluster's score columns come out of an exact 0/1
                # selection matmul instead of a dynamic lane slice.
                pick = (ncol == c * cs + jcol).astype(jnp.float32)
                tok = jax.lax.dot_general(
                    scores, pick, (((1,), (0,)), ((), ())),
                    precision=SCORE_PRECISION,
                    preferred_element_type=jnp.float32)   # (B, cs)
                hh = hh * (tok > 0.0).astype(hh.dtype)
            wd = matrix(R - 1)
            y_ref[...] += jax.lax.dot_general(
                hh.astype(wd.dtype), wd, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(0, kc, compute, 0)

    slab = (2,) + w_hbm.shape[:1] + (cs,) + w_hbm.shape[2:]
    if mixed:
        pl.run_scoped(
            body,
            buf=pltpu.VMEM(slab, w_hbm.dtype),
            sem=pltpu.SemaphoreType.DMA((2,)),
            obuf=pltpu.VMEM(slab, wout_hbm.dtype),
            osem=pltpu.SemaphoreType.DMA((2,)))
    else:
        pl.run_scoped(
            body,
            buf=pltpu.VMEM(slab, w_hbm.dtype),
            sem=pltpu.SemaphoreType.DMA((2,)))


@functools.partial(jax.jit, static_argnames=(
    "activation", "cluster_size", "groups", "kc", "cats", "interpret"))
def fused_cold_ffn(x, w, A, Bp, mask, *, activation: str, cluster_size: int,
                   groups: int, kc: int, cats: bool = False,
                   interpret: bool | None = None, wsc=None, wout=None):
    """Fused cold path: score -> top-k -> gather -> FFN in one pallas_call.

    x (B, D); w (G*nc_g*cs, R, D) group-major cold bundles (HBM-resident
    — never staged through the block pipeline; handed to the kernel in
    `_kernel_layout`); A (D, r) / Bp (r, G*nc_g*cs) the cold predictor
    slice; mask (B, 1) float live-row mask (1.0 = row steers the batch
    union).

    Quantized storage: pass the int8 codes as `w` plus `wsc`
    (G*nc_g*cs, R) fp32 per-row scales (staged per group through the
    block pipeline) and, for int4-mixed, `wout` (G*nc_g*cs, R, D) fp16
    outlier sidecar (HBM-resident, DMA'd alongside the codes as fp32,
    which holds every fp16 exactly). The cluster DMA then moves int8
    and the kernel dequantizes in VMEM before the FFN dots.

    Returns (y (B, D) fp32, idx (groups, kc) int32) — bitwise the same
    selection as the jnp path's jax.lax.top_k chain.
    """
    if interpret is None:
        interpret = default_interpret()
    B, D = x.shape
    Ntot, R, _ = w.shape
    assert Ntot % (groups * cluster_size) == 0
    nc_g = Ntot // (groups * cluster_size)
    assert 1 <= kc <= nc_g
    r = A.shape[1]
    quant = wsc is not None
    mixed = wout is not None
    in_specs = [
        pl.BlockSpec((B, D), lambda g: (0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),           # weights stay HBM
        pl.BlockSpec((D, r), lambda g: (0, 0)),
        pl.BlockSpec((r, nc_g * cluster_size),
                     lambda g: (0, g)),              # group's pred cols
        pl.BlockSpec((B, 1), lambda g: (0, 0)),
    ]
    with jax.named_scope("cold_layout"):
        operands = [x, _kernel_layout(w), A, Bp, mask]
    if quant:
        in_specs.append(pl.BlockSpec((nc_g * cluster_size, R),
                                     lambda g: (g, 0)))  # group's scales
        operands.append(wsc)
        if mixed:
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            with jax.named_scope("cold_layout"):
                operands.append(_kernel_layout(wout, jnp.float32))
    with jax.named_scope("cold_kernel"):
        y, idx = pl.pallas_call(
            functools.partial(_fused_kernel, activation=activation,
                              gated=R == 3, cats=cats, kc=kc, nc_g=nc_g,
                              cs=cluster_size, quant=quant, mixed=mixed),
            grid=(groups,),
            in_specs=in_specs,
            out_specs=(pl.BlockSpec((B, D), lambda g: (0, 0)),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            out_shape=(jax.ShapeDtypeStruct((B, D), jnp.float32),
                       jax.ShapeDtypeStruct((groups, kc), jnp.int32)),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            name="fused_cold_ffn",
        )(*operands)
    return y, idx
