"""Pallas TPU kernel: dense bundled FFN (the paper's hot/NPU path).

Tiled over the neuron dim: each grid step streams one MXU-aligned
(block_n, R, D) weight tile HBM->VMEM (double-buffered by the Pallas
grid pipeline) and accumulates into the (B, D) output in fp32 — the
dense engine that consumes the planner's hot prefix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret
from repro.kernels.cluster_gather_ffn import _kernel


@functools.partial(jax.jit, static_argnames=("activation", "block_n",
                                             "interpret"))
def dense_ffn(x, w, *, activation: str, block_n: int = 512,
              interpret: bool | None = None):
    """x (B, D); w (N, R, D). Returns (B, D) full dense bundled FFN."""
    if interpret is None:
        interpret = default_interpret()
    B, D = x.shape
    N, R, _ = w.shape
    block_n = min(block_n, N)
    assert N % block_n == 0, (N, block_n)
    gated = R == 3

    def kernel(x_ref, w_ref, o_ref):
        # reuse the gather kernel body with an implicit identity index
        _kernel(None, x_ref, w_ref, o_ref, activation=activation,
                gated=gated)

    out = pl.pallas_call(
        kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((B, D), lambda i: (0, 0)),
            pl.BlockSpec((block_n, R, D), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((B, D), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(x, w)
    return out.astype(x.dtype)
