"""A reference module for the tests alone: a DeepSeekMoE-shaped model
whose first `first_k_dense_replace` layers have a dense gated FFN of
`intermediate_size`. It declares that FFN's weights beside the default
MoE tree, as a configuration with leading dense layers would; it
computes no readings."""
import math

from chipbench import model


def leaves(pc, cfg):
    out = model.default_leaves(pc)
    k, f, D = cfg["first_k_dense_replace"], cfg["intermediate_size"], pc.d_model
    s = 1.0 / math.sqrt(D)
    out[("dense", "ffn", "w")] = ((k, f, 3, D), (s, s, 1 / math.sqrt(f)))
    return out
