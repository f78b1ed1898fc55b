"""The yardstick's arithmetic against hand sums."""
import pytest

from chipbench import counts, spec

SMOLLM = spec.load_json(spec.HERE / "configs" / "smollm-135m.json")
# DeepSeekMoE-16B's published widths (deepseek-ai/deepseek-moe-16b-base
# config.json), cut to 10 layers: the counts read a configuration's
# keys, whatever model it is
MOE = {"hidden_size": 2048, "num_attention_heads": 16,
       "num_key_value_heads": 16, "num_hidden_layers": 10,
       "vocab_size": 102400, "moe_intermediate_size": 1408,
       "n_routed_experts": 64, "n_shared_experts": 2,
       "num_experts_per_tok": 6}


def test_smollm_flops_per_token_hand_sum():
    ctx = 100
    # per layer: q 2*576*576, k and v 2*576*192 each, o 2*576*576,
    # attention 4*9*64*ctx, FFN 3*2*576*1536; head 2*576*49152
    layer = (2 * 576 * 576 + 2 * 2 * 576 * 192 + 2 * 576 * 576
             + 4 * 9 * 64 * ctx + 3 * 2 * 576 * 1536)
    assert counts.model_flops_token(SMOLLM, ctx) == \
        30 * layer + 2 * 576 * 49152


def test_moe_flops_per_token_hand_sum():
    ctx = 7
    attn = 4 * 2 * 2048 * 2048 + 4 * 16 * 128 * ctx
    ffn = 2 * 2048 * 64 + (2 + 6) * 3 * 2 * 2048 * 1408
    assert counts.model_flops_token(MOE, ctx) == \
        10 * (attn + ffn) + 2 * 2048 * 102400


def test_cold_kernel_bucket4_plan_hand_sum():
    # smollm bucket-4 plan: n_hot 64, kc 1 cluster of 64 rows, rank 64
    c = counts.cold_kernel_call(rows=4, d_model=576, rank=64,
                                n_cold=1536 - 64, cluster_size=64, kc=1)
    ops = 2 * 4 * 576 * 64 + 2 * 4 * 64 * 1472 + 3 * 2 * 4 * 576 * 64
    nbytes = (4 * 576 * 2 + 576 * 64 * 2 + 64 * 1472 * 2 + 4 * 4
              + 3 * 64 * 576 * 2 + 4 * 576 * 4 + 4)
    assert (c["ops"], c["bytes"]) == (ops, nbytes) == (1933312, 497172)


def test_peaks_are_keyed_by_device_kind():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_roofline_names_its_bound():
    p = counts.peaks("TPU v5 lite")
    t, which = counts.roofline_seconds(1933312, 497172, p)
    assert which == "memory" and t == pytest.approx(497172 / 819e9)
    t, which = counts.roofline_seconds(1e15, 1.0, p)
    assert which == "compute"


def test_weights_of_a_config_with_experts_match_the_program():
    """model.make_weights reads a configuration's keys: a small
    DeepSeekMoE-shaped file gives the program's own parameter tree."""
    from chipbench import model
    cfg = dict(MOE, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2, vocab_size=256,
               moe_intermediate_size=32, n_routed_experts=8,
               tie_word_embeddings=False, rms_norm_eps=1e-6,
               rope_theta=10000.0, hidden_act="silu",
               serve={"program_arch": "deepseek-moe-16b",
                      "dtype": "bfloat16"})
    pc = model.program_config(cfg)
    w = model.make_weights(pc, 2**31 + 3)     # checks the tree itself
    assert w["layers"]["moe"]["experts"].shape == (2, 8, 32, 3, 64)
