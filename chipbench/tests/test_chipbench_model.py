"""A configuration file made into the program's ModelConfig and the
benchmark's weights: the draw as it was, a reference's own weight
tree, and `serve.program`."""
import copy
import hashlib
import json
import shutil

import jax
import numpy as np
import pytest

from chipbench import model, spec

SEED = 2**31 + 3


def _dense() -> dict:
    """smollm-135m's file at a CPU size, served as the file says."""
    c = copy.deepcopy(spec.load_json(spec.HERE / "configs"
                                     / "smollm-135m.json"))
    c.update(hidden_size=128, intermediate_size=512, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)
    c["serve"].update(predictor_rank=16)
    return c


def _moe(**kw) -> dict:
    """A DeepSeekMoE-shaped file at a CPU size."""
    return dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                num_hidden_layers=2, vocab_size=256,
                moe_intermediate_size=32, n_routed_experts=8,
                n_shared_experts=2, num_experts_per_tok=6,
                tie_word_embeddings=False, rms_norm_eps=1e-6,
                rope_theta=10000.0, hidden_act="silu",
                serve={"program_arch": "deepseek-moe-16b",
                       "dtype": "bfloat16"}, **kw)


def _checksum(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 of every leaf's path, shape, dtype and bytes, as the draw
# before references could declare a tree made them at SEED
@pytest.mark.parametrize("cfg,digest", [
    (_dense(), "9538f058332298f32b1e40efdd5f9c9a"
               "5a195c2ad62e6126a49f473dc4dbd37b"),
    (_moe(), "9ed3961a44bd560abb3590c1b2e8435822"
             "fd63e30c404128f8d0a131b3320e1a"),
], ids=["dense", "moe"])
def test_weights_are_drawn_as_before(cfg, digest):
    ref = spec.load_module(spec.HERE / "references" / "dense_hybrid.py",
                           "chipbench_ref_dense_hybrid")
    pc = model.program_config(cfg, ref)
    assert _checksum(model.make_weights(pc, SEED, reference=ref,
                                        cfg=cfg)) == digest


def _root_with_leading_dense(tmp_path):
    """A benchmark tree with one more configuration and cell, added as
    files alone: a DeepSeekMoE-shaped file with a leading dense layer,
    the reference that declares its weights, and the cell's limits."""
    here = tmp_path / "chipbench"
    for d in ("configs", "references", "limits", "traffic"):
        (here / d).mkdir(parents=True)
    shutil.copy(spec.HERE / "traffic" / "chat-c8.json", here / "traffic")
    shutil.copy(spec.HERE / "tests" / "lead_dense_reference.py",
                here / "references" / "lead_dense.py")
    cfg = _moe(reference="lead_dense", first_k_dense_replace=1,
               intermediate_size=96)
    (here / "configs" / "moe-lead.json").write_text(json.dumps(cfg))
    (here / "limits" / "moe-lead.chat-c8.json").write_text(
        json.dumps({"limits": {"token_gap_mean": 0.001}}))
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    bench["configs"].append({"name": "moe-lead",
                             "file": "chipbench/configs/moe-lead.json"})
    bench["workloads"].append({"name": "moe-lead.chat-c8",
                               "config": "moe-lead", "traffic": "chat-c8",
                               "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_leading_dense_layers_reach_the_program_tree_check(tmp_path):
    cell = spec.load_cell("moe-lead.chat-c8", _root_with_leading_dense(tmp_path))
    ref = cell.reference()
    pc = model.program_config(cell.config, ref)
    tree = model.make_weights(pc, SEED, check_against_program=False,
                              reference=ref, cfg=cell.config)
    assert tree["dense"]["ffn"]["w"].shape == (1, 96, 3, 64)
    assert tree["layers"]["moe"]["experts"].shape == (2, 8, 32, 3, 64)
    with pytest.raises(ValueError) as e:
        model.make_weights(pc, SEED, reference=ref, cfg=cell.config)
    msg = str(e.value)
    assert "only the benchmark's: dense/ffn/w (1, 96, 3, 64) bfloat16" in msg
    # the paths both trees have agree, so they are not listed
    assert "layers/moe" not in msg and "only the program's" not in msg


def test_leading_dense_layers_need_a_reference_that_declares_them():
    cfg = _moe(first_k_dense_replace=1, intermediate_size=96)
    ref = spec.load_module(spec.HERE / "references" / "dense_hybrid.py",
                           "chipbench_ref_dense_hybrid")
    with pytest.raises(ValueError, match="must declare the leading dense"):
        model.program_config(cfg, ref)
    with pytest.raises(ValueError, match="must declare the leading dense"):
        model.program_config(cfg)


def test_serve_program_sets_model_config_fields():
    cfg = _moe()
    assert model.program_config(cfg).moe_capacity_factor == 1.25
    cfg["serve"]["program"] = {"moe_capacity_factor": 64.0}
    assert model.program_config(cfg).moe_capacity_factor == 64.0
    cfg["serve"]["program"] = {"moe_capacity_factor": 2.0,
                               "first_dense_layers": 1}
    with pytest.raises(ValueError, match="no ModelConfig field: "
                                         "first_dense_layers"):
        model.program_config(cfg)
    cfg["serve"]["program"] = {"num_layers": 4}
    with pytest.raises(ValueError, match="keys set: num_layers"):
        model.program_config(cfg)


def test_tree_diff_names_each_kind_of_difference():
    a = {"x": np.zeros((2, 3), np.float32), "y": {"z": np.zeros(4)}}
    b = {"x": np.zeros((2, 4), np.float32), "w": np.zeros(1, np.int32)}
    assert model.tree_diff(a, b).splitlines() == [
        "  only the benchmark's: y/z (4,) float64",
        "  only the program's: w (1,) int32",
        "  differs: x benchmark (2, 3) float32, program (2, 4) float32"]
    assert model.tree_diff(a, a) == ""
