"""Launch layer: spec fitting, input specs, collective parsing,
roofline math — all without touching the 512-device dry-run."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.dryrun import parse_collectives, _shape_bytes
from repro.launch.input_specs import adapt_config, input_specs
from repro.launch.mesh import make_host_mesh
from repro.launch.roofline import analyze_record, model_flops
from repro.sharding import _filter_spec


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


def test_filter_spec_drops_nondividing_axes():
    m = FakeMesh()
    # batch=1 cannot shard over data=16
    assert _filter_spec(P("data", None), m, shape=(1, 8)) == P(None, None)
    assert _filter_spec(P("data", None), m, shape=(32, 8)) == P("data", None)
    # tuple axes: ('pod','data') with pod absent -> ('data',)
    assert _filter_spec(P(("pod", "data")), m, shape=(32,)) == P(("data",))
    # unknown axis names dropped entirely
    assert _filter_spec(P("nope", "model"), m, shape=(4, 32)) == \
        P(None, "model")


def test_shape_bytes():
    assert _shape_bytes("bf16[8,64]") == 8 * 64 * 2
    assert _shape_bytes("f32[2,2]") == 16
    assert _shape_bytes("(bf16[8], f32[4])") == 16 + 16
    assert _shape_bytes("pred[10]") == 10


def test_parse_collectives():
    hlo = """
ENTRY %main {
  %ar = bf16[8,64] all-reduce(%x), replica_groups={}
  %ag.1 = f32[16,16]{1,0} all-gather(%y), dimensions={0}
  %cp = bf16[4,4] collective-permute-start(%z)
  %cpd = bf16[4,4] collective-permute-done(%cp)
  %notacoll = bf16[8] add(%a, %b)
}
"""
    out = parse_collectives(hlo)
    assert out["bytes"]["all-reduce"] == 8 * 64 * 2
    assert out["bytes"]["all-gather"] == 16 * 16 * 4
    assert out["bytes"]["collective-permute"] == 4 * 4 * 2  # start only
    assert out["counts"]["all-to-all"] == 0


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_input_specs_shapes(arch, shape_name):
    """Every (arch x shape) pair must produce well-formed input specs —
    the cheap half of the dry-run guarantee."""
    shape = INPUT_SHAPES[shape_name]
    cfg = adapt_config(get_config(arch), shape)
    if shape_name == "long_500k":
        assert cfg.subquadratic, f"{arch} must decode 500k sub-quadratically"
    mesh = make_host_mesh()
    specs = input_specs(cfg, shape, mesh)
    toks = specs["tokens"]
    if shape.kind == "decode":
        assert toks.shape == (shape.global_batch, 1)
    elif cfg.family == "vlm":
        assert toks.shape[1] + cfg.num_image_tokens == shape.seq_len
    else:
        assert toks.shape == (shape.global_batch, shape.seq_len)
    if cfg.family == "encdec" and shape.kind != "decode":
        assert specs["frames"].shape == (shape.global_batch,
                                         cfg.num_frames, cfg.d_model)


def test_roofline_terms_and_dominance():
    rec = {"arch": "smollm-135m", "shape": "decode_32k",
           "flops_per_device": 197e12, "bytes_per_device": 819e9,
           "n_devices": 256,
           "collectives": {"bytes": {"all-reduce": 50e9 * 2},
                           "counts": {}}}
    out = analyze_record(rec)
    assert abs(out["compute_s"] - 1.0) < 1e-6
    assert abs(out["memory_s"] - 1.0) < 1e-6
    assert abs(out["collective_s"] - 2.0) < 1e-6
    assert out["dominant"] == "collective"


def test_dispatch_groups_single_source_of_truth():
    """MoE dispatch groups derive from launch.mesh.dispatch_groups
    everywhere: one group per (pod x data) row, 1 without a mesh, and
    1 on a serving replica's (1, n_model) submesh — which is what
    makes dp x tp x ep compose (each replica dispatches over exactly
    its local tokens)."""
    from repro.launch.mesh import dispatch_groups

    class PodMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    class ReplicaSubmesh:
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 4}

    assert dispatch_groups(None) == 1
    assert dispatch_groups(PodMesh()) == 32
    assert dispatch_groups(FakeMesh()) == 16
    assert dispatch_groups(ReplicaSubmesh()) == 1


def test_dryrun_moe_group_inference_deduplicated():
    """Satellite regression: the two dry-run lowering paths used to
    re-derive moe_dispatch_groups inline; both must now go through
    adapt_moe_groups (which defers to the shared mesh helper), and
    the adapter passes non-MoE configs through untouched."""
    import inspect
    from repro.launch import dryrun
    src = inspect.getsource(dryrun)
    assert src.count("cfg = adapt_moe_groups(cfg, mesh)") == 2  # both paths
    assert "moe_dispatch_groups=nb" not in src             # inline gone
    cfg = get_config("deepseek-moe-16b")
    assert dryrun.adapt_moe_groups(cfg, FakeMesh()) \
        .moe_dispatch_groups == 16
    dense = get_config("smollm-135m")
    assert dryrun.adapt_moe_groups(dense, FakeMesh()) is dense


def test_dryrun_moe_decode_smoke():
    """The moe family's decode dry-run path end to end (adapt config,
    infer groups, lower the decode step on the mesh) — the cheap
    1-device half of the 256-device sweep guarantee."""
    from jax import set_mesh
    from repro.launch.dryrun import adapt_moe_groups, decode_plan_for
    from repro.launch.input_specs import cache_specs, param_specs
    from repro.models.model import build_model

    shape = INPUT_SHAPES["decode_32k"]
    mesh = make_host_mesh()
    cfg = adapt_config(get_config("deepseek-moe-16b"), shape).reduced()
    cfg = adapt_moe_groups(cfg, mesh)
    assert cfg.moe_dispatch_groups == 1        # host mesh: data == 1
    assert decode_plan_for(cfg, mesh.shape["model"]) is None  # router=plan
    model = build_model(cfg)
    with set_mesh(mesh):
        pspecs = param_specs(model, cfg, mesh)
        batch = input_specs(cfg, shape, mesh)
        cspecs = cache_specs(model, cfg, shape, mesh)
        lowered = jax.jit(
            lambda p, t, c: model.decode_step(p, t, c, None)).lower(
            pspecs, batch["tokens"], cspecs)
    # the lowered program must actually carry the stacked expert
    # tensor (L, E, f, R, D) — a dense-only fallthrough would drop it
    from repro.core.sparse_ffn import ffn_rows
    expert_dims = "x".join(map(str, (
        cfg.num_layers, cfg.num_experts, cfg.d_ff,
        ffn_rows(cfg.activation), cfg.d_model)))
    assert expert_dims in lowered.as_text()


def test_model_flops_moe_uses_active_params():
    dense = model_flops("qwen3-14b", "train_4k")
    moe_total = get_config("deepseek-moe-16b").param_count()
    moe_active = get_config("deepseek-moe-16b").active_param_count()
    assert moe_active < moe_total * 0.6
    assert model_flops("deepseek-moe-16b", "train_4k") == \
        6 * moe_active * 256 * 4096
    assert dense > 0
