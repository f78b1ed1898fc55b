"""The serving path's kernels compiled for a TPU v5e that is described,
not attached: the chip's compiler (installed with jax) refuses what
interpret mode accepts — unaligned DMA slices, value-level dynamic
slices, scatters, 1-D reshapes — so these compiles guard every change
to the fused cold kernel at its real widths without a chip.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, so a
worker that describes it at import would stop the other test workers
from collecting the same tests. Every test here compiles in the
worker's own process, with the persistent compilation cache off
(compiles for a described chip cannot be read back from it)."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

SMOLLM = get_config("smollm-135m")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("storage", ["fp16", "int8"])
@pytest.mark.parametrize("cats", [False, True], ids=["relu", "cats"])
@pytest.mark.parametrize("B", [1, 8])
@pytest.mark.parametrize("kc", [1, 4])
def test_fused_cold_ffn_compiles_for_v5e(one_chip, kc, B, cats, storage):
    """smollm-135m's cold path: D=576, 64-row clusters, rank-64
    predictor, 23 cold clusters in one group, bf16 or int8 bundles."""
    from repro.kernels.cluster_gather_ffn import fused_cold_ffn
    sf = SMOLLM.sparse_ffn
    D, cs, r = SMOLLM.d_model, sf.cluster_size, sf.predictor_rank
    n = (SMOLLM.d_ff - cs) // cs * cs
    quant = storage != "fp16"
    args = [_spec(one_chip, (B, D), jnp.bfloat16),
            _spec(one_chip, (n, 3, D), jnp.int8 if quant else jnp.bfloat16),
            _spec(one_chip, (D, r), jnp.bfloat16),
            _spec(one_chip, (r, n), jnp.bfloat16),
            _spec(one_chip, (B, 1), jnp.float32)]
    kw = {"wsc": _spec(one_chip, (n, 3), jnp.float32)} if quant else {}

    def cold(*a, **k):
        return fused_cold_ffn(*a, activation=SMOLLM.activation,
                              cluster_size=cs, groups=1, kc=kc, cats=cats,
                              interpret=False, **k)
    compiled = jax.jit(cold).lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_decode_step(sharding, cfg):
    """Optimized HLO text of one pallas-backend serving decode step of
    `cfg` at batch bucket 4, compiled for `sharding`'s device."""
    from repro.serving.families import serving_family
    fam = serving_family(cfg)
    model = fam.make_model(cfg)
    plan = fam.build_plan(cfg, backend="pallas").plan_for_batch(4)
    place = (lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                            sharding=sharding))
    params = jax.tree.map(place, jax.eval_shape(model.init,
                                                jax.random.key(0)))
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(4, 64)))
    step = fam.make_decode_step(cfg)
    return jax.jit(lambda p, t, c, m: step(p, t, c, plan, m)).lower(
        params, _spec(sharding, (4, 1), jnp.int32), cache,
        _spec(sharding, (4,), jnp.bool_)).compile().as_text()


def test_pallas_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """One serving decode step of full-width smollm-135m cut to 2
    layers, pallas backend, batch bucket 4: the kernel is in the
    compiled step. Off the chip the kernels default to interpret mode,
    so the test steers them to the chip's lowering."""
    from repro.kernels import cluster_gather_ffn
    monkeypatch.setattr(cluster_gather_ffn, "default_interpret",
                        lambda: False)
    text = _compile_decode_step(one_chip, SMOLLM.replace(num_layers=2))
    assert text.count("tpu_custom_call") >= 1


def test_v5e_decode_step_names_its_kernel_and_scopes(one_chip, monkeypatch):
    """The compiled step keeps what the benchmark's trace readers look
    for: the kernel's instruction named `fused_cold_ffn`
    (`cold_ffn_roofline` matches it by name) under the `cold_kernel`
    scope, and every decode scope in some op's op-name metadata."""
    import re
    from repro.kernels import cluster_gather_ffn
    monkeypatch.setattr(cluster_gather_ffn, "default_interpret",
                        lambda: False)
    text = _compile_decode_step(one_chip, SMOLLM.replace(num_layers=2))
    kernel = [line.strip() for line in text.splitlines()
              if "tpu_custom_call" in line and " = " in line]
    assert kernel and all(
        re.match(r"^%?fused_cold_ffn[\w.]* = .* custom-call\(", line)
        and "/cold_kernel/" in line for line in kernel)
    scopes = {part for name in re.findall(r'op_name="([^"]*)"', text)
              for part in name.split("/")}
    assert {"attention", "kv_write", "ffn_hot", "predictor", "cold_layout",
            "cold_kernel", "lm_head"} <= scopes


def test_fp32_pallas_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The same step held in fp32 at full matmul precision, as
    chip_smoke.py serves both backends for its end-to-end gate: every
    dot in the kernel then asks the chip for fp32 contraction."""
    from repro.kernels import cluster_gather_ffn
    monkeypatch.setattr(cluster_gather_ffn, "default_interpret",
                        lambda: False)
    cfg = SMOLLM.replace(num_layers=2, param_dtype="float32",
                         compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        text = _compile_decode_step(one_chip, cfg)
    assert text.count("tpu_custom_call") >= 1
