"""Unit tests for the semantic analysis tier (DESIGN.md §14): jaxpr
invariant rules, the trace registry, the pallas DMA race sanitizer and
its seeded mutant kernels, the trace-registry-drift AST rule, and the
CLI `--tier semantic` surface.

Everything in-process here runs on one host device; the shard_map
grid (tp/ep=2 entries, the double-psum fixture) is exercised through
the CLI subprocess, which forces 8 host devices before importing jax.
"""
import inspect
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis import AnalysisConfig, analyze_files
from repro.analysis import dma_sanitizer, jaxpr_rules, semantic_selftest
from repro.analysis.trace_registry import (KERNEL_ENTRY_POINTS,
                                           TraceEntry, entries,
                                           entry_names)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "scripts", "repro_analyze.py")


def run_cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # the CLI setdefaults this itself; force it here so an outer
    # XLA_FLAGS can't shrink the subprocess below the shard_map grid
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return subprocess.run([sys.executable, CLI, *args], cwd=REPO,
                          env=env, capture_output=True, text=True)


def rules_of(findings):
    return {f.rule for f in findings}


# ------------------------------------------------- jaxpr rule units ----

def _entry(fn, args, **kw):
    return TraceEntry("unit/fn", lambda: (fn, args), **kw)


def test_collective_count_mismatch_fires_without_mesh():
    # declared one psum, trace has none: exact-count rule must fire
    e = _entry(lambda x: x * 2.0, (jnp.zeros((4,), jnp.float32),),
               psums=1)
    assert "jaxpr-collective-count" in rules_of(jaxpr_rules.run_entries([e]))


def test_callback_fires_only_when_clock_driven():
    import jax

    def fn(x):
        jax.debug.print("x {v}", v=x[0])
        return x
    args = (jnp.zeros((4,), jnp.float32),)
    assert "jaxpr-callback" in rules_of(
        jaxpr_rules.run_entries([_entry(fn, args)]))
    assert not jaxpr_rules.run_entries(
        [_entry(fn, args, clock_driven=False)])


def test_const_capture_fires_over_cap():
    baked = jnp.zeros((1024,), jnp.float32)        # 4 KiB closure
    e = _entry(lambda x: x + baked, (jnp.zeros((1024,), jnp.float32),),
               const_cap_bytes=1024)
    assert "jaxpr-const-capture" in rules_of(jaxpr_rules.run_entries([e]))


def test_f64_fires_under_x64_ctx():
    import jax
    e = _entry(lambda x: x.astype(jnp.float64),
               (jnp.zeros((4,), jnp.float32),),
               trace_ctx=lambda: jax.enable_x64(True))
    assert "jaxpr-f64" in rules_of(jaxpr_rules.run_entries([e]))


def test_broken_build_surfaces_as_trace_error():
    def build():
        raise RuntimeError("boom")
    fs = jaxpr_rules.run_entries([TraceEntry("unit/broken", build)])
    assert rules_of(fs) == {"jaxpr-trace-error"}
    assert "boom" in fs[0].message


def test_clean_entry_has_no_findings():
    e = _entry(lambda x: jnp.tanh(x), (jnp.zeros((4,), jnp.float32),))
    assert jaxpr_rules.run_entries([e]) == []


# -------------------------------------------------- trace registry ----

def test_registry_names_are_unique_and_scoped():
    names = entry_names(max_devices=8)
    assert len(names) == len(set(names))
    assert all(n.split("/")[0] in ("kernel", "cold", "decode")
               for n in names)


def test_registry_covers_every_ops_export():
    # the live counterpart of the trace-registry-drift AST rule
    from repro.kernels import ops
    assert set(KERNEL_ENTRY_POINTS) == set(ops.__all__)
    names = " ".join(entry_names(max_devices=8))
    for kernel in ops.__all__:
        assert f"kernel/{kernel}" in names


def test_single_device_entries_trace_clean():
    one_dev = entries(max_devices=1)
    assert one_dev, "registry has no single-device entries"
    assert all(e.n_devices == 1 for e in one_dev)
    assert jaxpr_rules.run_entries(one_dev) == []


# ---------------------------------------------------- DMA sanitizer ----

def test_clean_mini_kernel_is_silent_and_faithful():
    fs, y, x, w = dma_sanitizer.run_mini_shadow(
        semantic_selftest.CLEAN_MINI, case="clean")
    assert fs == []
    want = sum(x @ w[k * 8:(k + 1) * 8] for k in range(4))
    assert dma_sanitizer.fidelity_findings("clean", y, want) == []


@pytest.mark.parametrize("name", sorted(semantic_selftest.MUTANTS))
def test_mutant_trips_its_race_classes(name):
    kernel, expected = semantic_selftest.MUTANTS[name]
    fs, _, _, _ = dma_sanitizer.run_mini_shadow(kernel, case=name)
    assert expected <= rules_of(fs), (name, fs)


def test_fidelity_comparator_reports_drift():
    fs = dma_sanitizer.fidelity_findings(
        "drift", np.ones((2, 2)), np.zeros((2, 2)))
    assert rules_of(fs) == {"dma-shadow-fidelity"}
    assert dma_sanitizer.fidelity_findings(
        "same", np.ones((2, 2)), np.ones((2, 2))) == []


def test_real_fused_kernel_sweep_is_race_free():
    assert dma_sanitizer.sweep_fused_cold_ffn() == []


# -------------------------------------- trace-registry-drift (AST) ----

_OPS_BAD = '__all__ = ["a_kernel", "b_kernel"]\n'
_REG_A_ONLY = 'KERNEL_ENTRY_POINTS = ("a_kernel",)\n'


def _drift_config():
    return AnalysisConfig(kernels_ops_path="x/ops.py",
                          trace_registry_path="x/reg.py")


def test_unregistered_kernel_export_fires():
    fs = analyze_files({"x/ops.py": _OPS_BAD, "x/reg.py": _REG_A_ONLY},
                       _drift_config())
    drift = [f for f in fs if f.rule == "trace-registry-drift"]
    assert len(drift) == 1
    assert "b_kernel" in drift[0].message
    assert drift[0].path == "x/ops.py"


def test_fully_registered_exports_are_clean():
    reg = 'KERNEL_ENTRY_POINTS = ("a_kernel", "b_kernel")\n'
    fs = analyze_files({"x/ops.py": _OPS_BAD, "x/reg.py": reg},
                       _drift_config())
    assert not [f for f in fs if f.rule == "trace-registry-drift"]


# -------------------------------------------- interpret unification ----

def test_kernel_wrappers_share_the_tpu_detection_default():
    from repro.kernels import default_interpret, ops
    from repro.kernels.cluster_gather_ffn import (cluster_gather_ffn,
                                                  fused_cold_ffn)
    from repro.kernels.dense_ffn import dense_ffn
    for fn in (dense_ffn, cluster_gather_ffn, fused_cold_ffn,
               ops.fused_cold_ffn, ops.cluster_gather_ffn_grouped):
        sig = inspect.signature(fn)
        assert sig.parameters["interpret"].default is None, fn
    import jax
    assert default_interpret() == (jax.default_backend() != "tpu")


# --------------------------------------------------------- CLI gate ----

def test_cli_semantic_self_test_proves_every_rule():
    r = run_cli("--tier", "semantic", "--self-test")
    assert r.returncode == 0, r.stdout + r.stderr
    from repro.analysis.semantic import semantic_rules
    for rule in semantic_rules():
        assert f"ok   {rule}" in r.stdout, rule


def test_cli_semantic_gate_is_clean(tmp_path):
    report = tmp_path / "report.json"
    r = run_cli("--tier", "semantic", "--json", str(report))
    assert r.returncode == 0, r.stdout + r.stderr
    import json
    data = json.loads(report.read_text())
    assert data["tier"] == "semantic"
    assert data["findings"] == data["kept"] == []
