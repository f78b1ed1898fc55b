"""Seeded fixtures proving every semantic rule fires (--tier semantic
--self-test).

Mirrors analysis/selftest/ for the jaxpr tier and the DMA sanitizer:

* fixture TraceEntries seed one jaxpr-rule violation each — a
  double-psum shard_map body (the collective-count regression the
  acceptance gate names), a bf16 psum, an f64 trace, a debug.print in
  clock-driven code, an oversized captured const, and a build that
  raises. The two bad collective bodies call `jax.lax.psum` through a
  local alias on purpose: the AST tier counts *names*, so an aliased
  reduce is exactly the regression only the traced jaxpr can see.
* mutant mini-kernels seed one DMA race class each — written against
  the real pl/pltpu surface (they would compile as pallas kernels)
  but only ever executed through dma_sanitizer's shadow harness. The
  clean mini-kernel must produce zero findings and match the eager
  reference, proving the harness neither under- nor over-reports.

Unlike analysis/selftest/ these fixtures ARE imported and executed —
they live here (not in the excluded selftest/ dir) so the repo-wide
AST scan also proves they carry no *syntactic* violations: what they
seed is invisible to that tier by construction.

The shard_map fixtures need >= 2 host devices; the CLI forces 8 via
XLA_FLAGS before importing jax, and the self-test fails loudly (rather
than skipping rules) when run in a 1-device process.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import dma_sanitizer, jaxpr_rules
from repro.analysis.trace_registry import TraceEntry

__all__ = ["fixture_entries", "clean_entries", "MUTANTS", "CLEAN_MINI",
           "EXPECTED_SEMANTIC", "run_semantic_self_test"]


# ------------------------------------------------ jaxpr fixtures ----

def _shard_mapped(local):
    """Wrap a shard-local body over the ambient 'model' mesh axis."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import current_mesh

    def fn(x):
        return jax.shard_map(local, mesh=current_mesh(),
                             in_specs=(P("model"),), out_specs=P(None),
                             axis_names={"model"}, check_vma=False)(x)
    return fn, (jnp.zeros((8,), jnp.float32),)


def _build_double_psum():
    # aliased reduce: invisible to the AST counter, plain as day in
    # the jaxpr — the seeded §3 budget regression
    from jax.lax import psum as allreduce

    def local(xl):
        y = allreduce(xl.astype(jnp.float32), "model")
        return allreduce(y, "model")
    return _shard_mapped(local)


def _build_bf16_psum():
    from jax.lax import psum as allreduce

    def local(xl):
        return allreduce(xl.astype(jnp.bfloat16), "model")
    return _shard_mapped(local)


def _build_clean_shard_map():
    def local(xl):
        return jax.lax.psum(xl.astype(jnp.float32), "model")
    return _shard_mapped(local)


def _build_f64():
    return (lambda x: x.astype(jnp.float64) * 2.0), \
        (jnp.zeros((4,), jnp.float32),)


def _x64_ctx():
    return jax.enable_x64(True)


def _build_callback():
    def fn(x):
        jax.debug.print("decode x[0] {v}", v=x[0])
        return x + 1.0
    return fn, (jnp.zeros((4,), jnp.float32),)


def _build_const_capture():
    baked = jnp.zeros((64, 1024), jnp.float32)       # 256 KiB closure

    def fn(x):
        return x @ baked
    return fn, (jnp.zeros((2, 64), jnp.float32),)


def _build_trace_error():
    raise RuntimeError("seeded broken registration")


def _build_clean():
    return (lambda x: jnp.tanh(x) * 2.0), (jnp.zeros((4,), jnp.float32),)


def fixture_entries() -> tuple:
    """Seeded-violation TraceEntries, keyed by the rule they prove."""
    return (
        TraceEntry("fixture/double-psum", _build_double_psum,
                   n_devices=2, psums=1, all_gathers=0),
        TraceEntry("fixture/bf16-psum", _build_bf16_psum,
                   n_devices=2, psums=1, all_gathers=0),
        TraceEntry("fixture/f64", _build_f64, trace_ctx=_x64_ctx),
        TraceEntry("fixture/callback", _build_callback),
        TraceEntry("fixture/const-capture", _build_const_capture,
                   const_cap_bytes=64 * 1024),
        TraceEntry("fixture/trace-error", _build_trace_error),
    )


def clean_entries() -> tuple:
    """Fixtures that must stay finding-free (incl. a correct
    single-psum shard_map body and a non-clock-driven callback)."""
    return (
        TraceEntry("fixture/clean-shardmap", _build_clean_shard_map,
                   n_devices=2, psums=1, all_gathers=0),
        TraceEntry("fixture/clean", _build_clean),
        TraceEntry("fixture/clean-offline-callback", _build_callback,
                   clock_driven=False),
    )


# ---------------------------------------------- mutant mini-kernels ----
# Each would compile as a pallas kernel; each is only ever run through
# dma_sanitizer.run_mini_shadow. Signature: (x_ref, w_hbm, y_ref,
# *, kc, cs) — kc clusters of cs rows, double-buffered HBM->VMEM.

def clean_mini(x_ref, w_hbm, y_ref, *, kc, cs):
    """Correct Fig-6(b) overlap: warm-up start, prefetch k+1, wait k."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(buf, sem):
        def dma(slot, k):
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds(k * cs, cs)], buf.at[slot], sem.at[slot])
        dma(0, 0).start()

        def step(k, _):
            slot = jax.lax.rem(k, 2)

            @pl.when(k + 1 < kc)
            def _prefetch():
                dma(jax.lax.rem(k + 1, 2), k + 1).start()

            dma(slot, k).wait()
            y_ref[...] += x_ref[...] @ buf[slot]
            return 0
        jax.lax.fori_loop(0, kc, step, 0)

    pl.run_scoped(body,
                  buf=pltpu.VMEM((2, cs) + w_hbm.shape[1:], w_hbm.dtype),
                  sem=pltpu.SemaphoreType.DMA((2,)))


def mutant_dropped_wait(x_ref, w_hbm, y_ref, *, kc, cs):
    """Never waits: compute reads slots whose copies are in flight."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(buf, sem):
        def dma(slot, k):
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds(k * cs, cs)], buf.at[slot], sem.at[slot])
        dma(0, 0).start()

        def step(k, _):
            slot = jax.lax.rem(k, 2)

            @pl.when(k + 1 < kc)
            def _prefetch():
                dma(jax.lax.rem(k + 1, 2), k + 1).start()

            # wait dropped
            y_ref[...] += x_ref[...] @ buf[slot]
            return 0
        jax.lax.fori_loop(0, kc, step, 0)

    pl.run_scoped(body,
                  buf=pltpu.VMEM((2, cs) + w_hbm.shape[1:], w_hbm.dtype),
                  sem=pltpu.SemaphoreType.DMA((2,)))


def mutant_premature_slot_reuse(x_ref, w_hbm, y_ref, *, kc, cs):
    """Single-slot buffer: the prefetch restarts the slot before the
    previous copy was waited on."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(buf, sem):
        def dma(k):
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds(k * cs, cs)], buf.at[0], sem.at[0])
        dma(0).start()

        def step(k, _):
            @pl.when(k + 1 < kc)
            def _prefetch():
                dma(k + 1).start()        # reuses slot 0 pre-wait

            dma(k).wait()
            y_ref[...] += x_ref[...] @ buf[0]
            return 0
        jax.lax.fori_loop(0, kc, step, 0)

    pl.run_scoped(body,
                  buf=pltpu.VMEM((1, cs) + w_hbm.shape[1:], w_hbm.dtype),
                  sem=pltpu.SemaphoreType.DMA((1,)))


def mutant_swapped_slot_wait(x_ref, w_hbm, y_ref, *, kc, cs):
    """Waits on the prefetch slot instead of the compute slot."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(buf, sem):
        def dma(slot, k):
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds(k * cs, cs)], buf.at[slot], sem.at[slot])
        dma(0, 0).start()

        def step(k, _):
            slot = jax.lax.rem(k, 2)

            @pl.when(k + 1 < kc)
            def _prefetch():
                dma(jax.lax.rem(k + 1, 2), k + 1).start()

            swapped = jax.lax.rem(k + 1, 2)          # wrong slot
            pltpu.make_async_copy(
                w_hbm.at[pl.ds(k * cs, cs)], buf.at[swapped],
                sem.at[swapped]).wait()
            y_ref[...] += x_ref[...] @ buf[slot]
            return 0
        jax.lax.fori_loop(0, kc, step, 0)

    pl.run_scoped(body,
                  buf=pltpu.VMEM((2, cs) + w_hbm.shape[1:], w_hbm.dtype),
                  sem=pltpu.SemaphoreType.DMA((2,)))


def mutant_double_wait(x_ref, w_hbm, y_ref, *, kc, cs):
    """Waits twice on the same copy."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(buf, sem):
        def dma(slot, k):
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds(k * cs, cs)], buf.at[slot], sem.at[slot])

        def step(k, _):
            slot = jax.lax.rem(k, 2)
            dma(slot, k).start()
            dma(slot, k).wait()
            dma(slot, k).wait()                      # second wait
            y_ref[...] += x_ref[...] @ buf[slot]
            return 0
        jax.lax.fori_loop(0, kc, step, 0)

    pl.run_scoped(body,
                  buf=pltpu.VMEM((2, cs) + w_hbm.shape[1:], w_hbm.dtype),
                  sem=pltpu.SemaphoreType.DMA((2,)))


def mutant_direct_overwrite(x_ref, w_hbm, y_ref, *, kc, cs):
    """Compute writes a slot while a copy into it is in flight."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(buf, sem):
        def dma(slot, k):
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds(k * cs, cs)], buf.at[slot], sem.at[slot])

        def step(k, _):
            slot = jax.lax.rem(k, 2)
            dma(slot, k).start()
            buf[slot] = jnp.zeros((cs,) + w_hbm.shape[1:],
                                  w_hbm.dtype)       # overwrite in flight
            dma(slot, k).wait()
            y_ref[...] += x_ref[...] @ buf[slot]
            return 0
        jax.lax.fori_loop(0, kc, step, 0)

    pl.run_scoped(body,
                  buf=pltpu.VMEM((2, cs) + w_hbm.shape[1:], w_hbm.dtype),
                  sem=pltpu.SemaphoreType.DMA((2,)))


# mutant name -> (kernel, race classes it must trip)
MUTANTS = {
    "mutant_dropped_wait": (mutant_dropped_wait,
                            {"dma-read-not-ready",
                             "dma-inflight-at-exit"}),
    "mutant_premature_slot_reuse": (mutant_premature_slot_reuse,
                                    {"dma-start-without-wait"}),
    "mutant_swapped_slot_wait": (mutant_swapped_slot_wait,
                                 {"dma-read-not-ready"}),
    "mutant_double_wait": (mutant_double_wait, {"dma-double-wait"}),
    "mutant_direct_overwrite": (mutant_direct_overwrite,
                                {"dma-slot-overwrite"}),
}

CLEAN_MINI = clean_mini

# rule -> the fixture/mutant that proves it fires
EXPECTED_SEMANTIC = {
    "jaxpr-collective-count": "fixture/double-psum",
    "jaxpr-collective-fp32": "fixture/bf16-psum",
    "jaxpr-f64": "fixture/f64",
    "jaxpr-callback": "fixture/callback",
    "jaxpr-const-capture": "fixture/const-capture",
    "jaxpr-trace-error": "fixture/trace-error",
    "dma-read-not-ready": "mutant_dropped_wait",
    "dma-inflight-at-exit": "mutant_dropped_wait",
    "dma-start-without-wait": "mutant_premature_slot_reuse",
    "dma-double-wait": "mutant_double_wait",
    "dma-slot-overwrite": "mutant_direct_overwrite",
    "dma-shadow-fidelity": "fidelity-drift",
}


def _mini_reference(x, w, kc, cs):
    return sum(x @ w[k * cs:(k + 1) * cs] for k in range(kc))


def run_semantic_self_test():
    """Returns (ok, report_lines) — every semantic rule must fire on
    its seeded fixture/mutant, every clean fixture must stay clean."""
    ok, lines = True, []
    if jax.device_count() < 2:
        return False, [
            "FAIL semantic self-test needs >= 2 host devices for the "
            "shard_map fixtures (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before jax imports)"]

    fired = {}                       # case -> set of rules that fired
    for entry in fixture_entries() + clean_entries():
        fs = jaxpr_rules.run_entries([entry])
        fired[entry.name] = {f.rule for f in fs}
        if entry.name.startswith("fixture/clean") and fs:
            ok = False
            lines.append(f"FAIL clean fixture {entry.name} produced: "
                         + "; ".join(str(f) for f in fs))
    for name, (kernel, _) in MUTANTS.items():
        fs, _, _, _ = dma_sanitizer.run_mini_shadow(kernel, case=name)
        fired[name] = {f.rule for f in fs}

    # the comparator itself: a drifted shadow output must be reported
    drift = dma_sanitizer.fidelity_findings(
        "fidelity-drift", np.ones((2, 2)), np.zeros((2, 2)))
    fired["fidelity-drift"] = {f.rule for f in drift}

    all_rules = jaxpr_rules.JAXPR_RULES + dma_sanitizer.DMA_RULES
    for rule in sorted(set(all_rules) | set(EXPECTED_SEMANTIC)):
        want = EXPECTED_SEMANTIC.get(rule)
        if want is None:
            ok = False
            lines.append(f"FAIL {rule}: no fixture seeds this rule")
        elif rule in fired.get(want, ()):
            lines.append(f"ok   {rule}: fires on {want}")
        else:
            ok = False
            lines.append(f"FAIL {rule}: seeded violation {want} did "
                         f"not fire (got {sorted(fired.get(want, ()))})")

    # every declared race class of every mutant must trip
    for name, (_, expected) in sorted(MUTANTS.items()):
        missing = expected - fired[name]
        if missing:
            ok = False
            lines.append(f"FAIL {name}: missed {sorted(missing)}")

    # the clean mini-kernel: no findings, faithful output
    fs, y, x, w = dma_sanitizer.run_mini_shadow(CLEAN_MINI,
                                                case="clean_mini")
    fs += dma_sanitizer.fidelity_findings(
        "clean_mini", y, _mini_reference(x, w, kc=4, cs=8))
    if fs:
        ok = False
        lines.append("FAIL clean mini-kernel produced: "
                     + "; ".join(str(f) for f in fs))
    else:
        lines.append("ok   clean mini-kernel: no findings, output "
                     "matches the eager reference")
    return ok, lines
