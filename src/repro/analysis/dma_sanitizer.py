"""DMA race sanitizer: shadow-state machine for the pallas cold kernels.

Interpret mode executes `pltpu.make_async_copy` synchronously, so a
missing/wrong `wait()` or a premature slot reuse is *invisible* to
every CPU test — the data is always there. On a real TPU the same bug
is a race: compute reads a VMEM slot whose copy hasn't landed. This
module re-executes the kernel body eagerly with the pallas surface
swapped for shadow objects that track every VMEM buffer slot through
idle -> in-flight -> ready and flag the §4.3 pipeline's race classes:

* dma-start-without-wait — start() on a slot whose previous copy was
    never waited on (premature slot reuse; the in-flight copy is lost).
* dma-double-wait — wait() with no copy in flight (double wait, or a
    wait paired with a different semaphore than the start signaled).
* dma-slot-overwrite — direct compute write to a slot while a copy
    into it is in flight.
* dma-read-not-ready — compute read of a slot that is not ready (the
    dropped-wait race: garbage on real hardware).
* dma-inflight-at-exit — a copy still in flight when its run_scoped
    scope ends (its semaphore leaks past the kernel).
* dma-shadow-fidelity — the shadow execution's outputs diverged from
    the real interpret-mode kernel: the harness itself rotted and its
    race verdicts can no longer be trusted.

The harness patches the *target module's* `pl` / `pltpu` / `jax`
globals (restored on exit), so the real `_fused_kernel` body runs
unmodified — what is sanitized is exactly the shipped kernel, swept
over every storage dtype including the int4 sidecar's paired
descriptors (sweep_fused_cold_ffn). Seeded mutant kernels in
semantic_selftest.py prove each race class still fires.
"""
from __future__ import annotations

import contextlib
import sys

import numpy as np

from repro.analysis.framework import Finding

__all__ = ["DMA_RULES", "Sanitizer", "PlainRef", "HBMRef",
           "shadow_env", "run_fused_shadow", "run_mini_shadow",
           "fidelity_findings", "sweep_fused_cold_ffn"]

DMA_RULES = ("dma-start-without-wait", "dma-double-wait",
             "dma-slot-overwrite", "dma-read-not-ready",
             "dma-inflight-at-exit", "dma-shadow-fidelity")

IDLE, INFLIGHT, READY = "idle", "in-flight", "ready"


class Sanitizer:
    """Finding collector + per-grid-step state shared by the shadows."""

    def __init__(self, case: str):
        self.case = case
        self.findings: list = []
        self.program_id = 0

    def report(self, rule: str, message: str):
        self.findings.append(
            Finding(rule, f"semantic/{self.case}", 1,
                    f"[grid step {self.program_id}] {message}"))


# ------------------------------------------------------- shadow refs ----

class PlainRef:
    """Untracked mutable block ref (x/a/b/mask/y/idx blocks) backed by
    a numpy array — kernels read/write it like a pallas Ref."""

    def __init__(self, arr):
        self._a = np.array(arr)

    shape = property(lambda self: self._a.shape)
    dtype = property(lambda self: self._a.dtype)
    value = property(lambda self: self._a)

    def __getitem__(self, ix):
        return self._a[_np_index(ix)]

    def __setitem__(self, ix, val):
        self._a[_np_index(ix)] = np.asarray(val)

    def __jax_array__(self):          # jnp.zeros_like(y_ref) etc.
        import jax.numpy as jnp
        return jnp.asarray(self._a)


class _DS:
    """Shadow pl.ds: a (start, size) window."""

    def __init__(self, start, size):
        self.start, self.size = int(start), int(size)


def _np_index(ix):
    """A pallas-style index (pl.ds windows, slices, ints) as a numpy
    index."""
    if isinstance(ix, _DS):
        return slice(ix.start, ix.start + ix.size)
    if isinstance(ix, tuple):
        return tuple(_np_index(i) for i in ix)
    return ix


class _SrcSlice:
    def __init__(self, arr, ix):
        self._arr, self._ix = arr, ix

    def read(self):
        return self._arr[_np_index(self._ix)].copy()


class HBMRef:
    """HBM-resident operand: only `.at[pl.ds(...)]` source windows."""

    def __init__(self, arr):
        self._a = np.asarray(arr)

    shape = property(lambda self: self._a.shape)
    dtype = property(lambda self: self._a.dtype)

    @property
    def at(self):
        return _HBMAt(self._a)


class _HBMAt:
    def __init__(self, arr):
        self._arr = arr

    def __getitem__(self, ix):
        return _SrcSlice(self._arr, ix)


def _slot_of(ix):
    """Leading-axis slot index of a ref access, or None for whole-
    buffer access."""
    if isinstance(ix, tuple):
        ix = ix[0] if ix else None
    if ix is None or ix is Ellipsis or isinstance(ix, slice):
        return None
    try:
        return int(ix)
    except (TypeError, ValueError):
        return None


class TrackedVMEM:
    """Double-buffer scratch: slot states on the leading axis."""

    def __init__(self, san: Sanitizer, name: str, shape, dtype):
        self.san, self.name = san, name
        self._a = np.zeros(shape, dtype)
        self.state = [IDLE] * shape[0]
        self.pending = [None] * shape[0]      # sem key of active copy

    shape = property(lambda self: self._a.shape)
    dtype = property(lambda self: self._a.dtype)

    @property
    def at(self):
        return _VmemAt(self)

    def _slots(self, ix):
        s = _slot_of(ix)
        return range(len(self.state)) if s is None else (s,)

    def __getitem__(self, ix):
        for s in self._slots(ix):
            if self.state[s] != READY:
                self.san.report(
                    "dma-read-not-ready",
                    f"compute reads {self.name}[{s}] while it is "
                    f"{self.state[s]} — garbage on real hardware")
        return self._a[ix]

    def __setitem__(self, ix, val):
        for s in self._slots(ix):
            if self.state[s] == INFLIGHT:
                self.san.report(
                    "dma-slot-overwrite",
                    f"compute writes {self.name}[{s}] while a copy "
                    f"into it is in flight")
        self._a[ix] = np.asarray(val)


class _VmemAt:
    def __init__(self, buf):
        self._buf = buf

    def __getitem__(self, slot):
        return _DstSlot(self._buf, int(slot))


class _DstSlot:
    def __init__(self, buf, slot):
        self.buf, self.slot = buf, slot


class ShadowSem:
    def __init__(self, name: str):
        self.name = name

    @property
    def at(self):
        return _SemAt(self)


class _SemAt:
    def __init__(self, sem):
        self._sem = sem

    def __getitem__(self, slot):
        return (self._sem, int(slot))


class ShadowCopy:
    """One make_async_copy descriptor driving the state machine."""

    def __init__(self, san, src, dst, sem):
        self.san, self.src, self.dst, self.sem = san, src, dst, sem

    def start(self):
        buf, slot = self.dst.buf, self.dst.slot
        if buf.state[slot] == INFLIGHT:
            self.san.report(
                "dma-start-without-wait",
                f"start() reuses {buf.name}[{slot}] while its previous "
                f"copy is still in flight")
        buf.state[slot] = INFLIGHT
        buf.pending[slot] = self.sem
        # data lands now — the *state* decides whether reads were safe
        buf._a[slot] = self.src.read()

    def wait(self):
        buf, slot = self.dst.buf, self.dst.slot
        if buf.state[slot] != INFLIGHT:
            self.san.report(
                "dma-double-wait",
                f"wait() on {buf.name}[{slot}] with no copy in flight "
                f"(state {buf.state[slot]})")
            return
        if buf.pending[slot] is not None \
                and buf.pending[slot][0] is not self.sem[0]:
            self.san.report(
                "dma-double-wait",
                f"wait() on {buf.name}[{slot}] pairs semaphore "
                f"{self.sem[0].name} with a copy started on "
                f"{buf.pending[slot][0].name}")
        buf.state[slot] = READY
        buf.pending[slot] = None


# -------------------------------------------------- shadow namespaces ----

class _VMEMSpec:
    def __init__(self, shape, dtype):
        self.shape, self.dtype = tuple(shape), np.dtype(dtype)


class _SemSpec:
    def __init__(self, shape):
        self.shape = shape


class _SemTypeNS:
    @staticmethod
    def DMA(shape):
        return _SemSpec(shape)


class _ShadowPl:
    def __init__(self, san: Sanitizer):
        self._san = san

    def program_id(self, axis):
        return self._san.program_id

    @staticmethod
    def when(cond):
        def deco(f):
            if bool(cond):
                f()
            return f
        return deco

    @staticmethod
    def ds(start, size):
        return _DS(start, size)

    @staticmethod
    def multiple_of(x, _m):
        return x

    def run_scoped(self, body, **kwargs):
        allocs = {}
        for name, spec in kwargs.items():
            if isinstance(spec, _VMEMSpec):
                allocs[name] = TrackedVMEM(self._san, name,
                                           spec.shape, spec.dtype)
            elif isinstance(spec, _SemSpec):
                allocs[name] = ShadowSem(name)
            else:
                raise TypeError(f"unshadowed scoped alloc {name}: "
                                f"{spec!r}")
        body(**allocs)
        for name, alloc in allocs.items():
            if not isinstance(alloc, TrackedVMEM):
                continue
            for s, st in enumerate(alloc.state):
                if st == INFLIGHT:
                    self._san.report(
                        "dma-inflight-at-exit",
                        f"{name}[{s}] copy still in flight at scope "
                        f"exit — its semaphore leaks past the kernel")


class _ShadowPltpu:
    def __init__(self, san: Sanitizer):
        self._san = san
        self.SemaphoreType = _SemTypeNS()

    @staticmethod
    def VMEM(shape, dtype):
        return _VMEMSpec(shape, dtype)

    def make_async_copy(self, src, dst, sem):
        return ShadowCopy(self._san, src, dst, sem)


class _LaxShim:
    """jax.lax with fori_loop unrolled to a Python loop so ref
    mutations execute eagerly instead of being traced away."""

    def __getattr__(self, name):
        import jax
        return getattr(jax.lax, name)

    @staticmethod
    def fori_loop(lo, hi, body, init, **_kw):
        val = init
        for i in range(int(lo), int(hi)):
            val = body(i, val)
        return val


class _JaxShim:
    lax = _LaxShim()

    def __getattr__(self, name):
        import jax
        return getattr(jax, name)


@contextlib.contextmanager
def shadow_env(module, san: Sanitizer):
    """Swap `module`'s pl/pltpu/jax globals for the shadow surface."""
    saved = {k: getattr(module, k) for k in ("pl", "pltpu", "jax")}
    module.pl = _ShadowPl(san)
    module.pltpu = _ShadowPltpu(san)
    module.jax = _JaxShim()
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(module, k, v)


# ------------------------------------------------------------ drivers ----

def run_fused_shadow(x, wc, A, Bp, *, activation: str, kc: int,
                     cats: bool = False, active_mask=None,
                     wq=None, wsc=None, wout=None, case: str = "fused"):
    """Shadow-execute the real kernels/cluster_gather_ffn._fused_kernel
    over its full grid, hand-slicing each BlockSpec window exactly as
    fused_cold_ffn's specs do. Returns (findings, y, idx)."""
    from repro.kernels import cluster_gather_ffn as cg

    x = np.asarray(x, np.float32)
    wc = np.asarray(wc)
    G, nc_g, cs, R, D = wc.shape
    B = x.shape[0]
    blk = nc_g * cs
    quant, mixed = wq is not None, wout is not None
    stored = np.asarray(wq if quant else wc)
    w_flat = stored.reshape(G * blk, R, D)
    wsc_flat = None if wsc is None else np.asarray(wsc).reshape(G * blk, R)
    wout_flat = None if wout is None else np.asarray(wout).reshape(
        G * blk, R, D)
    mask = (np.ones((B, 1), np.float32) if active_mask is None
            else np.asarray(active_mask, np.float32).reshape(B, 1))
    Bp = np.asarray(Bp)

    san = Sanitizer(case)
    y_ref = PlainRef(np.zeros((B, D), np.float32))
    idx_ref = PlainRef(np.zeros((G, kc), np.int32))
    w_hbm = HBMRef(cg._kernel_layout(w_flat))
    wout_hbm = None if wout_flat is None else HBMRef(
        cg._kernel_layout(wout_flat, np.float32))
    with shadow_env(cg, san):
        for g in range(G):
            san.program_id = g
            refs = [PlainRef(x), w_hbm, PlainRef(np.asarray(A)),
                    PlainRef(Bp[:, g * blk:(g + 1) * blk]),
                    PlainRef(mask)]
            if quant:
                refs.append(PlainRef(wsc_flat[g * blk:(g + 1) * blk]))
                if mixed:
                    refs.append(wout_hbm)
            refs += [y_ref, idx_ref]
            cg._fused_kernel(*refs, activation=activation, gated=R == 3,
                             cats=cats, kc=kc, nc_g=nc_g, cs=cs,
                             quant=quant, mixed=mixed)
    return san.findings, y_ref.value, idx_ref.value


def run_mini_shadow(kernel, *, case: str, kc: int = 4, cs: int = 8,
                    d: int = 16, b: int = 2):
    """Drive a mini kernel (signature (x_ref, w_hbm, y_ref, *, kc, cs))
    through the shadow surface — the mutant-kernel harness. Returns
    (findings, y, x, w)."""
    module = sys.modules[kernel.__module__]
    rng = np.random.default_rng(0)
    x = rng.standard_normal((b, cs)).astype(np.float32)
    w = rng.standard_normal((kc * cs, d)).astype(np.float32)
    san = Sanitizer(case)
    y_ref = PlainRef(np.zeros((b, d), np.float32))
    with shadow_env(module, san):
        san.program_id = 0
        kernel(PlainRef(x), HBMRef(w), y_ref, kc=kc, cs=cs)
    return san.findings, y_ref.value, x, w


def fidelity_findings(case: str, got, want, idx_got=None, idx_want=None,
                      atol: float = 1e-4) -> list:
    """Compare shadow outputs against the real interpret-mode kernel's;
    divergence means the harness no longer executes the shipped math
    and its race verdicts are void."""
    findings = []
    if not np.allclose(np.asarray(got), np.asarray(want),
                       rtol=1e-4, atol=atol):
        err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
        findings.append(Finding(
            "dma-shadow-fidelity", f"semantic/{case}", 1,
            f"shadow y diverges from interpret-mode kernel "
            f"(max abs err {err:.3g})"))
    if idx_got is not None and not np.array_equal(
            np.asarray(idx_got), np.asarray(idx_want)):
        findings.append(Finding(
            "dma-shadow-fidelity", f"semantic/{case}", 1,
            "shadow cluster selection diverges from interpret-mode "
            "kernel"))
    return findings


def sweep_fused_cold_ffn() -> list:
    """Sanitize the shipped fused kernel over every storage dtype
    (incl. the int4 sidecar's paired descriptors) and both gating
    modes, with a fidelity check against interpret mode per cell."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import fused_cold_ffn

    G, nc_g, cs, R, D, r, B, kc = 2, 3, 8, 3, 16, 4, 2, 2
    ks = jax.random.split(jax.random.key(7), 6)
    x = jax.random.normal(ks[0], (B, D), jnp.float32)
    wc = jax.random.normal(ks[1], (G, nc_g, cs, R, D), jnp.float32)
    A = jax.random.normal(ks[2], (D, r), jnp.float32)
    Bp = jax.random.normal(ks[3], (r, G * nc_g * cs), jnp.float32)
    wq = jax.random.randint(ks[4], wc.shape, -127, 128).astype(jnp.int8)
    wsc = jax.random.uniform(ks[5], wc.shape[:-1], jnp.float32,
                             0.01, 0.1)
    wout = (wq.astype(jnp.float16) * 0.01).astype(jnp.float16)

    cells = [("fp16", False, {}), ("fp16-cats", True, {}),
             ("int8", False, {"wq": wq, "wsc": wsc}),
             ("int4-mixed", False, {"wq": wq, "wsc": wsc,
                                    "wout": wout})]
    findings = []
    for name, cats, quant in cells:
        case = f"dma/fused_cold_ffn/{name}"
        got, y, idx = run_fused_shadow(
            x, wc, A, Bp, activation="silu", kc=kc, cats=cats,
            case=case, **quant)
        findings.extend(got)
        y_ref, idx_ref = fused_cold_ffn(
            x, wc, A, Bp, activation="silu",
            mode="cats" if cats else "relu", kc=kc, interpret=True,
            **quant)
        findings.extend(fidelity_findings(
            case, y, y_ref, idx_got=idx, idx_want=idx_ref))
    return findings
