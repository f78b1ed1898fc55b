"""Jaxpr-level invariant rules — the semantic half of repro-analyze.

The AST tier (collectives.py etc.) pattern-matches source; this tier
checks the *traced program*: each registered entry point
(trace_registry.py) is staged to a ClosedJaxpr under its declared mesh
and the rules below assert post-trace facts XLA will actually compile.
Where the AST psum counter is branch-heuristic, these counts are ground
truth — a psum inside a layer `scan` body appears exactly once in the
trace, i.e. once per layer.

Rules (each fires as a Finding with path "semantic/<entry name>"):

* jaxpr-collective-count — exact psum / all_gather equation counts
    match the entry's declaration (tp1 paths declare zero, tp2/ep2
    paths declare the single per-layer output reduction + the id
    gather the jnp cold path emits). Any extra collective is a §3 mesh
    -discipline regression; any missing one means the path silently
    stopped reducing across shards.
* jaxpr-collective-fp32 — every psum operand is float32 (XLA:CPU's
    bf16 all-reduce promotion crash, and reduction precision); every
    all_gather operand is integer (the cold path only gathers cluster
    *ids* — gathering activations would reintroduce the traffic the
    shard-local design removed).
* jaxpr-f64 — no float64/complex128 aval anywhere in the trace and no
    f64 captured const: a weak-type promotion to f64 doubles every
    buffer on the serving path.
* jaxpr-callback — no pure_callback / io_callback / debug_callback
    equation in clock-driven entries: a host callback inside a decode
    step stalls the device stream the deterministic event clock prices.
* jaxpr-const-capture — total bytes of consts closed over by the trace
    stay under the entry's cap: a weight array baked into the jaxpr is
    silently duplicated into every executable the bucket table holds.
* jaxpr-trace-error — the entry failed to trace at all (build or
    make_jaxpr raised); surfaced as a finding so the gate reports the
    broken registration instead of crashing.
"""
from __future__ import annotations

from repro.analysis.framework import Finding

__all__ = ["JAXPR_RULES", "iter_eqns", "collect_consts", "check_trace",
           "run_entries"]

JAXPR_RULES = ("jaxpr-collective-count", "jaxpr-collective-fp32",
               "jaxpr-f64", "jaxpr-callback", "jaxpr-const-capture",
               "jaxpr-trace-error")

# collective primitive names across jax releases (newer jax splits
# psum into variant primitives; match the closed set, not a prefix,
# so psum_scatter never counts as the output reduction)
_PSUM = {"psum", "psum2", "psum_invariant"}
_ALL_GATHER = {"all_gather", "all_gather_invariant"}
_CALLBACK = {"pure_callback", "io_callback", "debug_callback", "debug_print"}


def _subjaxprs(val):
    """Yield any jaxpr nested in one eqn param value (pjit/scan/cond
    carry ClosedJaxprs, shard_map a bare Jaxpr, cond a tuple)."""
    vals = val if isinstance(val, (tuple, list)) else (val,)
    for v in vals:
        inner = getattr(v, "jaxpr", v)       # ClosedJaxpr -> Jaxpr
        if hasattr(inner, "eqns"):
            yield v, inner


def iter_eqns(jaxpr):
    """Depth-first over every equation, descending into subjaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for _, inner in _subjaxprs(val):
                yield from iter_eqns(inner)


def collect_consts(closed):
    """Every const captured by the trace, top-level and nested
    (deduped by identity: nested ClosedJaxprs often alias the same
    buffers the outer trace closes over)."""
    seen, out = set(), []

    def visit(node):
        for c in getattr(node, "consts", ()):
            if id(c) not in seen:
                seen.add(id(c))
                out.append(c)
        inner = getattr(node, "jaxpr", node)
        for eqn in getattr(inner, "eqns", ()):
            for val in eqn.params.values():
                for closed_sub, _ in _subjaxprs(val):
                    visit(closed_sub)

    visit(closed)
    return out


def _is_f64(aval) -> bool:
    dt = str(getattr(aval, "dtype", ""))
    return dt in ("float64", "complex128")


def check_trace(entry, closed) -> list:
    """Run every jaxpr rule over one traced entry. `entry` is a
    trace_registry.TraceEntry; `closed` its ClosedJaxpr."""
    path = f"semantic/{entry.name}"
    findings = []
    n_psum = n_ag = 0
    bad_dtypes, f64_hit, callbacks = [], None, []
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in _PSUM:
            n_psum += 1
            for v in eqn.invars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and "float" in str(dt) \
                        and str(dt) != "float32":
                    bad_dtypes.append(f"psum over {dt}")
        elif name in _ALL_GATHER:
            n_ag += 1
            for v in eqn.invars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and not ("int" in str(dt)
                                           or str(dt) == "bool"):
                    bad_dtypes.append(f"all_gather over {dt}")
        elif name in _CALLBACK or "callback" in name:
            callbacks.append(name)
        if f64_hit is None:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and _is_f64(aval):
                    f64_hit = f"{name} touches {aval.dtype}"
                    break

    if (n_psum, n_ag) != (entry.psums, entry.all_gathers):
        findings.append(Finding(
            "jaxpr-collective-count", path, 1,
            f"traced {n_psum} psum / {n_ag} all_gather, declared "
            f"{entry.psums} / {entry.all_gathers}: the per-layer "
            f"collective budget drifted (§3 mesh discipline)"))
    for msg in bad_dtypes:
        findings.append(Finding(
            "jaxpr-collective-fp32", path, 1,
            f"{msg}: psums must reduce in f32, all_gathers must move "
            f"integer ids only"))
    if f64_hit is None:
        for c in collect_consts(closed):
            if _is_f64(c):
                f64_hit = f"captured const of dtype {c.dtype}"
                break
    if f64_hit:
        findings.append(Finding(
            "jaxpr-f64", path, 1,
            f"{f64_hit}: f64 promotion doubles every serving buffer"))
    if entry.clock_driven:
        for name in sorted(set(callbacks)):
            findings.append(Finding(
                "jaxpr-callback", path, 1,
                f"{name} traced into clock-driven code: host callbacks "
                f"stall the decode stream"))
    const_bytes = sum(getattr(c, "nbytes", 0)
                      for c in collect_consts(closed))
    if const_bytes > entry.const_cap_bytes:
        findings.append(Finding(
            "jaxpr-const-capture", path, 1,
            f"trace closes over {const_bytes} const bytes "
            f"(cap {entry.const_cap_bytes}): closure-baked arrays are "
            f"duplicated into every bucket executable"))
    return findings


def run_entries(entries) -> list:
    """Trace and check each entry; a trace failure becomes a
    jaxpr-trace-error finding rather than an exception."""
    findings = []
    for entry in entries:
        try:
            closed = entry.trace()
        except Exception as e:           # noqa: BLE001 - surfaced as finding
            findings.append(Finding(
                "jaxpr-trace-error", f"semantic/{entry.name}", 1,
                f"entry failed to trace: {type(e).__name__}: {e}"))
            continue
        findings.extend(check_trace(entry, closed))
    return findings
