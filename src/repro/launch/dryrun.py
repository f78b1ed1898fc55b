"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input shape) on the production meshes, record
memory/cost/collective artifacts for the roofline (deliverable g).

MUST set XLA_FLAGS before any jax import — the host platform locks its
device count on first init.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
      --shape decode_32k --mesh pod --out artifacts/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all   # full sweep
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs import get_config, ASSIGNED_ARCHS, INPUT_SHAPES  # noqa: E402
from repro.launch.mesh import make_production_mesh                  # noqa: E402
from repro.launch import input_specs as ispec                       # noqa: E402
from repro.models.model import build_model                          # noqa: E402
from repro.optim.adamw import AdamW                                 # noqa: E402
from repro.train.steps import make_train_step                       # noqa: E402
from repro.core.planner import build_plan                           # noqa: E402


_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes of every `dtype[d0,d1,...]` in an HLO type expression."""
    total = 0
    for m in re.finditer(r"(\w+)\[([0-9,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-collective-kind result bytes (per device) from optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+(%?)(" +
                     "|".join(_COLLECTIVES) + r")(-start|-done)?\(", line)
        if not m:
            continue
        kind = m.group(3)
        if m.group(4) == "-done":
            continue                       # avoid double count of async pairs
        out[kind] += _shape_bytes(m.group(1))
        counts[kind] += 1
    return {"bytes": out, "counts": counts}


def decode_plan_for(cfg, groups: int):
    """Hybrid plan for the decode dry-run: per-shard grouped cold path."""
    if not cfg.sparse_ffn.enabled or cfg.family in ("ssm", "moe"):
        return None
    plan = build_plan(cfg, groups=groups).plan_for_batch(1)
    return plan


def adapt_moe_groups(cfg, mesh):
    """MoE configs dispatch within data-local token groups: retie
    `moe_dispatch_groups` to the mesh's replica rows
    (launch.mesh.dispatch_groups — the shared helper both dry-run
    paths and the serving engine use). Non-MoE configs pass through."""
    if not cfg.num_experts:
        return cfg
    from repro.launch.mesh import dispatch_groups
    return cfg.replace(moe_dispatch_groups=dispatch_groups(mesh))


def lower_target(arch: str, shape_name: str, multi_pod: bool,
                 verbose: bool = True) -> dict:
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "ok": False}
    t0 = time.time()
    try:
        shape = INPUT_SHAPES[shape_name]
        cfg = ispec.adapt_config(get_config(arch), shape)
        if cfg.param_count() > 5e10:
            # bf16 Adam moments so the 314B/405B train state fits
            opt = AdamW(moment_dtype="bfloat16")
            fsdp = True
        else:
            opt = AdamW()
            fsdp = False
        mesh = make_production_mesh(multi_pod=multi_pod)
        cfg = adapt_moe_groups(cfg, mesh)
        model = build_model(cfg)
        groups = mesh.shape["model"]

        with jax.set_mesh(mesh):
            pspecs = ispec.param_specs(model, cfg, mesh,
                                       fsdp=fsdp and shape.kind == "train")
            batch = ispec.input_specs(cfg, shape, mesh)

            if shape.kind == "train":
                ospecs = jax.tree.map(
                    lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype,
                                                    sharding=sd.sharding),
                    jax.eval_shape(opt.init, pspecs))
                step = make_train_step(model, opt)
                lowered = jax.jit(step).lower(pspecs, ospecs, batch)
            elif shape.kind == "prefill":
                lowered = jax.jit(model.prefill).lower(pspecs, batch)
            else:
                plan = decode_plan_for(cfg, groups)
                cspecs = ispec.cache_specs(model, cfg, shape, mesh)
                fn = lambda p, t, c: model.decode_step(p, t, c, plan)  # noqa
                lowered = jax.jit(fn).lower(pspecs, batch["tokens"], cspecs)
                if plan:
                    rec["plan"] = {"n_hot": plan.n_hot, "k_cold": plan.k_cold,
                                   "groups": plan.groups,
                                   "cluster_size": plan.cluster_size}
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

            ca = compiled.cost_analysis() or {}
            rec["flops_per_device"] = float(ca.get("flops", -1.0))
            rec["bytes_per_device"] = float(ca.get("bytes accessed", -1.0))
            ma = compiled.memory_analysis()
            if ma is not None:
                rec["memory"] = {
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                    "alias_bytes": int(ma.alias_size_in_bytes),
                }
            rec["collectives"] = parse_collectives(compiled.as_text())
            rec["n_devices"] = mesh.size
            rec["ok"] = True
    except Exception as e:  # record failures as artifacts, not crashes
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    if verbose:
        status = "OK " if rec["ok"] else "FAIL"
        print(f"[{status}] {arch} x {shape_name} x {rec['mesh']} "
              f"({rec['total_s']}s)", flush=True)
        if not rec["ok"]:
            print("   ", rec["error"], flush=True)
    return rec


# ----------------------------------------------------------- cost probe ----
#
# XLA's cost analysis counts a while-loop body ONCE regardless of trip
# count (verified empirically), so the scanned dry-run under-reports
# FLOPs/bytes/collectives by ~the layer count. The probe lowers two
# UNROLLED reduced-depth variants (whole pattern groups for the hybrid)
# with single-chunk flash attention — the lowered HLO then contains no
# loops at all — and extrapolates linearly in depth:
#     cost(L) = base + L * per_layer   (exact: HLO cost is affine in L)

def _probe_depths(cfg):
    if cfg.block_pattern:
        p = len(cfg.block_pattern)
        return p, 2 * p                      # whole groups, no remainder
    return 2, 4


def _probe_cfg(cfg, L):
    kw = {"num_layers": L}
    if cfg.num_encoder_layers:
        kw["num_encoder_layers"] = L
    return cfg.replace(**kw)


def _cost_of(arch, shape_name, cfg, multi_pod):
    """Lower+compile one variant, return (flops, bytes, coll bytes/counts)."""
    from repro.models import blocks as _blocks
    from repro.models import attention as _attn
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = adapt_moe_groups(cfg, mesh)
    model = build_model(cfg)
    groups = mesh.shape["model"]
    opt = AdamW(moment_dtype="bfloat16" if cfg.param_count() > 5e10
                else "float32")
    _blocks.UNROLL = True
    _attn.FLASH_FULL_BLOCKS = True
    try:
        with jax.set_mesh(mesh):
            pspecs = ispec.param_specs(model, cfg, mesh,
                                       fsdp=shape.kind == "train"
                                       and cfg.param_count() > 5e10)
            batch = ispec.input_specs(cfg, shape, mesh)
            if shape.kind == "train":
                ospecs = jax.eval_shape(opt.init, pspecs)
                step = make_train_step(model, opt)
                lowered = jax.jit(step).lower(pspecs, ospecs, batch)
            elif shape.kind == "prefill":
                lowered = jax.jit(model.prefill).lower(pspecs, batch)
            else:
                plan = decode_plan_for(cfg, groups)
                cspecs = ispec.cache_specs(model, cfg, shape, mesh)
                fn = lambda p, t, c: model.decode_step(p, t, c, plan)  # noqa
                lowered = jax.jit(fn).lower(pspecs, batch["tokens"], cspecs)
            compiled = lowered.compile()
            ca = compiled.cost_analysis() or {}
            txt = compiled.as_text()
            coll = parse_collectives(txt)
            return (float(ca.get("flops", 0.0)),
                    float(ca.get("bytes accessed", 0.0)),
                    coll["bytes"], coll["counts"], mesh.size,
                    model_traffic_bytes(txt))
    finally:
        _blocks.UNROLL = False
        _attn.FLASH_FULL_BLOCKS = False


def probe_target(arch: str, shape_name: str, multi_pod: bool = False,
                 verbose: bool = True) -> dict:
    rec = {"arch": arch, "shape": shape_name, "kind": "probe",
           "mesh": "2x16x16" if multi_pod else "16x16", "ok": False}
    t0 = time.time()
    try:
        shape = INPUT_SHAPES[shape_name]
        cfg = ispec.adapt_config(get_config(arch), shape)
        L_full = cfg.num_layers
        l1, l2 = _probe_depths(cfg)
        f1, b1, c1, n1, ndev, t1 = _cost_of(arch, shape_name,
                                            _probe_cfg(cfg, l1), multi_pod)
        f2, b2, c2, n2, _, t2 = _cost_of(arch, shape_name,
                                         _probe_cfg(cfg, l2), multi_pod)
        dL = l2 - l1

        def extrap(v1, v2):
            per = (v2 - v1) / dL
            base = v1 - l1 * per
            return base + L_full * per

        rec["flops_per_device"] = extrap(f1, f2)
        rec["bytes_per_device"] = extrap(b1, b2)
        rec["traffic_bytes_per_device"] = extrap(t1, t2)
        rec["collectives"] = {
            "bytes": {k: extrap(c1[k], c2[k]) for k in c1},
            "counts": {k: extrap(n1[k], n2[k]) for k in n1},
        }
        rec["probe_depths"] = [l1, l2]
        rec["n_devices"] = ndev
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    if verbose:
        status = "OK " if rec["ok"] else "FAIL"
        print(f"[{status}] probe {arch} x {shape_name} ({rec['total_s']}s)",
              flush=True)
        if not rec["ok"]:
            print("   ", rec["error"], flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="unrolled cost probe for the roofline")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    if args.probe:
        out = args.out if args.out != "artifacts/dryrun" \
            else "artifacts/probe"
        os.makedirs(out, exist_ok=True)
        archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) \
            else [args.arch]
        shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
            else [args.shape]
        n_fail = 0
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}__{shape}"
                path = os.path.join(out, tag + ".json")
                if os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            print(f"[SKIP] probe {tag} (cached)", flush=True)
                            continue
                rec = probe_target(arch, shape)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                n_fail += 0 if rec["ok"] else 1
        print(f"probe done; failures: {n_fail}", flush=True)
        raise SystemExit(1 if n_fail else 0)

    os.makedirs(args.out, exist_ok=True)
    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            print(f"[SKIP] {tag} (cached)", flush=True)
                            continue
                rec = lower_target(arch, shape, mp)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                n_fail += 0 if rec["ok"] else 1
    print(f"done; failures: {n_fail}", flush=True)
    raise SystemExit(1 if n_fail else 0)




# ----------------------------------------------- traffic-model bytes ----
#
# 'bytes accessed' from XLA:CPU counts dtype-convert copies that exist
# only because the CPU backend lowers bf16 dots as f32 (verified in
# §Perf iteration 4: a single (N,R,D) bf16 weight was converted to f32
# 40x in the llama3 long_500k probe). The TPU MXU consumes bf16
# natively. `model_traffic_bytes` re-prices the HLO: compute/data ops
# count operands at their *root* (pre-convert/bitcast/reshape) dtypes;
# layout and dtype artifacts count zero.

# dtype/layout artifacts are transparent for pricing (consumers price
# operands at the artifact's ROOT); slices terminate resolution (their
# own, smaller, result type is the right price for consumers).
_ARTIFACT_OPS = {"convert", "bitcast", "copy", "transpose", "reshape",
                 "broadcast", "get-tuple-element", "tuple"}
_SKIP_OPS = _ARTIFACT_OPS | {"slice", "parameter", "constant", "iota",
                             "while", "conditional", "call", "after-all",
                             "partition-id", "custom-call"}


def model_traffic_bytes(hlo_text: str) -> float:
    types, src = {}, {}
    ops = []
    line_re = re.compile(
        r"\s*(%?[\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*?)\)")
    for line in hlo_text.splitlines():
        m = line_re.match(line)
        if not m:
            continue
        name, ts, kind, args = m.groups()
        name = name.lstrip("%")
        types[name] = ts
        refs = re.findall(r"%?([\w.\-]+)", args)
        operands = [r for r in refs if r in types]
        if kind in _ARTIFACT_OPS and operands:
            src[name] = operands[0]
        ops.append((name, ts, kind, operands))

    def root(n):
        seen = 0
        while n in src and seen < 50:
            n = src[n]
            seen += 1
        return n

    total = 0.0
    for _name, ts, kind, operands in ops:
        if kind in _SKIP_OPS:
            continue
        rb = _shape_bytes(ts)
        if kind in ("dot", "fusion", "dynamic-update-slice",
                    "dynamic-slice", "gather", "scatter", "concatenate",
                    "reduce", "sort", "select-and-scatter") \
                or kind in _COLLECTIVES:
            ob = sum(_shape_bytes(types.get(root(o), "")) for o in operands)
            total += rb + ob
        else:
            total += rb          # top-level elementwise: result only
    return total


if __name__ == "__main__":
    main()
