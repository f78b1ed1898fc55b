"""Probe: where do ep=4 and one chip part for deepseek-moe-16b on a TPU?

`chip_smoke.py --chips 4` checks every MoE layer of a 4-layer cut at
ep=4 against the same weights whole on one chip. This probe separates
the suspects at the real widths, with no engine:

  a. the 4-layer stacked expert tensor (L, 64, 1408, 3, 2048), past
     2**31 elements, fetched to the host (`jax.device_get`);
  b. the host copy put whole on one chip (`jax.device_put`);
  c. the sharded tensor moved whole to one chip, device to device;
  d. layer L-1 sliced out of the one-chip copy (one-device indexing
     past element 2**31);
  e. one MoE layer (`models.moe.apply_moe_ffn`, its own arrays) at
     ep=4 and on one chip, each against the host CPU, in fp32 at full
     matmul precision.

Each of a-d compares 4096 sampled elements exactly, and per-layer sums
of squares, against the device-side original. Needs four devices:

  python scripts/probe_ep_layer.py                 # four TPU chips
  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python scripts/probe_ep_layer.py --d-ff 22   # rehearsal on CPU
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-ff", type=int, default=1408,
                    help="cut only to rehearse off the chip")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    devs = jax.devices()
    if len(devs) < 4:
        print(f"needs 4 devices, found {len(devs)}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    from repro.configs import get_config
    from repro.launch.mesh import make_serving_mesh
    from repro.models.moe import apply_moe_ffn, init_moe_ffn

    print(f"platform {devs[0].platform} kind {devs[0].device_kind} "
          f"count {len(devs)}", flush=True)
    mesh = make_serving_mesh(4, 1)
    shape = (args.layers, 64, args.d_ff, 3, 2048)
    print(f"expert tensor {shape}: {np.prod(shape):.4g} elements "
          f"({np.prod(shape) / 2**31:.3f} x 2**31), "
          f"{np.prod(shape) * 4 / 2**30:.2f} GiB fp32", flush=True)
    rng = np.random.default_rng(0)
    idx = tuple(rng.integers(0, n, 4096) for n in shape)
    last = np.arange(4096) % 2 == 0        # half the samples in layer L-1
    idx = (np.where(last, shape[0] - 1, idx[0]),) + idx[1:]
    pick = jax.jit(lambda a, i: a[i])
    sq = jax.jit(lambda a: jnp.sum(jnp.square(a), axis=(1, 2, 3, 4)))

    w = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                out_shardings=NamedSharding(mesh, P(None, "model")))(
        jax.random.key(0))
    ref = np.asarray(pick(w, idx))
    ref_sq = np.asarray(sq(w), np.float64)

    def report(name, samples, sums):
        bad = int(np.sum(samples != ref))
        rel = np.abs(np.asarray(sums, np.float64) - ref_sq) / ref_sq
        bad_at = sorted(set(idx[0][samples != ref].tolist()))
        print(f"{name}: {bad}/4096 sampled elements differ (layers "
              f"{bad_at}); per-layer sum of squares relative diff "
              f"{[float(f'{r:.3g}') for r in rel]}", flush=True)

    host = jax.device_get(w)
    report("a. device_get", host[idx], [
        np.sum(np.square(layer, dtype=np.float64)) for layer in host])
    one = jax.device_put(host, devs[0])
    report("b. host -> one chip", np.asarray(pick(one, idx)),
           np.asarray(sq(one)))
    lay = jax.jit(lambda a: a[shape[0] - 1])(one)
    sel = idx[0] == shape[0] - 1
    print(f"d. layer {shape[0] - 1} sliced on one chip: "
          f"{int(np.sum(np.asarray(lay)[tuple(i[sel] for i in idx[1:])] != ref[sel]))}"
          f"/{int(sel.sum())} sampled elements differ", flush=True)
    del one, lay
    one = jax.device_put(w, devs[0])
    report("c. sharded -> one chip", np.asarray(pick(one, idx)),
           np.asarray(sq(one)))
    del one, w, host

    cfg = get_config("deepseek-moe-16b").replace(
        num_layers=1, d_ff=args.d_ff, param_dtype="float32",
        compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        p = init_moe_ffn(jax.random.key(1), cfg, jnp.float32)
        p = jax.device_get(p)
        x = np.asarray(jax.random.normal(jax.random.key(2), (64, 2048)))
        spec = {"router": P(), "experts": P("model"),
                "shared": {"w": P("model")}}
        p4 = jax.device_put(p, jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec,
            is_leaf=lambda s: isinstance(s, P)))
        with jax.set_mesh(mesh):
            y4, _, t4 = jax.jit(lambda q, v: apply_moe_ffn(
                q, v, cfg, collect_trace=True))(p4, x)
        y4, t4 = np.asarray(y4, np.float64), np.asarray(t4)
        del p4
        f1 = jax.jit(lambda q, v: apply_moe_ffn(q, v, cfg,
                                                collect_trace=True))
        y1, _, t1 = f1(jax.device_put(p, devs[0]), x)
        y1, t1 = np.asarray(y1, np.float64), np.asarray(t1)
        cpu = jax.devices("cpu")[0]
        yc, _, tc = f1(jax.device_put(p, cpu), jax.device_put(x, cpu))
        yc, tc = np.asarray(yc, np.float64), np.asarray(tc)
    for name, y, t in (("ep=4", y4, t4), ("one chip", y1, t1)):
        print(f"e. one MoE layer, {name} vs host CPU: routing equal "
              f"{np.array_equal(t, tc)}, relative L2 "
              f"{np.linalg.norm(y - yc) / np.linalg.norm(yc):.3g}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
