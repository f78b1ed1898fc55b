"""Kernel `kernels/cluster_gather_ffn.py::fused_cold_ffn`: the least
time the chip could take for one call (operations over the bf16 peak
or HBM bytes over the HBM peak, whichever is larger; ops and bytes
counted from the served plan's shapes) over the mean device time of
the kernel's events in the traced window (%). None where the trace
holds no event of the kernel."""
from chipbench import counts

# The pallas_call's op is a custom call named after the kernel's jit:
# "%fused_cold_ffn.8 = (...) custom-call(...)".
PATTERN = r"^%?fused_cold_ffn[\w.]* = .* custom-call\("


def read(ctx):
    if ctx.trace_obj is None or not ctx.traced_steps:
        return None
    durs = ctx.kernel_durations(PATTERN)
    if not durs:
        return None
    bound, which, per_bucket = 0.0, {}, {}
    for s in ctx.traced_steps:
        b = ctx.bucket(s)
        n_hot, kc, cs = ctx.plans[b]
        call = counts.cold_kernel_call(
            rows=b, d_model=ctx.cfg["hidden_size"],
            rank=ctx.cfg["serve"]["predictor_rank"],
            n_cold=ctx.cfg["intermediate_size"] - n_hot,
            cluster_size=cs, kc=kc)
        t, w = counts.roofline_seconds(call["ops"], call["bytes"], ctx.peak)
        bound += t
        which[w] = which.get(w, 0) + 1
        per_bucket[b] = (call["ops"], call["bytes"], w)
    for b, (ops, nbytes, w) in sorted(per_bucket.items()):
        ctx.note(f"cold_ffn bucket {b}: {ops} ops, {nbytes} bytes per "
                 f"call, {w}-bound")
    bound /= len(ctx.traced_steps)
    mean = sum(durs) / len(durs)
    ctx.note(f"cold_ffn: {len(durs)} kernel events, mean {mean * 1e6:.3f} us, "
             f"roofline {bound * 1e6:.4f} us ({max(which, key=which.get)} bound)")
    return 100.0 * bound / mean
