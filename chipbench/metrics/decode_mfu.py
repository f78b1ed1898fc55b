"""The whole decode step's share of the chip's bf16 peak (%): model
FLOPs of every token the window's steps decoded (each at its own
context length, counted from the configuration), over the window's
seconds, over the peak of the device kind. A hot/cold plan's skipped
neurons are not subtracted, so the yardstick stays put when a change
moves the plan. The count is the cell's (`ctx.model_flops_token`):
the reference's own where it defines one."""


def read(ctx):
    flops = 0
    for s in ctx.window_steps:
        for uid in s.tokens:
            flops += ctx.model_flops_token(ctx.position(uid, s) + 1)
    secs = ctx.window_s
    if not flops or secs <= 0:
        return None
    return 100.0 * flops / secs / (ctx.peak["bf16_flops_per_s"] * ctx.chips)
