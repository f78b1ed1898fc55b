"""Decode step on the device: the union of device-operation intervals
in the traced window over the engine steps that ran in it (ms)."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_steps:
        return None
    return 1e3 * ctx.trace["busy_s"] / len(ctx.traced_steps)
