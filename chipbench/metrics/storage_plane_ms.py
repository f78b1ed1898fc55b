"""Storage plane: host-clock seconds inside `StoragePlane.step`, wrapped
from the harness, per engine step of the window (ms). None where the
engine has no storage plane to wrap."""


def read(ctx):
    secs = [s.storage_s for s in ctx.window_steps if s.storage_s is not None]
    if not secs or len(secs) != len(ctx.window_steps):
        return None
    return 1e3 * sum(secs) / len(secs)
