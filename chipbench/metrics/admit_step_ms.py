"""Admission and prefill-on-admit: mean host-clock wall of the window's
engine steps that admitted at least one request (ms)."""


def read(ctx):
    walls = [s.t1 - s.t0 for s in ctx.window_steps if s.admitted]
    return 1e3 * sum(walls) / len(walls) if walls else None
