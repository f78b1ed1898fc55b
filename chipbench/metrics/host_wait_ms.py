"""Engine host step: the host blocked on the chip. Time inside the
program's `serve.pull` spans (each a read of a device array on the
host) per `serve.step` span of the traced phase (ms). None where the
program writes no such span."""
from chipbench import program_trace as pt


def read(ctx):
    prog = pt.of(ctx)
    if prog is None:
        return None
    split = pt.step_split(prog)
    return 1e-6 * sum(wait for _, wait, _ in split) / len(split)
