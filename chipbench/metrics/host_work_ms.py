"""Engine host step: the Python the chip waits for. Time inside the
program's `serve.step` spans outside its `serve.pull` (waiting for the
chip) and `serve.price` (the storage plane, `storage_plane_ms`) spans,
per step of the traced phase (ms). None where the program writes no
such span."""
from chipbench import program_trace as pt


def read(ctx):
    prog = pt.of(ctx)
    if prog is None:
        return None
    split = pt.step_split(prog)
    return 1e-6 * sum(w - wait - price for w, wait, price in split) / len(split)
