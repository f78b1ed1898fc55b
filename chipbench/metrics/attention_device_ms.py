"""Attention: device time of the ops traced under the `attention`
named scope (its `kv_write` included) outside prefill, per
`serve.step` of the traced phase (ms): the union of their intervals,
averaged over the chips. None where the program writes
no `serve.*` span."""
from chipbench import program_trace as pt


def read(ctx):
    prog = pt.of(ctx)
    if prog is None:
        return None
    return 1e3 * prog.busy("attention") / len(prog.steps)
