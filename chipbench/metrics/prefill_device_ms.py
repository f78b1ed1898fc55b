"""Admission and prefill: device time of the ops traced under the
`prefill` named scope per request admitted in the traced phase, the
requests counted from the `group` arg of its `serve.prefill` spans
(ms). None where the program writes no `serve.*` span or no request
was admitted."""
from chipbench import program_trace as pt


def read(ctx):
    prog = pt.of(ctx)
    if prog is None:
        return None
    admitted = sum(args.get("group", 0)
                   for _, _, _, args in prog.named(pt.PREFILL))
    if not admitted:
        return None
    return 1e3 * prog.busy("prefill") / admitted
