"""Decode step: device time of the ops under no program scope (what
the compiler puts in: the layer scan's slices, copies and write-backs;
the embedding; eager dispatches), per `serve.step` of the traced phase
(ms): the union of their intervals, averaged over the chips. None where
the program writes no `serve.*` span."""
from chipbench import program_trace as pt


def read(ctx):
    prog = pt.of(ctx)
    if prog is None:
        return None
    return 1e3 * prog.busy(pt.UNSCOPED) / len(prog.steps)
