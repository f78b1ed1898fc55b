"""Engine host step: device-to-host reads per step, the mean of the
`pulls` count each `serve.step` span of the traced phase carries. None
where the program writes no such span."""
from chipbench import program_trace as pt


def read(ctx):
    prog = pt.of(ctx)
    if prog is None:
        return None
    pulls = [args.get("pulls") for _, _, _, args in prog.steps]
    if any(not isinstance(p, int) for p in pulls):
        raise ValueError(f"a {pt.STEP} span carries no int `pulls`: {pulls}")
    return sum(pulls) / len(pulls)
