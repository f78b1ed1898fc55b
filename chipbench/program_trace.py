"""The program's own spans and named scopes in the traced phase's
profiler file, for the per-layer metrics that read them.

The serving engine writes a host span per phase of a step, all named
`serve.*`: `serve.step` around the whole step, carrying the step's
counts as its args, and inside it `serve.admit` (with `serve.prefill`
per prompt-length group), `serve.sample`, `serve.pull` (each read of a
device array on the host: the host waits for the chip), `serve.decode`,
`serve.price` and `serve.finish`. Its traced code runs under
`jax.named_scope`s, which the compiler keeps in each instruction's
op-name metadata (`jit(decode)/while/body/attention/...`). The profiler
file holds every program's optimized HLO; a device op event names its
instruction, and the device's "XLA Modules" line (on the CPU, the
event's own stats) its program. Ops the compiler made with no op name
(loop copies, rewrites) count as unscoped.

`trace.load` keeps none of this, so this module reads the same
`.xplane.pb` once more, while the harness still holds it, and keeps the
result on the metric context. Against a program that writes no
`serve.*` span (one without `repro.serving.spans`) it finds nothing,
and every reader of it returns None. Where the program writes them, a
missing profiler file, a traced phase with no `serve.step` span, or
device ops none of which maps to a program scope is an error, not a
metric left out.
"""
from __future__ import annotations

import bisect
import importlib.util
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

from chipbench import trace as tr
from chipbench.spec import ROOT

PREFIX = "serve."
STEP, PULL, PRICE, DECODE, PREFILL = (
    "serve.step", "serve.pull", "serve.price", "serve.decode",
    "serve.prefill")
ATTENTION = ("attention", "kv_write")
FFN = ("ffn_hot", "predictor", "cold_layout", "cold_kernel")
PREFILL_SCOPE = "prefill"
SCOPES = ATTENTION + FFN + ("experts", "lm_head", "sample", PREFILL_SCOPE)
DECODE_MODULE = re.compile(r"^jit_decode\b")
METADATA_PLANE = b"/host:metadata"
UNSCOPED = "unscoped"


def category(path: str) -> str:
    """The part of the step a device op belongs to, from the scopes in
    its op name: prefill, attention, ffn, other (a scope of its own),
    or unscoped (what the compiler inserted: loop copies, slices)."""
    parts = set(path.split("/")) if path else set()
    if PREFILL_SCOPE in parts:
        return "prefill"
    if parts & set(ATTENTION):
        return "attention"
    if parts & set(FFN):
        return "ffn"
    if parts & set(SCOPES):
        return "other"
    return UNSCOPED


@dataclass
class Program:
    """Spans (name, start, end, args) in ns, and device ops per device
    as (start, end, category, program, short name, op name), inside the
    traced window. Loops and calls, whose events hold their bodies' events,
    have the category None: they count as busy, in no category.
    `modules`: per device, (name, start, end) of each program run."""
    t0: float
    t1: float
    spans: list = field(default_factory=list)
    ops: dict = field(default_factory=lambda: defaultdict(list))
    modules: dict = field(default_factory=lambda: defaultdict(list))

    def named(self, name):
        return [s for s in self.spans if s[0] == name]

    @property
    def steps(self):
        return self.named(STEP)

    def inside(self, name, outer):
        """Spans named `name` that lie within span `outer`."""
        return [s for s in self.named(name)
                if outer[1] <= s[1] and s[2] <= outer[2]]

    def busy(self, cat) -> float:
        """Seconds in which an op of category `cat` ran, averaged over
        the devices (the union of its ops' intervals)."""
        if not self.ops:
            return 0.0
        total = 0.0
        for evs in self.ops.values():
            m = tr.merge(tr.clip([(s, e) for s, e, c, *_ in evs
                                  if c == cat], self.t0, self.t1))
            total += sum(e - s for s, e in m)
        return total * 1e-9 / len(self.ops)

    @property
    def scoped(self) -> bool:
        """Whether any device op carries a program scope."""
        return any(c not in (UNSCOPED, None) for evs in self.ops.values()
                   for _, _, c, *_ in evs)


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _msg(buf) -> dict:
    """Field number -> its values, of one protobuf message."""
    out = defaultdict(list)
    for f, v in _fields(buf):
        out[f].append(v)
    return out


def _ints(values) -> list:
    """A repeated integer field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


def _text(values) -> str:
    return bytes(values[0]).decode() if values else ""


def hlo_op_names(path: str) -> dict:
    """{program: {instruction: op name}} from the optimized HLO that the
    profiler keeps for every program it saw, in its `/host:metadata`
    plane, under the program's name there (`jit_decode(<id>)`, as the
    device's "XLA Modules" line names its runs). `ProfileData` shows no
    event metadata of that plane, so this reads the file's protobuf
    wire format: XSpace.planes (1); XPlane.name (2), event_metadata (4)
    and stat_metadata (5), maps of key (1) to value (2);
    XEventMetadata.name (2), stats (5); XStat.metadata_id (1),
    bytes_value (6); XStatMetadata.name (2); then the HloProto."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for plane in _msg(space)[1]:
        fields = _fields(plane)
        # fields come in number order: the name before the maps
        if next((bytes(v) for f, v in fields if f == 2), b"") \
                != METADATA_PLANE:
            continue
        events, stat_names = [], {}
        for f, v in fields:
            if f in (4, 5):
                entry = _msg(v)
                value = _msg(entry[2][0]) if entry[2] else _msg(b"")
                if f == 4:
                    events.append(value)
                else:
                    stat_names[_ints(entry[1])[0]] = _text(value[2])
        out = {}
        for md in events:
            for stat in md[5]:
                st = _msg(stat)
                if stat_names.get(_ints(st[1])[0]) == "Hlo Proto" and st[6]:
                    out[_text(md[2])] = _hlo_op_names(st[6][0])
        return out
    return {}


def _hlo_op_names(hlo) -> dict:
    """{instruction: op name} of one HloProto: hlo_module (1);
    HloModuleProto.computations (3); HloComputationProto.instructions
    (2), id (5), root_id (6); HloInstructionProto.name (1), metadata
    (7), id (35), called_computation_ids (38); OpMetadata.op_name (2).
    A fusion has no op name of its own: it takes its fused
    computation's root's, or else the first one found in it."""
    comps, insts = {}, {}
    for module in _msg(hlo)[1]:
        for comp in _msg(module)[3]:
            c = _msg(comp)
            ids = []
            for ins in c[2]:
                i = _msg(ins)
                op = _text(_msg(i[7][0])[2]) if i[7] else ""
                iid = _ints(i[35])[0] if i[35] else None
                insts[iid] = (_text(i[1]), op, _ints(i[38]))
                ids.append(iid)
            root = _ints(c[6])[0] if c[6] else None
            comps[_ints(c[5])[0] if c[5] else None] = [root] + ids
    done = {}

    def resolve(iid, depth=0):
        if iid not in done:
            _, op, called = insts[iid]
            done[iid] = op
            if not op and depth < 8:
                for j in (j for cid in called for j in comps.get(cid, ())):
                    if j in insts and resolve(j, depth + 1):
                        done[iid] = done[j]
                        break
        return done[iid]
    return {insts[iid][0]: resolve(iid) for iid in insts}


def load(path: str, t0: float, t1: float) -> Program:
    """The `serve.*` spans and the device ops of [t0, t1] (ns), each
    op with the category of the scopes in its op name."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    prog = Program(t0, t1)
    dev = re.compile(r"^/device:[A-Z]+:(\d+)$")
    raw, host_raw = defaultdict(list), defaultdict(list)
    for plane in data.planes:
        m = dev.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                d = int(m.group(1))
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e <= t0 or s >= t1:
                        continue
                    if line.name == "XLA Ops":
                        raw[d].append((s, e, ev.name, None, None))
                    else:
                        prog.modules[d].append((ev.name, s, e))
            elif not m:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name.startswith(PREFIX):
                        if s >= t0 and e <= t1:
                            prog.spans.append((ev.name, s, e, tr._stats(ev)))
                    elif e > s and e > t0 and s < t1:
                        st = tr._stats(ev)
                        if "hlo_op" in st:      # the CPU runs ops on the host
                            host_raw[0].append((
                                s, e, ev.name, st["hlo_op"],
                                f"{st.get('hlo_module')}"
                                f"({st.get('program_id')})"))
    if not raw:
        raw = host_raw
    names = hlo_op_names(path)
    for d, evs in raw.items():
        runs = sorted((s, e, n) for n, s, e in prog.modules.get(d, ()))
        starts = [r[0] for r in runs]
        for s, e, name, instr, program in evs:
            short, opcode = tr.op_name(name)
            if program is None:         # the module run the op lies in
                k = bisect.bisect_right(starts, s) - 1
                program = runs[k][2] if k >= 0 and s < runs[k][1] else ""
                instr = name.partition(" = ")[0].lstrip("%")
            # a CPU op's name is its instruction's: "while.25"
            opcode = opcode or instr.rpartition(".")[0]
            path_ = names.get(program, {}).get(instr, "")
            cat = None if opcode in tr.CONTAINERS else category(path_)
            prog.ops[d].append((s, e, cat, program, short, path_))
    prog.spans.sort(key=lambda sp: (sp[1], -sp[2]))
    return prog


def xplane_of(ctx):
    """The traced phase's profiler file, where the harness keeps it
    until the readers are done (`run.py`: .chipbench_tmp/trace-<pid>)."""
    d = ROOT / ".chipbench_tmp" / f"trace-{os.getpid()}"
    try:
        return tr.find_xplane(str(d))
    except FileNotFoundError as e:
        raise FileNotFoundError(
            f"{e}: the traced phase's profiler file is not where "
            "run.py kept it") from None


def writes_spans() -> bool:
    """Whether the program under test writes `serve.*` spans: it has
    the module that names them."""
    return importlib.util.find_spec("repro.serving.spans") is not None


def of(ctx):
    """The Program of this traced run, read once per run; None where
    the run is untraced or the program writes no `serve.*` span."""
    if hasattr(ctx, "_program"):
        return ctx._program
    prog = None
    if ctx.traced is not None:
        prog = load(xplane_of(ctx), ctx.traced[0], ctx.traced[1])
        if not prog.steps:
            if writes_spans():
                raise RuntimeError(
                    f"the program writes {PREFIX}* spans, but the traced "
                    f"phase holds no {STEP} span")
            prog = None
        elif not prog.scoped:
            raise RuntimeError(
                f"the traced phase holds {len(prog.steps)} {STEP} spans, "
                "but no device op maps to a program scope: no op name "
                f"was read from the profiler file's {METADATA_PLANE!r} "
                "plane")
    ctx._program = prog
    if prog is not None:
        for line in report(prog, ctx):
            ctx.note(line)
    return prog


def step_split(prog) -> list:
    """Per `serve.step`: (wall, pulls, pricing) in ns."""
    out = []
    for st in prog.steps:
        wait = sum(e - s for _, s, e, _ in prog.inside(PULL, st))
        price = sum(e - s for _, s, e, _ in prog.inside(PRICE, st))
        out.append((st[2] - st[1], wait, price))
    return out


def idle_by_span(prog) -> dict:
    """Device-idle seconds of the window by the innermost `serve.*`
    span at each gap's midpoint (tr.OUTSIDE: in no such span),
    averaged over the devices."""
    spans = [(n, s, e) for n, s, e, _ in prog.spans]
    idle = defaultdict(float)
    for evs in prog.ops.values():
        m = tr.merge(tr.clip([(s, e) for s, e, *_ in evs],
                             prog.t0, prog.t1))
        for s, e in tr.gaps(m, prog.t0, prog.t1):
            idle[tr.label((s + e) / 2, spans)] += (e - s) * 1e-9
    n = max(len(prog.ops), 1)
    return {k: v / n for k, v in idle.items()}


def clock_check(prog) -> tuple:
    """(steps whose decode ops all lie inside [their serve.decode's
    start, the end of their last serve.pull], steps with decode ops):
    the program's spans and the device's events share one clock."""
    ok = n = 0
    for st in prog.steps:
        dec = prog.inside(DECODE, st)
        pulls = prog.inside(PULL, st)
        if not dec or not pulls:
            continue
        lo, hi = dec[0][1], pulls[-1][2]
        # the decode program's runs (or, where the trace has no module
        # line, its ops) that start while this step is the latest begun
        evs = [(s, e) for d in prog.modules.values() for mod, s, e in d
               if DECODE_MODULE.match(mod) and st[1] <= s < st[2]]
        if not prog.modules:
            evs = [(s, e) for d in prog.ops.values()
                   for s, e, _, mod, *_ in d
                   if DECODE_MODULE.match(mod) and st[1] <= s < st[2]]
        if not evs:
            continue
        n += 1
        ok += all(lo <= s and e <= hi for s, e in evs)
    return ok, n


def top_ops(prog, top: int = 10) -> list:
    """[short name, category, op name, seconds] of the ops with the most
    device time, averaged over the devices."""
    secs, where = defaultdict(float), {}
    for evs in prog.ops.values():
        for s, e, c, _, name, path in evs:
            if c is not None:
                secs[name] += (min(e, prog.t1) - max(s, prog.t0)) * 1e-9
                where[name] = (c, path)
    n = max(len(prog.ops), 1)
    best = sorted(secs.items(), key=lambda kv: -kv[1])[:top]
    return [[k, *where[k], v / n] for k, v in best]


def report(prog, ctx) -> list:
    """The stderr lines of a traced run: idle by innermost program span,
    the host step split against the harness's step wall, device time by
    scope and the top ops' scopes, the clock check."""
    idle = idle_by_span(prog)
    total = sum(idle.values())
    out = sorted(([k, round(v, 6)] for k, v in idle.items()),
                 key=lambda kv: -kv[1])
    share = 100.0 * idle.get(tr.OUTSIDE, 0.0) / total if total else 0.0
    lines = [f"idle by program span: {out}; {share:.2f}% of idle time "
             f"in no {PREFIX}* span"]
    split = step_split(prog)
    walls = [s.t1 - s.t0 for s in ctx.traced_steps]
    if split and walls:
        mean = [sum(x[i] for x in split) / len(split) * 1e-6
                for i in range(3)]
        lines.append(
            f"program steps: {len(split)} {STEP} spans, mean {mean[0]:.4f} "
            f"ms (pulls {mean[1]:.4f}, pricing {mean[2]:.4f}); the "
            f"harness's {len(walls)} traced steps, mean wall "
            f"{1e3 * sum(walls) / len(walls):.4f} ms")
    if prog.scoped:
        per = {c: round(1e3 * prog.busy(c) / len(prog.steps), 4)
               for c in ("attention", "ffn", "other", "prefill", UNSCOPED)}
        lines.append(f"device ms per {STEP} by scope: {per}")
        lines.append(f"top device ops by scope: {top_ops(prog)}")
    ok, n = clock_check(prog)
    if n:
        lines.append(f"clock check: {ok} of {n} steps' decode ops lie "
                     f"inside their {DECODE} start .. last {PULL} end")
    return lines
