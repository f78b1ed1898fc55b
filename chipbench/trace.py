"""Reduce a profiler trace (`.xplane.pb`) to the benchmark's numbers.

Read with `jax.profiler.ProfileData` alone. Device operations are the
events of each TPU plane's "XLA Ops" line; on a trace with no device
plane (the CPU) they are the host events that carry an `hlo_op` stat.
Host spans are the harness's own `TraceAnnotation`s. From them:

* busy: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices;
* per-operation device time, summed by operation (loops and calls,
  whose events hold their bodies' events, left out);
* idle gaps: the complement of busy inside the window, each labelled
  with the innermost harness span that holds its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

OUTSIDE = "outside_spans"
# Ops whose event spans the ops of their body: kept out of per-op time.
CONTAINERS = ("while", "call", "conditional")
_OPCODE = re.compile(r"[)}\]] ([a-z][\w\-]*)\(")


def op_name(text: str) -> tuple:
    """(short name, opcode) of a TPU op event, whose name is its HLO
    instruction text: 'copy.92 copy bf16[1,8,1280,3,64]'."""
    name, eq, rest = text.partition(" = ")
    m = _OPCODE.search(rest) if eq else None
    if not m:
        return text[:120], ""
    kind = "tuple" if rest.startswith("(") else rest.split("{", 1)[0]
    return f"{name.lstrip('%')} {m[1]} {kind[:60]}", m[1]


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def gaps(merged, t0, t1):
    """Idle intervals of [t0, t1] around merged busy intervals."""
    out, t = [], t0
    for s, e in merged:
        if s > t:
            out.append((t, min(s, t1)))
        t = max(t, e)
        if t >= t1:
            break
    if t < t1:
        out.append((t, t1))
    return [(s, e) for s, e in out if e > s]


def label(t, spans):
    """The innermost span (shortest) holding time t, by name."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else OUTSIDE


@dataclass
class Trace:
    ops: dict = field(default_factory=lambda: defaultdict(list))
    spans: list = field(default_factory=list)     # (name, start, end) ns

    @property
    def devices(self):
        return sorted(self.ops)


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):    # a stat the reader cannot decode
        return {}


def load(path: str, span_names=()) -> Trace:
    """Device op events per device and the named host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    dev = re.compile(r"^/device:[A-Z]+:(\d+)$")
    host_ops = defaultdict(list)
    for plane in data.planes:
        m = dev.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                for ev in line.events:
                    tr.ops[int(m.group(1))].append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
            elif not m:
                for ev in line.events:
                    if ev.name in span_names:
                        tr.spans.append((ev.name, ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
                    elif ev.duration_ns > 0 and "hlo_op" in _stats(ev):
                        host_ops[0].append((ev.name, ev.start_ns,
                                            ev.start_ns + ev.duration_ns))
    if not tr.ops:
        tr.ops.update(host_ops)
    return tr


def reduce(tr: Trace, t0: float, t1: float, top: int = 10) -> dict:
    """Busy and idle over [t0, t1] (ns), op times, labelled gaps."""
    window = (t1 - t0) * 1e-9
    busy, op_s, idle = [], defaultdict(float), defaultdict(float)
    longest = []
    for d in tr.devices:
        evs = [(s, e) for _, s, e in tr.ops[d]]
        m = merge(clip(evs, t0, t1))
        busy.append(sum(e - s for s, e in m) * 1e-9)
        for name, s, e in tr.ops[d]:
            short, op = op_name(name)
            if e > t0 and s < t1 and op not in CONTAINERS:
                op_s[short] += (min(e, t1) - max(s, t0)) * 1e-9
        for s, e in gaps(m, t0, t1):
            lab = label((s + e) / 2, tr.spans)
            idle[lab] += (e - s) * 1e-9
            longest.append((lab, (e - s) * 1e-9))
    n = max(len(busy), 1)
    ops = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / n,
        "window_s": window,
        "device_ops": [[k, v / n] for k, v in ops[:top]],
        "idle_by_span": sorted(([k, v / n] for k, v in idle.items()),
                               key=lambda kv: -kv[1])[:top],
        "idle_gaps": [list(g) for g in sorted(longest,
                                              key=lambda g: -g[1])[:top]],
    }


def kernel_events(tr: Trace, pattern: str, t0: float, t1: float):
    """Durations (s) of the device events whose name matches."""
    rx = re.compile(pattern)
    return [(e - s) * 1e-9 for d in tr.devices for name, s, e in tr.ops[d]
            if rx.search(name) and s >= t0 and e <= t1]
