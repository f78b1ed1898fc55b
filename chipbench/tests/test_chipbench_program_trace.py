"""The readers of the program's own spans and scopes: on a synthetic
traced phase, on a trace recorded on the CPU, and against the names the
program writes."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import program_trace as pt
from chipbench import spec
from chipbench import trace as tr

MS = 1_000_000                                      # ns


def _steps():
    """Two steps of 10 ms; the first admits a group of two."""
    sp = []
    for base, phases, args in (
            (0, [("serve.admit", 0, 2), ("serve.sample", 2, 3),
                 ("serve.pull", 3, 4), ("serve.decode", 4, 5),
                 ("serve.pull", 5, 8), ("serve.price", 8, 9.5),
                 ("serve.finish", 9.5, 10)], {"pulls": 2}),
            (10, [("serve.admit", 0, 0.5), ("serve.sample", 0.5, 1),
                  ("serve.pull", 1, 1.5), ("serve.decode", 1.5, 2),
                  ("serve.pull", 2, 6), ("serve.price", 6, 9),
                  ("serve.finish", 9, 10)], {"pulls": 2})):
        sp.append(("serve.step", base * MS, (base + 10) * MS, args))
        sp += [(n, (base + a) * MS, (base + b) * MS, {}) for n, a, b in phases]
    sp.append(("serve.prefill", 0.5 * MS, 1.5 * MS, {"group": 2}))
    return sorted(sp, key=lambda s: (s[1], -s[2]))


def _program(shift=0.0):
    """Device ops of the two steps; `shift` (ms) moves the first step's
    decode ops earlier than its serve.decode span."""
    prog = pt.Program(0, 20 * MS, spans=_steps())
    ops = [(0.6, 1.4, "prefill", "jit_prefill"),
           (4.0 - shift, 5.3, None, "jit_decode"),         # the layer loop
           (4.1 - shift, 4.6, "attention", "jit_decode"),
           (4.6, 4.8, "ffn", "jit_decode"),
           (4.8, 5.2, pt.UNSCOPED, "jit_decode"),
           (5.2, 5.3, "other", "jit_decode"),
           (12.1, 12.5, "attention", "jit_decode"),
           (12.5, 12.7, "ffn", "jit_decode"),
           (12.7, 13.0, pt.UNSCOPED, "jit_decode")]
    prog.ops[0] = [(a * MS, b * MS, c, m, f"op{i}", "")
                   for i, (a, b, c, m) in enumerate(ops)]
    return prog


class Ctx:
    """What the readers use of the harness's metric context; `prog`
    stands in for the Program `of` would read from the file."""

    def __init__(self, prog=None, traced=(0, 20 * MS, [])):
        if prog is not None:
            self._program = prog
        self.traced = traced
        self.traced_steps = []
        self.notes = []

    def note(self, msg):
        self.notes.append(msg)


@pytest.mark.parametrize("name,expected", [
    ("host_wait_ms", (1 + 3 + 0.5 + 4) / 2),
    ("host_work_ms", ((10 - 4 - 1.5) + (10 - 4.5 - 3)) / 2),
    ("host_syncs_per_step", 2.0),
    ("attention_device_ms", (0.5 + 0.4) / 2),
    ("ffn_device_ms", (0.2 + 0.2) / 2),
    ("unscoped_device_ms", (0.4 + 0.3) / 2),
    ("prefill_device_ms", 0.8 / 2),
])
def test_readers_on_a_synthetic_phase(name, expected):
    got = spec.metric_reader(name)(Ctx(_program()))
    assert got == pytest.approx(expected, rel=1e-9)


def test_host_syncs_per_step_needs_an_int_pulls_count():
    prog = _program()
    prog.spans = [(n, s, e, {} if n == "serve.step" else a)
                  for n, s, e, a in prog.spans]
    with pytest.raises(ValueError, match="no int `pulls`"):
        spec.metric_reader("host_syncs_per_step")(Ctx(prog))


def test_idle_by_span_clock_check_and_categories():
    prog = _program()
    # gaps labelled at their midpoints: 0.3, 2.7, 8.7 and 16.5 ms
    assert pt.idle_by_span(prog) == pytest.approx({
        "serve.admit": 0.6e-3, "serve.sample": 2.6e-3,
        "serve.price": (6.8 + 7.0) * 1e-3})
    assert pt.clock_check(prog) == (2, 2)
    assert pt.clock_check(_program(shift=0.5)) == (1, 2)
    assert pt.category("jit(decode)/while/body/attention/kv_write/x") \
        == "attention"
    assert pt.category("jit(prefill)/prefill/while/body/attention/dot") \
        == "prefill"
    assert pt.category("jit(decode)/while/body/cold_kernel/pallas_call") \
        == "ffn"
    assert pt.category("jit(decode)/lm_head/dot_general") == "other"
    assert pt.category("jit(decode)/while/body/dynamic_slice") == pt.UNSCOPED
    assert pt.category("") == pt.UNSCOPED


def test_clock_check_reads_program_runs_where_the_trace_has_them():
    prog = _program(shift=0.5)                 # its ops start too early
    prog.modules[0] = [("jit_decode(7)", 4.05 * MS, 5.3 * MS),
                       ("jit_argmax(2)", 2.5 * MS, 2.6 * MS),
                       ("jit_decode(7)", 12.05 * MS, 13.0 * MS)]
    assert pt.clock_check(prog) == (2, 2)
    prog.modules[0][2] = ("jit_decode(7)", 12.05 * MS, 16.5 * MS)
    assert pt.clock_check(prog) == (1, 2)


def test_report_lines():
    ctx = Ctx(_program())
    lines = pt.report(ctx._program, ctx)
    assert lines[0].startswith("idle by program span: [['serve.price'")
    assert "0.00% of idle time in no serve.* span" in lines[0]
    assert any(line.startswith("clock check: 2 of 2") for line in lines)
    assert any(line.startswith("top device ops by scope") for line in lines)


READERS = ("host_wait_ms", "host_work_ms", "host_syncs_per_step",
           "attention_device_ms", "ffn_device_ms", "unscoped_device_ms",
           "prefill_device_ms")


def _scoped(x):
    with jax.named_scope("attention"):
        y = jnp.tanh(x @ x)
    with jax.named_scope("ffn_hot"):
        y = jnp.sin(y @ x)
    return y.sum()


def _unscoped(x):
    return jnp.sin(jnp.tanh(x @ x) @ x).sum()


def _record(tmp_path, names, fn=_scoped):
    f = jax.jit(fn)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("traced_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation(names[0], pulls=1) as sp:
                with jax.profiler.TraceAnnotation(names[1]):
                    f(x).block_until_ready()
                sp.set_metadata(rows=1)
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    win = next(s for s in tr.load(path, ("traced_window",)).spans)
    return path, win


def test_of_reads_a_recorded_trace_once(tmp_path, monkeypatch):
    path, (_, t0, t1) = _record(tmp_path, ("serve.step", "serve.pull"))
    monkeypatch.setattr(pt, "xplane_of", lambda ctx: path)
    ctx = Ctx(traced=(t0, t1, []))
    prog = pt.of(ctx)
    assert len(prog.steps) == 3
    assert [s[3] for s in prog.steps] == [{"pulls": 1, "rows": 1}] * 3
    assert all(len(prog.inside("serve.pull", s)) == 1 for s in prog.steps)
    assert ctx.notes and pt.of(ctx) is prog
    assert spec.metric_reader("host_syncs_per_step")(ctx) == 1.0
    # the CPU's op events name their program and instruction, and the
    # file holds each program's HLO with the scopes in its op names
    assert prog.scoped
    assert {c for evs in prog.ops.values() for _, _, c, *_ in evs} >= {
        "attention", "ffn"}
    assert spec.metric_reader("attention_device_ms")(ctx) > 0
    assert spec.metric_reader("ffn_device_ms")(ctx) > 0
    names = pt.hlo_op_names(path)
    scoped = next(v for k, v in names.items() if k.startswith("jit__scoped"))
    assert any(p.endswith("attention/dot_general") for p in scoped.values())


def test_of_finds_nothing_without_program_spans(tmp_path, monkeypatch):
    """A program that writes no serve.* span and has no module naming
    them (the harness's own spans only): every reader returns None and
    nothing raises."""
    path, (_, t0, t1) = _record(tmp_path, ("engine.step", "submit"))
    monkeypatch.setattr(pt, "xplane_of", lambda ctx: path)
    monkeypatch.setattr(pt, "writes_spans", lambda: False)
    for name in READERS:
        assert spec.metric_reader(name)(Ctx(traced=(t0, t1, []))) is None
    assert pt.of(Ctx(traced=None)) is None


@pytest.mark.parametrize("case,error,cause", [
    ("no file", FileNotFoundError, "not where run.py kept it"),
    ("no step span", RuntimeError, "holds no serve.step span"),
    ("no scope", RuntimeError, "no device op maps to a program scope"),
])
def test_readers_raise_where_the_program_writes_spans(
        case, error, cause, tmp_path, monkeypatch):
    """Where the program writes serve.* spans, a profiler file that is
    not found, a traced phase without them, or device ops none of
    which carries a scope fails every reader, naming the cause."""
    assert pt.writes_spans()
    monkeypatch.setattr(pt, "ROOT", tmp_path / "checkout")
    t0, t1 = 0, 1
    if case != "no file":
        names = (("engine.step", "submit") if case == "no step span"
                 else ("serve.step", "serve.pull"))
        path, (_, t0, t1) = _record(tmp_path / "trace", names, fn=_unscoped)
        monkeypatch.setattr(pt, "xplane_of", lambda ctx: path)
    for name in READERS:
        with pytest.raises(error, match=cause):
            spec.metric_reader(name)(Ctx(traced=(t0, t1, [])))


def test_the_names_read_are_the_names_the_program_writes():
    from repro.serving import spans
    read = {pt.STEP, pt.PULL, pt.PRICE, pt.DECODE, pt.PREFILL}
    assert read <= set(spans.SPANS)
    assert all(s.startswith(pt.PREFIX) for s in spans.SPANS)
    assert set(pt.SCOPES) == set(spans.SCOPES)
    assert "pulls" in spans.COUNTS
    assert pt.DECODE_MODULE.match("jit_decode")
