"""The trace reduction on synthetic intervals and on a tiny trace
recorded on the CPU inside the test."""
import jax
import jax.numpy as jnp

from chipbench import trace as tr


def test_merge_gaps_and_labels():
    busy = tr.merge([(5, 8), (0, 2), (1, 3), (10, 12), (7, 9)])
    assert busy == [(0, 3), (5, 9), (10, 12)]
    assert tr.gaps(busy, 0, 14) == [(3, 5), (9, 10), (12, 14)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    spans = [("engine.step", 0, 20), ("storage_plane.step", 2, 6)]
    assert tr.label(4, spans) == "storage_plane.step"
    assert tr.label(9.5, spans) == "engine.step"
    assert tr.label(30, spans) == tr.OUTSIDE


def test_reduce_synthetic_two_devices():
    t = tr.Trace()
    t.ops[0] += [("fusion", 0, 4), ("kernel", 6, 8)]
    t.ops[1] += [("fusion", 0, 3)]
    t.spans += [("engine.step", 0, 10), ("storage_plane.step", 4, 6)]
    red = tr.reduce(t, 0, 10)
    assert red["window_s"] == 10e-9
    assert abs(red["busy_s"] - 4.5e-9) < 1e-18      # (6 + 3) / 2 devices
    idle = dict(red["idle_by_span"])
    assert abs(idle["storage_plane.step"] - 1e-9) < 1e-18
    assert abs(idle["engine.step"] - 4.5e-9) < 1e-18  # (2 + 7) / 2
    assert tr.kernel_events(t, "kern", 0, 10) == [2e-9]


def test_reduce_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("traced_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("engine.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)),
                ("engine.step", "traced_window"))
    win = [(s, e) for n, s, e in t.spans if n == "traced_window"]
    steps = [s for s in t.spans if s[0] == "engine.step"]
    assert len(win) == 1 and len(steps) == 3
    assert t.devices and sum(len(v) for v in t.ops.values()) >= 3
    red = tr.reduce(t, *win[0])
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"] and red["idle_gaps"]
    assert {lab for lab, _ in red["idle_by_span"]} <= {
        "engine.step", "traced_window", tr.OUTSIDE}
