"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Reads the cell from BENCHMARK.json and its files by name (spec.py),
makes the weights and the traffic from the seed, builds the engine,
warms every shape the traffic uses, starts the clients, serves for
`--seconds` on the host clock, then checks what was served against
the cell's plain reference. With --trace 1 the line carries the per-layer
metrics, host-clock ones from the window and device ones from a traced
phase that follows it (TRACE_SECONDS and at least TRACE_STEPS steps);
with --trace 0, the end-to-end ones. The last stdout line is
one JSON object; the numbers compared, with their limits, are the
last lines of stderr and the result's last key.

Exits non-zero with no result when JAX finds no TPU, fewer chips than
the cell asks for, or a device kind without published peaks.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TRACE_SECONDS, TRACE_STEPS = 2.0, 3      # the traced phase: both reached


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


class CompileWatch:
    """Backend compiles and persistent-cache hits, from JAX's events."""

    def __init__(self):
        import jax.monitoring
        self.compiles, self.compile_s = 0, 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _ev(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return (self.compiles, self.compile_s, self.hits, self.misses)


class HostWatch:
    """What the host did inside a span: the process's CPU seconds (all
    threads), context switches, and the garbage collector's passes and
    seconds."""

    def __init__(self):
        self.gc_n, self.gc_full, self.gc_s = 0, 0, 0.0
        self._t = None
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gc_n += 1
            self.gc_full += info["generation"] == 2
            self.gc_s += time.perf_counter() - self._t

    def snap(self):
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (time.perf_counter(), time.process_time(), ru.ru_nvcsw,
                ru.ru_nivcsw, self.gc_n, self.gc_full, self.gc_s)

    @staticmethod
    def line(a, b) -> str:
        d = [y - x for x, y in zip(a, b)]
        return (f"host in the window: {d[0]:.3f} s wall, {d[1]:.3f} s "
                f"process CPU, {d[2]} voluntary / {d[3]} involuntary "
                f"context switches, {d[4]} gc passes ({d[5]} full) "
                f"{d[6]:.4f} s")


def check(readings: dict, limits: dict):
    """-> ({name: {value, limit}}, whether every number is finite and
    within its limit)."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = readings.get(name)
        ok = ok and v is not None and math.isfinite(v) and v <= limit
        checks[name] = {"value": v, "limit": limit}
    return checks, ok


class Ctx:
    """What a per-layer metric reader may read."""

    def __init__(self, cfg, run, plans, buckets, peak, chips, trace=None,
                 trace_obj=None, traced=None):
        self.cfg, self.run, self.plans, self.buckets = cfg, run, plans, buckets
        self.peak, self.chips = peak, chips
        self.trace, self.trace_obj = trace, trace_obj
        self.traced = traced                      # (t0_ns, t1_ns, steps)
        self.traced_steps = traced[2] if traced else []
        # host-clock readers read the untraced window
        self.window_steps = [s for s in run.steps if s.window]
        self.window_s = run.window[1] - run.window[0]
        self.notes = []

    def note(self, msg):
        self.notes.append(msg)

    def bucket(self, step) -> int:
        n = len(step.tokens)
        return next((b for b in self.buckets if n <= b), self.buckets[-1])

    def position(self, uid, step) -> int:
        """Position of the token the step decoded for request uid."""
        r = self.run.requests[uid]
        return len(r.prompt) + step.index - r.first_step

    def kernel_durations(self, pattern):
        from chipbench import trace as tr
        t0, t1, _ = self.traced
        return tr.kernel_events(self.trace_obj, pattern, t0, t1)


def percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def end_to_end(run) -> dict:
    """decode_tok_s, itl_p95_ms, ttft_p50_ms from the host clock. The
    TTFT is a median: a window holds some 140 due requests, too few for
    a tail with ten beyond it; its p95 is logged with the stats."""
    t0, t1 = run.window
    win = [s for s in run.steps if s.window]
    toks = sum(len(s.tokens) for s in win)
    itl = []
    for r in run.requests.values():
        for a, b in zip(r.token_t, r.token_t[1:]):
            if a >= t0 and b <= t1:
                itl.append(b - a)
    ttft = []
    for r in run.requests.values():
        if t0 <= r.due < t1:
            got = r.token_t[0] if r.token_t and r.token_t[0] <= t1 else t1
            ttft.append(got - r.due)
    out = {"decode_tok_s": toks / (t1 - t0) if t1 > t0 else None,
           "itl_p95_ms": None if not itl else 1e3 * percentile(itl, 95),
           "ttft_p50_ms": None if not ttft else 1e3 * percentile(ttft, 50)}
    stats = {"window_s": t1 - t0, "tokens": toks, "steps": len(win),
             "gaps": len(itl), "requests_due": len(ttft),
             "itl_p50_ms": None if not itl else 1e3 * percentile(itl, 50),
             "ttft_p95_ms": None if not ttft else 1e3 * percentile(ttft, 95),
             "completed": sum(1 for s in win for _ in s.finished)}
    return out, stats


def device_info(peak_bytes):
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak_bytes)}


def memory_peak() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        try:
            st = d.memory_stats() or {}
        except Exception:          # the backend keeps no memory stats
            st = {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(cell, seed: int, seconds: float, trace: bool, peak: dict,
             t_process: float = None, use_cache: bool = True,
             control: bool = False) -> dict:
    """Everything after the look for a chip; returns the result dict.
    `control` also reads the reference's lower-precision control on
    the same served tokens (the limits' upper reading), and holds it to
    the same limits: "control" and "control_correct" in the result."""
    import jax
    from chipbench import engine as eng
    from chipbench import model, plain
    from chipbench import spec as spec_mod
    from chipbench import trace as trace_mod
    from chipbench.loop import SPANS, Driver
    from chipbench.traffic import Traffic

    t_process = time.perf_counter() if t_process is None else t_process
    if use_cache:
        from repro.launch.compile_cache import use_compile_cache
        log(f"compile cache: {use_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = CompileWatch()
    host = HostWatch()
    cfg, mix = cell.config, cell.traffic
    stamps = {"imports": time.perf_counter() - t_process}

    t = time.perf_counter()
    pc = model.program_config(cfg)
    weights = model.make_weights(pc, seed)
    jax.block_until_ready(weights)
    stamps["weights"] = time.perf_counter() - t
    log(f"weights: {model.nbytes(weights) / 2**30:.3f} GiB in "
        f"{stamps['weights']:.2f} s")

    t = time.perf_counter()
    engine, plans, timer = eng.build(cfg, pc, weights, mix, seed)
    stamps["engine"] = time.perf_counter() - t
    for b, (nh, kc, cs) in sorted(plans.items()):
        log(f"plan bucket {b}: n_hot {nh}, kc {kc}, cluster_size {cs}")

    traffic = Traffic(mix, seed, pc.vocab_size)
    t = time.perf_counter()
    c0 = watch.snap()
    n_warm = eng.warm_up(engine, mix, traffic.shapes, pc.vocab_size)
    c1 = watch.snap()
    stamps["warm_up"] = time.perf_counter() - t
    log(f"warm-up: {n_warm} steps in {stamps['warm_up']:.2f} s; "
        f"{c1[0] - c0[0]} backend compiles ({c1[1] - c0[1]:.2f} s), "
        f"persistent cache {c1[2] - c0[2]} hits / {c1[3] - c0[3]} misses")

    driver = Driver(engine, traffic, timer)
    t = time.perf_counter()
    driver.start()
    # set-up's objects live to the end: no collection walks them again
    gc.collect()
    gc.freeze()
    stamps["clients_start"] = time.perf_counter() - t

    c2, h2 = watch.snap(), host.snap()
    jax.config.update("jax_log_compiles", True)    # names any compile
    try:
        run = driver.window(seconds)
    finally:
        jax.config.update("jax_log_compiles", False)
    c3, h3 = watch.snap(), host.snap()
    log(HostWatch.line(h2, h3))
    setup_s = run.window[0] - t_process
    log(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.2f}" for k, v in stamps.items()))
    log(f"compiles inside the window: {c3[0] - c2[0]} "
        f"({c3[1] - c2[1]:.3f} s)")
    if not traffic.closed:
        late = run.lateness
        log(f"generator lateness: n {len(late)}, p50 "
            f"{percentile(late, 50)} s, max {max(late, default=0.0)} s")
    if run.pool_exhausted:
        log("traffic pool exhausted inside the window")
    traced = None
    if trace:
        # a phase of its own past the window: the profiler slows the
        # host, and writing its trace takes seconds
        tdir = ROOT / ".chipbench_tmp" / f"trace-{os.getpid()}"
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no event per Python call
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("traced_window"):
            traced = driver.after(TRACE_SECONDS, TRACE_STEPS)
        jax.profiler.stop_trace()
    peak_bytes = memory_peak()

    e2e, stats = end_to_end(run)
    log("window: " + json.dumps(stats))
    done = [r for r in run.requests.values() if r.tokens]
    log(f"requests: {len(run.requests)} submitted, {len(done)} served "
        f"tokens, {stats['completed']} completed in the window")

    # free the program's state before the reference runs
    engine.close()
    del engine, driver
    gc.collect()
    jax.clear_caches()

    served = plain.Served(requests=done, steps=run.steps, plans=plans,
                          buckets=tuple(sorted(mix["engine"]["buckets"])),
                          ctx_budget=int(mix["engine"]["ctx_budget"]))
    t = time.perf_counter()
    c4 = watch.snap()
    readings = cell.reference().readings(cfg, weights, served,
                                         control=control)
    c5 = watch.snap()
    log(f"reference: {time.perf_counter() - t:.2f} s over "
        f"{readings.get('requests')} requests, {readings.get('tokens')} "
        f"served tokens; {c5[0] - c4[0]} backend compiles "
        f"({c5[1] - c4[1]:.2f} s), persistent cache {c5[2] - c4[2]} hits "
        f"/ {c5[3] - c4[3]} misses")
    log("readings: " + json.dumps({k: v for k, v in readings.items()
                                   if k != "control"}))
    checks, ok = check(readings, cell.limits["limits"])
    correct = ok and bool(done)

    result = {"correct": correct,
              "attempted": len(run.requests), "failed": 0}
    if not trace:
        e2e["setup_s"] = setup_s
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if e2e.get(m["name"]) is not None}
        result["device"] = device_info(peak_bytes)
    else:
        tr = trace_mod.load(str(trace_mod.find_xplane(str(tdir))),
                            SPANS + ("traced_window",))
        wins = [(s, e) for n, s, e in tr.spans if n == "traced_window"]
        tr.spans = [sp for sp in tr.spans if sp[0] != "traced_window"]
        t0, t1 = wins[0] if wins else (
            min(s for _, s, _ in tr.spans), max(e for _, _, e in tr.spans))
        red = trace_mod.reduce(tr, t0, t1)
        ctx = Ctx(cfg, run, plans, served.buckets, peak, cell.chips,
                  trace=red, trace_obj=tr, traced=(t0, t1, traced))
        result["metrics"] = {}
        for m in cell.per_layer:
            v = spec_mod.metric_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        for n in ctx.notes:
            log(n)
        log(f"traced window {red['window_s']:.3f} s, busy "
            f"{red['busy_s']:.4f} s, {len(traced)} steps")
        log("idle by host span: " + json.dumps(red["idle_by_span"]))
        result["device"] = dict(device_info(peak_bytes),
                                busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(tdir, ignore_errors=True)
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    if control:
        result["readings"] = {k: v for k, v in readings.items()
                              if k != "control"}
        result["control"] = readings["control"]
        _, result["control_correct"] = check(readings["control"],
                                             cell.limits["limits"])
        log(f"control: {json.dumps(readings['control'])}; correct "
            f"{result['control_correct']}")
    result["checks"] = checks
    return result


def look_for_chip(chips: int):
    """The device kind's peaks, or an error: a TPU, enough chips, a
    kind with published peaks."""
    import jax
    from chipbench import counts
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    try:
        return counts.peaks(devs[0].device_kind)
    except KeyError as e:
        raise SystemExit(str(e)) from None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import spec
    cell = spec.load_cell(args.workload)
    peak = look_for_chip(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), peak,
                      t_process=T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
