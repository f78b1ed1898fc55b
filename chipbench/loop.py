"""The serving loop: drive an engine with one traffic mix on the host
clock, and record what every step and every request did.

The engine is used only through `submit(prompt, max_new)` -> uid and
`step()` -> a result with `tokens` {uid: token}, `admitted`, `finished`
and `trace` (None when there is no work). Each step is timed on the
host clock; `step()` has returned only after the sampled tokens were
on the host, so its end follows the device. Host spans name what the
host was doing, for the device trace's idle gaps.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

from chipbench.traffic import Traffic

SPAN_STEP = "engine.step"
SPAN_STORAGE = "storage_plane.step"
SPAN_SUBMIT = "submit"
SPAN_WAIT = "wait_arrivals"
SPANS = (SPAN_STEP, SPAN_STORAGE, SPAN_SUBMIT, SPAN_WAIT)


@dataclass
class StepRec:
    t0: float
    t1: float
    tokens: dict                  # uid -> served token
    admitted: list
    finished: list
    trace: object = None          # the activation trace the step returned
    storage_s: float = None       # host seconds inside the storage plane
    window: bool = False          # inside the measured window
    index: int = -1               # position in Run.steps


@dataclass
class ReqRec:
    index: int
    uid: int
    prompt: np.ndarray
    max_new: int
    due: float                    # host clock at which it was due
    submitted: float
    first_step: int = None        # index of the step that served token 0
    tokens: list = field(default_factory=list)
    token_t: list = field(default_factory=list)


class Timer:
    """Accumulates host seconds spent in one wrapped callable."""

    def __init__(self):
        self.seconds = 0.0

    def wrap(self, fn, span: str):
        def timed(*a, **kw):
            t = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(span):
                    return fn(*a, **kw)
            finally:
                self.seconds += time.perf_counter() - t
        return timed


@dataclass
class Run:
    steps: list = field(default_factory=list)
    requests: dict = field(default_factory=dict)   # uid -> ReqRec
    window: tuple = None                           # (t_start, t_end)
    lateness: list = field(default_factory=list)   # open loop, seconds
    pool_exhausted: bool = False


class Driver:
    def __init__(self, engine, traffic: Traffic, storage_timer: Timer = None,
                 clock=time.perf_counter, sleep=time.sleep):
        self.engine, self.traffic = engine, traffic
        self.timer, self.clock, self.sleep = storage_timer, clock, sleep
        self.run = Run()
        self.next_k = 0

    # ---------------------------------------------------- requests ----
    def _submit_next(self, due: float):
        if self.next_k >= self.traffic.pool:
            self.run.pool_exhausted = True
            return
        req = self.traffic.request(self.next_k)
        self.next_k += 1
        with jax.profiler.TraceAnnotation(SPAN_SUBMIT):
            uid = self.engine.submit(req.prompt, max_new=req.max_new)
        now = self.clock()
        self.run.requests[uid] = ReqRec(req.index, uid, req.prompt,
                                        req.max_new, due, now)
        if not self.traffic.closed:
            self.run.lateness.append(now - due)

    def _submit_due(self, now: float):
        while (self.next_k < self.traffic.pool
               and self.t_start + self.traffic.due[self.next_k] <= now):
            self._submit_next(self.t_start + self.traffic.due[self.next_k])

    def _step(self, in_window: bool):
        s0 = self.timer.seconds if self.timer else None
        t0 = self.clock()
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            r = self.engine.step()
        t1 = self.clock()
        if r is None:
            return None
        rec = StepRec(t0, t1, dict(r.tokens), list(r.admitted),
                      list(r.finished), getattr(r, "trace", None),
                      None if s0 is None else self.timer.seconds - s0,
                      in_window, len(self.run.steps))
        i = rec.index
        self.run.steps.append(rec)
        for uid, tok in rec.tokens.items():
            q = self.run.requests[uid]
            if q.first_step is None:
                q.first_step = i
            q.tokens.append(int(tok))
            q.token_t.append(t1)
        if self.traffic.closed:
            for _ in rec.finished:
                self._submit_next(t1)
        return rec

    # -------------------------------------------------------- run ----
    def start(self):
        """Start the clients and serve one step, so the window opens
        on a running batch."""
        self.t_start = self.clock()
        if self.traffic.closed:
            for _ in range(int(self.traffic.mix["clients"])):
                self._submit_next(self.t_start)
        else:
            first = self.t_start + float(self.traffic.due[0])
            self._wait_until(first)
            self._submit_due(self.clock())
        self._step(False)

    def _wait_until(self, t: float):
        with jax.profiler.TraceAnnotation(SPAN_WAIT):
            while self.clock() < t:
                self.sleep(min(1e-3, max(t - self.clock(), 0.0)))

    def _serve(self, seconds: float, in_window: bool, min_steps: int = 0):
        """Serve until `seconds` have passed and `min_steps` steps ran;
        returns (start, close): the close is the end of the last step
        started, or `seconds` where the loop idles at the end."""
        t0 = self.clock()
        t_end = t0 + seconds
        last, n = t0, 0
        while True:
            now = self.clock()
            if now >= t_end and n >= min_steps:
                break
            if not self.traffic.closed:
                self._submit_due(now)
            rec = self._step(in_window)
            if rec is not None:
                last, n = rec.t1, n + 1
                continue
            if self.traffic.closed or self.next_k >= self.traffic.pool:
                break
            self._wait_until(min(self.t_start
                                 + self.traffic.due[self.next_k], t_end))
        return t0, max(last, min(self.clock(), t_end))

    def window(self, seconds: float) -> Run:
        """The measured window."""
        self.run.window = self._serve(seconds, True)
        return self.run

    def after(self, seconds: float, min_steps: int = 0) -> list:
        """Serve on past the window (the traced phase); returns the
        steps it ran, which no end-to-end metric counts."""
        i = len(self.run.steps)
        self._serve(seconds, False, min_steps)
        return self.run.steps[i:]
