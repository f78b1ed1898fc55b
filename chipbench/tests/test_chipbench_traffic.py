"""The traffic generator and the serving loop, on a stand-in engine and
a stand-in clock; and BENCHMARK.json against the benchmark's contract."""
import json
import re
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import spec
from chipbench.loop import Driver
from chipbench.traffic import Traffic

CHAT = spec.load_json(spec.HERE / "traffic" / "chat-c8.json")
OPEN = dict(CHAT, loop="open", pool=256,
            arrivals={"process": "gamma", "cv": 3.0, "rate_per_s": 40.0})


def _blocks(t, n):
    pairs = list(zip(t.prompt_lens.tolist(), t.max_new.tolist()))
    return [pairs[i:i + n] for i in range(0, len(pairs), n)]


@pytest.mark.parametrize("mix", [CHAT, OPEN], ids=["closed", "open"])
def test_same_seed_same_schedule_and_every_seed_the_same_work(mix):
    a, b = Traffic(mix, 2**31 + 17, 1000), Traffic(mix, 2**31 + 17, 1000)
    c = Traffic(mix, 5, 1000)
    assert (a.prompt_lens == b.prompt_lens).all()
    assert (a.max_new == b.max_new).all()
    for k in (0, 7, a.pool - 1):
        assert (a.request(k).prompt == b.request(k).prompt).all()
    if a.due is not None:
        assert np.array_equal(a.due, b.due)
        n = mix["block"]
        assert sorted(np.diff(a.due[:n], prepend=0.0)) == \
            pytest.approx(sorted(np.diff(c.due[:n], prepend=0.0)))
    # another seed: every block holds the same sizes, in another order
    n = mix["block"]
    for x, y in zip(_blocks(a, n), _blocks(c, n)):
        assert sorted(x) == sorted(y)
    assert not (a.prompt_lens == c.prompt_lens).all()
    k = next(k for k in range(a.pool)
             if a.prompt_lens[k] == c.prompt_lens[k])
    assert not (a.request(k).prompt == c.request(k).prompt).all()


def test_mix_shapes_and_clipping():
    t = Traffic(CHAT, 1, 1000)
    # the 32 quantiles of a log-normal of median 1020 and sigma 0.5,
    # each at the nearest length the program prefills
    assert t.shapes == [256, 512, 1024]
    for blk in _blocks(t, CHAT["block"]):
        lens, counts = np.unique([p for p, _ in blk], return_counts=True)
        assert lens.tolist() == t.shapes and counts.tolist() == [1, 7, 24]
        assert 120 <= np.median([o for _, o in blk]) <= 135
    assert t.max_new.min() >= 16
    assert (t.prompt_lens + t.max_new <= CHAT["engine"]["ctx_budget"]).all()
    assert t.pool == CHAT["pool"]


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


class FakeEngine:
    """Admits everything queued, emits one token per running request
    per step, each step taking `step_s` on the stand-in clock."""

    def __init__(self, clock, step_s):
        self.clock, self.step_s = clock, step_s
        self.queue, self.running, self.left = [], [], {}
        self.uid = 0

    def submit(self, prompt, max_new):
        self.uid += 1
        self.queue.append(self.uid)
        self.left[self.uid] = max_new
        return self.uid

    def step(self):
        if not (self.queue or self.running):
            return None
        self.clock.t += self.step_s
        adm, self.queue = self.queue, []
        self.running += adm
        toks = {u: 1 for u in self.running}
        done = []
        for u in self.running:
            self.left[u] -= 1
            if self.left[u] == 0:
                done.append(u)
        self.running = [u for u in self.running if u not in done]
        return SimpleNamespace(tokens=toks, admitted=adm, finished=done,
                               trace=None)


def test_closed_loop_times_each_request_from_when_it_was_due():
    clock = Clock()
    mix = dict(CHAT, clients=2)
    d = Driver(FakeEngine(clock, 0.01), Traffic(mix, 3, 100), clock=clock, sleep=clock.sleep)
    d.start()
    run = d.window(2.0)
    due = sorted(r.due for r in run.requests.values())
    assert due[:2] == [0.0, 0.0]
    for r in run.requests.values():
        if r.tokens:
            # the next request is due the moment the previous answer
            # completed, and its first token comes one step later
            assert r.token_t[0] == pytest.approx(r.due + 0.01)
    # at most `clients` requests are ever outstanding
    live = [r for r in run.requests.values()
            if len(r.tokens) < r.max_new]
    assert len(live) <= 2


def test_open_loop_is_timed_from_the_schedule_not_the_submit():
    clock = Clock()
    mix = dict(OPEN, arrivals=dict(OPEN["arrivals"], rate_per_s=50.0))
    traffic = Traffic(mix, 4, 100)
    # a slow server: steps of 0.1 s, far below the offered 50/s
    d = Driver(FakeEngine(clock, 0.1), traffic, clock=clock, sleep=clock.sleep)
    d.start()
    run = d.window(3.0)
    reqs = sorted(run.requests.values(), key=lambda r: r.index)
    for r in reqs:
        assert r.due == pytest.approx(traffic.due[r.index])
        assert r.submitted >= r.due
    assert max(run.lateness) > 0.05        # the generator ran late
    served = [r for r in reqs if r.tokens]
    ttft = [r.token_t[0] - r.due for r in served]
    assert max(ttft) > 0.1                 # queueing counts from due


def test_a_cell_is_added_with_files_and_entries_only(tmp_path):
    """An open-loop cell of new files and BENCHMARK.json entries,
    found by name, served by the loop as it is."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    here = tmp_path / "chipbench"
    (here / "traffic" / "bursts.json").write_text(json.dumps(
        dict(OPEN, arrivals=dict(OPEN["arrivals"], rate_per_s=5.0))))
    (here / "limits" / "smollm-135m.bursts.json").write_text(
        json.dumps({"limits": {"token_gap_mean": 0.001}}))
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "smollm-135m.bursts",
                           "config": "smollm-135m", "traffic": "bursts",
                           "chips": 1, "why": "bursts"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("smollm-135m.bursts", root=tmp_path)
    assert cell.traffic["loop"] == "open"
    assert cell.limits["limits"] == {"token_gap_mean": 0.001}
    assert {m["name"] for m in cell.per_layer} >= {"device_idle"}
    clock = Clock()
    traffic = Traffic(cell.traffic, 9, 100)
    d = Driver(FakeEngine(clock, 0.01), traffic, clock=clock,
               sleep=clock.sleep)
    d.start()
    run = d.window(4.0)
    assert len(run.requests) >= 10
    assert all(r.due == pytest.approx(traffic.due[r.index])
               for r in run.requests.values())


def test_reference_batches_hold_a_bounded_number_of_tokens():
    from chipbench import plain
    reqs = [SimpleNamespace(prompt=np.zeros(p, np.int32), tokens=[0] * o)
            for p, o in [(576, 300), (1792, 200), (1792, 10), (100, 50)]
            * 5]
    seen = 0
    for T, batch, real in plain.length_batches(reqs, 2048):
        assert len(batch) * T <= plain.BATCH_TOKENS
        assert all(len(r.prompt) + len(r.tokens) <= T for r in batch)
        seen += real
    assert seen == len(reqs)


def test_benchmark_file_meets_the_contract():
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert b["paths"] == ["chipbench"] and 1 <= b["run_seconds"] <= 51
    cfgs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and c["file"].startswith("chipbench/")
        body = spec.load_json(spec.ROOT / c["file"])
        assert body["source"].startswith(c["source"].split(" ")[0])
        assert all(name.match(k) for k in c["reduced"])
        assert (spec.HERE / "references"
                / f"{body['reference']}.py").exists()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and unit.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and unit.match(m["unit"])
        assert (spec.HERE / "metrics" / f"{m['name']}.py").exists()
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = spec.load_cell(w["name"])
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer and cell.limits["limits"]
