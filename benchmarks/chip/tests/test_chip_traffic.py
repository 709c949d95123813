"""The traffic generator and the open loop's clock."""
import json
import os
import sys
import time
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import manifest  # noqa: E402
import traffic  # noqa: E402
from repro.runtime.batching import streams  # noqa: E402,F401  (slow import)

MIX = {"kind": "lm_open_poisson", "rate_rps": 2.0, "schedule_seed": 7,
       "prompt_lens": [128, 256, 512], "prompt_p": [0.5, 0.3, 0.2],
       "output": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                  "min": 32, "max": 512},
       "max_batch": 32, "max_seq": 1024, "preroll_s": 10, "tail_s": 20}
BIG_SEED = 2**31 + 123456


def test_the_schedule_is_the_mix_s_and_the_tokens_the_seed_s():
    a = traffic.lm_schedule(MIX, 30)
    assert a == traffic.lm_schedule(MIX, 30)
    lens = [x.prompt_len for x in a]
    pa = traffic.prompt_tokens(BIG_SEED, lens, 151936)
    pb = traffic.prompt_tokens(BIG_SEED, lens, 151936)
    pc = traffic.prompt_tokens(BIG_SEED + 1, lens, 151936)
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
    assert not any(np.array_equal(x, y) for x, y in zip(pa, pc))
    assert all(1 <= t.min() and t.max() < 151936 for t in pa)
    other = traffic.lm_schedule(dict(MIX, schedule_seed=8), 30)
    assert [x.t for x in a] != [x.t for x in other]


def test_every_lm_mix_fits_and_prefills():
    d = os.path.join(os.path.dirname(traffic.__file__), "traffic")
    for name in sorted(os.listdir(d)):
        with open(os.path.join(d, name)) as f:
            mix = json.load(f)
        if mix["kind"] == "lm_open_poisson":
            traffic.check_fits(mix)
    with pytest.raises(ValueError, match="not a multiple"):
        traffic.check_fits(dict(MIX, prompt_lens=[256, 576], max_seq=2048))
    with pytest.raises(ValueError, match="max_seq"):
        traffic.check_fits(dict(MIX, max_seq=1000))


def test_the_schedule_holds_the_mix_s_proportions():
    a = traffic.lm_schedule(MIX, 30)
    b = traffic.lm_schedule(dict(MIX, schedule_seed=8), 30)
    for phase in ("preroll", "window", "tail"):
        pa = [x for x in a if x.phase == phase]
        pb = [x for x in b if x.phase == phase]
        assert Counter(x.prompt_len for x in pa) == \
            Counter(x.prompt_len for x in pb)
        assert Counter(x.gen_len for x in pa) == \
            Counter(x.gen_len for x in pb)
        assert np.isclose(np.ptp([x.t for x in pa]),
                          np.ptp([x.t for x in pb]), rtol=0.2)
    window = [x for x in a if x.phase == "window"]
    assert len(window) == 60          # 2 req/s over 30 s
    assert all(0 <= x.t < 30 for x in window)
    assert all(x.t < 0 for x in a if x.phase == "preroll")
    assert Counter(x.prompt_len for x in window) == {128: 30, 256: 18,
                                                     512: 12}
    assert all(32 <= x.gen_len <= 512 for x in a)
    assert 110 <= np.median([x.gen_len for x in window]) <= 146


class _Handle:
    def __init__(self, gen):
        self.gen, self.state, self.n_tokens = gen, "queued", 0

    @property
    def done(self):
        return self.n_tokens >= self.gen


class _Sched:
    depth = 0


class FakeEngine:
    """Admits every queued request at the next step and emits one token
    per request per step; step ``stall_at`` takes ``stall_s``."""

    def __init__(self, step_s, stall_at, stall_s):
        self.step_s, self.stall_at, self.stall_s = step_s, stall_at, stall_s
        self.active, self.queue, self.n = {}, [], 0
        self.scheduler = _Sched()

    def submit(self, prompt, gen):
        h = _Handle(gen)
        self.queue.append(h)
        self.scheduler.depth = len(self.queue)
        return h

    def step(self):
        time.sleep(self.stall_s if self.n == self.stall_at else self.step_s)
        self.n += 1
        for h in self.queue:
            h.state = "decoding"
            self.active[id(h)] = h
        self.queue = []
        self.scheduler.depth = 0
        for k, h in list(self.active.items()):
            h.n_tokens += 1
            if h.done:
                del self.active[k]


def test_ttft_runs_from_the_scheduled_arrival():
    lm = manifest.load_module("drivers", "lm")
    mix = dict(MIX, rate_rps=100.0, preroll_s=0.05, tail_s=0.2,
               prompt_lens=[4], prompt_p=[1.0],
               output={"dist": "uniform", "min": 3, "max": 3})
    cell = manifest.Cell("fake", 1, {"vocab_size": 100}, mix, [], [])
    eng = FakeEngine(step_s=0.002, stall_at=20, stall_s=0.3)
    t_win = time.perf_counter() + mix["preroll_s"]
    reqs = lm.requests(cell, 5, 0.6, t_win)
    loop = lm.OpenLoop(eng, reqs)
    loop.drive(t_win + 0.8)
    stall = [s for s in loop.steps if s.t1 - s.t0 > 0.2]
    assert len(stall) == 1
    s0, s1 = stall[0].t0, stall[0].t1
    hit = [r for r in reqs if s0 + 0.05 < r.due < s1 - 0.1 and r.times]
    calm = [r for r in reqs if r.due > s1 + 0.05 and r.times]
    assert hit and calm
    ttft = lambda r: r.times[0] - r.due
    # Requests due inside the stall wait for it, measured from their
    # scheduled arrival; from their submission they would look as fast
    # as the others.
    assert min(ttft(r) for r in hit) > 0.09
    assert max(ttft(r) for r in calm) < 0.05
    assert max(r.times[0] - r.submitted for r in hit) < 0.05
    rec = {"kind": "lm", "reqs": [r for r in reqs if r in hit or r in calm]}
    for r in rec["reqs"]:
        object.__setattr__(r, "arrival", r.arrival.__class__(
            r.arrival.t, r.arrival.prompt_len, r.arrival.gen_len, "window"))
    p95 = manifest.load_module("metrics", "ttft_p95_ms").read({"rec": rec})
    assert p95 > 90.0
