"""The engine's profiler spans: one ``loom.engine.step`` per step holding
one span per phase, the admissions' spans inside ``admit``, and the stats
that name each admitted request. The CPU profiler records host spans, so
this runs the smoke-config engine under ``jax.profiler`` here."""
import glob
import os

import jax
import numpy as np
from jax.profiler import ProfileData

from repro import configs
from repro.api import session as loom
from repro.core.policy import uniform_policy
from repro.runtime.batching import BatchingEngine

LEAVES = ("loom.engine.admit", "loom.engine.prefill", "loom.engine.scatter",
          "loom.engine.decode", "loom.engine.sync", "loom.engine.emit")


def _traced_run(trace_dir):
    """Three requests over two slots (the third waits for a slot), traced;
    returns the loom.* spans as (name, start, end, stats) and the
    requests as {request_id: prompt_len}."""
    cfg = configs.get("qwen3-1.7b", smoke=True)
    sess = loom.compile(cfg, uniform_policy(8, 8), mode="serve_packed",
                        backend="xla", rng=0)
    eng = BatchingEngine(sess, max_batch=2)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab, size=(n,)).astype(np.int32)
               for n in (5, 7, 6)]
    # compile outside the trace: one request through every program
    eng.submit(prompts[0], 2)
    eng.run(max_steps=20)
    jax.profiler.start_trace(trace_dir)
    try:
        handles = [eng.submit(p, g) for p, g in zip(prompts, (3, 2, 3))]
        eng.run(max_steps=50)
    finally:
        jax.profiler.stop_trace()
    for h in handles:
        h.result(timeout=0)
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for ln in plane.lines for e in ln.events
             if e.name.startswith("loom.")]
    reqs = {h.request_id: len(p) for h, p in zip(handles, prompts)}
    return spans, reqs


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_phase_spans_nest_and_name_the_requests(tmp_path):
    spans, reqs = _traced_run(str(tmp_path))
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    steps = by["loom.engine.step"]
    assert [s[3]["step_num"] for s in steps] == list(
        range(steps[0][3]["step_num"], steps[0][3]["step_num"] + len(steps)))
    # every leaf inside exactly one step; one admit and one emit a step
    for name in LEAVES:
        for leaf in by.get(name, []):
            assert sum(_inside(leaf, st) for st in steps) == 1, leaf
    assert len(by["loom.engine.admit"]) == len(steps)
    assert len(by["loom.engine.emit"]) == len(steps)
    assert len(by["loom.engine.decode"]) == len(by["loom.engine.sync"]) >= 3
    # admissions nest inside admit, one prefill and scatter per request
    admits = by["loom.engine.admit"]
    for name in ("loom.engine.prefill", "loom.engine.scatter"):
        assert len(by[name]) == len(reqs)
        for s in by[name]:
            assert any(_inside(s, a) for a in admits), s
    assert sum(a[3]["n_admitted"] for a in admits) == len(reqs)
    got = {s[3]["request_id"]: s[3]["prompt_len"]
           for s in by["loom.engine.prefill"]}
    assert got == reqs
    slots = {s[3]["request_id"]: s[3]["slot"]
             for s in by["loom.engine.prefill"]}
    assert slots == {s[3]["request_id"]: s[3]["slot"]
                     for s in by["loom.engine.scatter"]}
    assert set(slots.values()) <= {0, 1}
    # the phases of a step do not overlap, and decode comes before sync
    for st in steps:
        inner = sorted((s for s in spans if s[0] in ("loom.engine.admit",
                        "loom.engine.decode", "loom.engine.sync",
                        "loom.engine.emit") and _inside(s, st)),
                       key=lambda s: s[1])
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
        names = [s[0] for s in inner]
        assert names[0] == "loom.engine.admit"
        assert names[-1] == "loom.engine.emit"
        if "loom.engine.decode" in names:
            assert names[1:3] == ["loom.engine.decode", "loom.engine.sync"]
        dec = [s for s in inner if s[0] == "loom.engine.decode"]
        for d in dec:
            assert 1 <= d[3]["n_active"] <= 2
