"""Driver for ``kind: lm`` configurations: open-loop serving.

Set-up makes the weights from the seed, compiles the configuration's
route with ``loom.compile``, builds a ``BatchingEngine`` at the mix's
``max_batch`` and ``max_seq``, warms every prefill length of the mix and
the batched decode, and runs the open-loop pre-roll. The window then
drives ``engine.step()`` against the schedule: due requests are
submitted before each step, and each token is stamped with the end of
the step that emitted it. After the window the tail keeps the same load
until every request due in the window has its first token.

Once the program's state is freed, a sample of finished requests (drawn
from the seed, the longest among them) is compared with the plain
reference: prompt and served tokens go through the float32 forward, and
the widest gap by which a served token's logit lies below the best one,
in units of that position's logit spread, is held to the configuration's
limit (``checks.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

import checks as checks_mod
import manifest
import trace_reduce
import traffic as traffic_mod

# Each request due in the window gets its first token, at the latest, this
# long after the window has closed; later ones count as never served.
FIRST_TOKEN_WAIT_S = 60.0


def program_config(c: dict, mix: dict):
    """The program's own config for ``c``, checked against the fields
    that the configuration's reference module says the file fixes
    (``program_fields``), with the KV length the mix asks for."""
    from repro import configs
    pc = configs.get(c["program_config"], smoke=c.get("program_smoke", False))
    want = manifest.load_module("refs", c["reference"]).program_fields(c)
    got = {k: getattr(pc, k) for k in want}
    if got != want:
        raise ValueError(f"program config {c['program_config']} is {got}, "
                         f"the benchmark's file says {want}")
    if mix["max_seq"] > c["max_position_embeddings"]:
        raise ValueError("the mix's max_seq exceeds max_position_embeddings")
    return dataclasses.replace(pc, max_seq=mix["max_seq"])


@dataclasses.dataclass
class Req:
    arrival: traffic_mod.Arrival
    prompt: np.ndarray
    due: float                      # scheduled arrival, host clock
    handle: object = None
    submitted: float | None = None
    admitted: float | None = None   # start of the step that took it in
    times: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    prefill_tokens: int             # prompt tokens admitted in the step
    contexts: list                  # valid KV length of each decoded row


def _warm(eng, mix: dict, vocab: int) -> None:
    """One short request per prompt length: compiles each prefill, the
    pool scatter, the batched decode and the host-side argmax."""
    for n in mix["prompt_lens"]:
        h = eng.submit(np.ones(n, np.int32) % vocab, 2)
        while eng.step():
            pass
        h.result(timeout=0)


class OpenLoop:
    """Steps the engine against the schedule, stamping every token."""

    def __init__(self, eng, reqs: list):
        from repro.runtime.batching import streams
        self.queued = streams.QUEUED
        self.eng, self.reqs = eng, reqs
        self.next = 0               # index of the next arrival
        self.live: list = []        # submitted and not finished
        self.steps: list[Step] = []
        self.memory = 0             # most bytes in use at a step's end

    def drive(self, until: float, traced: bool = False, stop=None,
              memory: bool = False) -> None:
        """Run until ``until`` (host clock) or until ``stop()`` holds;
        ``traced`` puts the benchmark's host spans in the trace, and
        ``memory`` samples the chips' bytes in use after every step."""
        eng, reqs = self.eng, self.reqs
        while True:
            now = time.perf_counter()
            if now >= until or (stop is not None and stop()):
                return
            with trace_reduce.span("bench.submit", traced):
                while self.next < len(reqs) and reqs[self.next].due <= now:
                    r = reqs[self.next]
                    r.handle = eng.submit(r.prompt, r.arrival.gen_len)
                    r.submitted = time.perf_counter()
                    self.live.append(r)
                    self.next += 1
            if not (eng.active or eng.scheduler.depth):
                nxt = reqs[self.next].due if self.next < len(reqs) else until
                with trace_reduce.span("bench.sleep", traced):
                    time.sleep(max(0.0, min(nxt, until) - time.perf_counter()))
                continue
            t0 = time.perf_counter()
            with trace_reduce.span("bench.engine_step", traced):
                eng.step()
            t1 = time.perf_counter()
            pre, ctx, keep = 0, [], []
            for r in self.live:
                if r.admitted is None and r.handle.state != self.queued:
                    r.admitted = t0
                    pre += r.arrival.prompt_len
                n = r.handle.n_tokens
                new = n - len(r.times)
                if new:
                    if new > (0 if r.times else 1):   # a decoded token
                        ctx.append(r.arrival.prompt_len + n - 1)
                    r.times += [t1] * new
                if not r.handle.done:
                    keep.append(r)
            self.live = keep
            self.steps.append(Step(t0, t1, pre, ctx))
            if memory:
                self.memory = max(self.memory, checks_mod.bytes_in_use())


def build(cell, seed: int, log, wrap=None):
    """Weights from the seed, the compiled session and a warm engine."""
    import jax

    import repro.api as loom
    from repro.core.policy import uniform_policy
    from repro.runtime.batching import BatchingEngine

    c, mix = cell.config, cell.traffic
    if mix["kind"] != "lm_open_poisson":
        raise ValueError(f"the lm driver runs no {mix['kind']!r} traffic")
    traffic_mod.check_fits(mix)
    ref = manifest.load_module("refs", c["reference"])
    pcfg = program_config(c, mix)
    route = c["route"]
    t = time.perf_counter()
    params, specs = ref.program_tree(ref.make_weights(seed, c))
    sess = loom.compile(pcfg, uniform_policy(route["a_bits"], route["w_bits"]),
                        mode=route["mode"], backend=route["backend"],
                        params=params, specs=specs)
    del params
    jax.block_until_ready(sess.params)
    log(f"weights+compile {time.perf_counter() - t:.3f} s")
    if wrap is not None:
        sess = wrap(sess)
    eng = BatchingEngine(sess, max_batch=mix["max_batch"],
                         max_seq=mix["max_seq"])
    t = time.perf_counter()
    _warm(eng, mix, c["vocab_size"])
    log(f"warm-up {time.perf_counter() - t:.3f} s")
    return sess, eng


def requests(cell, seed: int, seconds: float, t_win: float) -> list:
    """The run's requests, due relative to the window start ``t_win``."""
    sched = traffic_mod.lm_schedule(cell.traffic, seconds)
    prompts = traffic_mod.prompt_tokens(seed, [a.prompt_len for a in sched],
                                        cell.config["vocab_size"])
    return [Req(a, p, t_win + a.t) for a, p in zip(sched, prompts)]


def run(cell, seed: int, seconds: float, trace_dir: str | None, *,
        t_start: float, log, wrap=None, control=None) -> dict:
    """One run of an LM cell; returns the run record for the metrics.
    ``control = (a_bits, w_bits)`` also puts the lower-precision
    reference in the program's place on the same sample
    (``rec["control_checks"]``)."""
    import jax

    from repro.runtime.batching import streams

    c, mix = cell.config, cell.traffic
    ref = manifest.load_module("refs", c["reference"])
    sess, eng = build(cell, seed, log, wrap)
    t_win = time.perf_counter() + float(mix["preroll_s"])
    reqs = requests(cell, seed, seconds, t_win)
    loop = OpenLoop(eng, reqs)
    loop.drive(t_win)
    setup_s = time.perf_counter() - t_start

    compiles = trace_reduce.count_compiles()
    traced = trace_dir is not None
    if traced:
        trace_reduce.start(trace_dir)
    t_w0 = time.perf_counter()
    with trace_reduce.span("bench.window", traced):
        loop.drive(t_win + seconds, traced, memory=True)
    t_w1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    n_compiles = len(compiles)
    window = [r for r in reqs if r.arrival.phase == "window"]
    loop.drive(t_w1 + FIRST_TOKEN_WAIT_S,
               stop=lambda: all(r.times or (r.handle is not None
                                            and r.handle.done)
                                for r in window))
    log(f"bytes in use in the window {loop.memory}, process peak "
        f"{checks_mod.process_peak_bytes()}")

    done = [r for r in reqs if r.handle is not None
            and r.handle.state == streams.DONE]
    failed = sum(1 for r in window if not r.times
                 or r.handle.state == streams.FAILED)
    served = {id(r): r.handle.result(timeout=0) for r in done}
    loop.eng = None
    del eng, sess
    gc.collect()

    sample = _sample(done, seed, int(mix["compare_requests"]))
    t = time.perf_counter()
    gap, ctrl = _compare(ref, c, seed, sample, served, mix["max_seq"],
                         control)
    log(f"reference over {len(sample)} requests, "
        f"{sum(len(served[id(r)]) for r in sample)} served tokens: "
        f"{time.perf_counter() - t:.3f} s")
    checks = checks_of(c, gap, failed)
    return {
        "kind": "lm", "setup_s": setup_s, "t_w0": t_w0, "t_w1": t_w1,
        "window_s": t_w1 - t_w0, "t_win": t_win, "seconds": seconds,
        "reqs": reqs, "steps": loop.steps, "config": c, "traffic": mix,
        "attempted": len(window), "failed": failed,
        "memory_peak_bytes": loop.memory, "compiles_in_window": n_compiles,
        "checks": checks, "correct": checks_mod.correct(checks),
        "control_checks": None if ctrl is None
        else checks_of(c, ctrl, failed),
    }


def checks_of(c: dict, gap: float, failed: int) -> dict:
    """The numbers compared for ``correct``, each with its limit."""
    return {"served_gap": (gap, c["limits"]["served_gap"]),
            "failed_requests": (float(failed), 0.0)}


def _sample(done: list, seed: int, k: int) -> list:
    """``k`` finished requests: the one with the most served tokens, and
    the rest drawn from the seed."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r.arrival.gen_len, r.arrival.t))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _compare(ref, c: dict, seed: int, sample: list, served: dict,
             pad_to: int, control=None) -> tuple[float, float | None]:
    """Widest normalized gap over every served token of the sample, and
    the same for the token the control puts first at each position."""
    if not sample:
        return float("inf"), None
    w = ref.make_weights(seed, c)
    gap = max(float(np.max(ref.served_gaps(w, c, r.prompt, served[id(r)],
                                           pad_to))) for r in sample)
    if control is None:
        return gap, None
    return gap, max(float(np.max(ref.served_gaps(
        w, c, r.prompt, served[id(r)], pad_to, control=control)))
        for r in sample)
