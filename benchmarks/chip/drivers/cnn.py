"""Driver for ``kind: cnn`` configurations: closed-loop classification.

Set-up makes the weights and ``distinct_batches`` image batches on the
device from the seed, compiles the configuration's route with
``loom.compile`` and warms ``ServingSession.classify`` at the mix's
batch, then times a few calls to learn how many make the mix's
``ahead_s`` seconds of device work. The window dispatches ``classify``
back to back, cycling over the image batches, and keeps that many calls
in flight: before it dispatches one more it waits for the oldest, so a
host that stands still for less than ``ahead_s`` leaves the chip fed.
When ``seconds`` are up it dispatches nothing more, waits for every call
it dispatched, and reads the clock after that wait: every call counts,
over all of that time.

A reservoir of ``compare_calls`` calls, drawn from the seed, keeps its
logits; once the program's state is freed, the float32 reference
classifies the same images and the widest logit error of any image, in
units of that image's spread of reference logits, is held to the
configuration's limit (``checks.py``).
"""
from __future__ import annotations

import collections
import gc
import math
import time

import numpy as np

import checks as checks_mod
import manifest
import trace_reduce

TIMED_CALLS = 3                 # set-up calls timed to size the calls in flight


def program_config(c: dict):
    from repro.models.cnn import CNNConfig, ConvSpec
    return CNNConfig(
        name=c["name"], in_ch=c["in_channels"], img=c["image_size"],
        convs=tuple(ConvSpec(v["name"], v["out"], v["kernel"],
                             v.get("stride", 1), v.get("pool", 1))
                    for v in c["convs"]),
        fcs=tuple(c["fcs"]))


def run(cell, seed: int, seconds: float, trace_dir: str | None, *,
        t_start: float, log, wrap=None, control=None) -> dict:
    """One run of a CNN cell; returns the run record for the metrics.
    ``control = (a_bits, w_bits)`` also puts the lower-precision
    reference in the program's place on the same calls
    (``rec["control_checks"]``)."""
    import jax

    import repro.api as loom
    from repro.core.policy import uniform_policy

    c, mix = cell.config, cell.traffic
    ref = manifest.load_module("refs", c["reference"])
    route = c["route"]
    if mix["kind"] != "cnn_closed_loop":
        raise ValueError(f"the cnn driver runs no {mix['kind']!r} traffic")
    batch, n_inputs = int(mix["batch"]), int(mix["distinct_batches"])

    t = time.perf_counter()
    params, specs = ref.program_tree(ref.make_weights(seed, c))
    sess = loom.compile(program_config(c),
                        uniform_policy(route["a_bits"], route["w_bits"]),
                        mode=route["mode"], backend=route["backend"],
                        params=params, specs=specs)
    del params
    images = ref.make_images(seed, c, n_inputs, batch)
    xs = [images[i] for i in range(n_inputs)]
    del images
    jax.block_until_ready((sess.params, xs))
    log(f"weights+compile {time.perf_counter() - t:.3f} s")
    if wrap is not None:
        sess = wrap(sess)
    t = time.perf_counter()
    jax.block_until_ready(sess.classify(xs[0]))
    log(f"warm-up {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    for i in range(TIMED_CALLS):
        jax.block_until_ready(sess.classify(xs[i % n_inputs]))
    call_s = (time.perf_counter() - t) / TIMED_CALLS
    depth = max(1, math.ceil(float(mix["ahead_s"]) / call_s))
    log(f"one call {call_s * 1e3:.3f} ms: {depth} calls in flight")
    setup_s = time.perf_counter() - t_start

    compiles = trace_reduce.count_compiles()
    traced = trace_dir is not None
    rng = np.random.default_rng([seed, 3])
    k = int(mix["compare_calls"])
    kept: list[tuple[int, int, object]] = []      # (call, input, logits)
    in_flight: collections.deque = collections.deque()
    n = 0
    memory = 0                  # most bytes in use after a wait
    if traced:
        trace_reduce.start(trace_dir)
    t_w0 = time.perf_counter()
    with trace_reduce.span("bench.window", traced):
        while n == 0 or time.perf_counter() < t_w0 + seconds:
            if len(in_flight) == depth:
                with trace_reduce.span("bench.wait", traced):
                    jax.block_until_ready(in_flight.popleft())
                memory = max(memory, checks_mod.bytes_in_use())
            with trace_reduce.span("bench.classify", traced):
                out = sess.classify(xs[n % n_inputs])
            in_flight.append(out)
            # reservoir sampling: every call is kept with equal chance
            if n < k:
                kept.append((n, n % n_inputs, out))
            else:
                j = int(rng.integers(0, n + 1))
                if j < k:
                    kept[j] = (n, n % n_inputs, out)
            n += 1
        with trace_reduce.span("bench.wait", traced):
            jax.block_until_ready(list(in_flight))
        memory = max(memory, checks_mod.bytes_in_use())
    t_w1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    n_compiles = len(compiles)
    log(f"bytes in use in the window {memory}, process peak "
        f"{checks_mod.process_peak_bytes()}")
    got = [(inp, np.asarray(out)) for _, inp, out in kept]
    del sess, kept, out, in_flight
    gc.collect()

    t = time.perf_counter()
    w = ref.make_weights(seed, c)
    walk = ref.layers(c)
    err = max(ref.logit_error(g, ref.logits(w, xs[inp], walk))
              for inp, g in got)
    ctrl = None if control is None else max(
        ref.logit_error(ref.logits(w, xs[inp], walk, *control),
                        ref.logits(w, xs[inp], walk)) for inp, _ in got)
    log(f"reference over {len(got)} calls of {batch} images: "
        f"{time.perf_counter() - t:.3f} s")
    checks = checks_of(c, err)
    return {
        "kind": "cnn", "setup_s": setup_s, "t_w0": t_w0, "t_w1": t_w1,
        "window_s": t_w1 - t_w0, "seconds": seconds, "calls": n,
        "in_flight": depth, "batch": batch, "config": c, "traffic": mix,
        "attempted": n * batch, "failed": 0,
        "memory_peak_bytes": memory, "compiles_in_window": n_compiles,
        "checks": checks, "correct": checks_mod.correct(checks),
        "control_checks": None if ctrl is None else checks_of(c, ctrl),
    }


def checks_of(c: dict, err: float) -> dict:
    """The number compared for ``correct``, with its limit."""
    return {"logit_error": (err, c["limits"]["logit_error"])}

