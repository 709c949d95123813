"""Driver for ``kind: cnn`` configurations: closed-loop classification.

Set-up makes the weights and ``distinct_batches`` image batches on the
device from the seed, compiles the configuration's route with
``loom.compile`` and warms ``ServingSession.classify`` at the mix's
batch. The window calls ``classify`` back to back, each call ending in
``block_until_ready``, cycling over the image batches. The window closes
with the first call that ends after ``seconds``.

A reservoir of ``compare_calls`` calls, drawn from the seed, keeps its
logits; once the program's state is freed, the float32 reference
classifies the same images and the widest logit error of any image, in
units of that image's spread of reference logits, is held to the
configuration's limit (``checks.py``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

import checks as checks_mod
import manifest
import trace_reduce


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
    setup_s = time.perf_counter() - t_start

    compiles = trace_reduce.count_compiles()
    traced = trace_dir is not None
    rng = np.random.default_rng([seed, 3])
    k = int(mix["compare_calls"])
    kept: list[tuple[int, int, object]] = []      # (call, input, logits)
    calls: list[tuple[float, float]] = []
    memory = 0                  # most bytes in use at a call's end
    if traced:
        trace_reduce.start(trace_dir)
    t_w0 = time.perf_counter()
    with trace_reduce.span("bench.window", traced):
        while not calls or calls[-1][1] < t_w0 + seconds:
            i = len(calls)
            t0 = time.perf_counter()
            with trace_reduce.span("bench.classify", traced):
                out = jax.block_until_ready(sess.classify(xs[i % n_inputs]))
            calls.append((t0, time.perf_counter()))
            memory = max(memory, checks_mod.bytes_in_use())
            # reservoir sampling: every call is kept with equal chance
            if i < k:
                kept.append((i, i % n_inputs, out))
            else:
                j = int(rng.integers(0, i + 1))
                if j < k:
                    kept[j] = (i, i % n_inputs, out)
    t_w1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    n_compiles = len(compiles)
    log(f"bytes in use in the window {memory}, process peak "
        f"{checks_mod.process_peak_bytes()}")
    got = [(inp, np.asarray(out)) for _, inp, out in kept]
    del sess, kept, out
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
        "window_s": t_w1 - t_w0, "seconds": seconds, "calls": calls,
        "batch": batch, "config": c, "traffic": mix,
        "attempted": len(calls) * batch, "failed": 0,
        "memory_peak_bytes": memory, "compiles_in_window": n_compiles,
        "checks": checks, "correct": checks_mod.correct(checks),
        "control_checks": None if ctrl is None else checks_of(c, ctrl),
    }


def checks_of(c: dict, err: float) -> dict:
    """The number compared for ``correct``, with its limit."""
    return {"logit_error": (err, c["limits"]["logit_error"])}

