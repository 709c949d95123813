"""A CPU rehearsal of each driver, end to end at a tiny size.

Only the tests call it; ``run_cell.py`` keeps refusing without a chip.
It builds a cell from the program's smoke presets (``qwen3-smoke`` on
the ``xla`` route, ``paper-cnn-smoke`` on ``pallas_interpret``), with
the same limits as the benchmark's configurations, runs the driver for
a second or two and returns its record. It prints nothing: no number of
a CPU run goes out under a device metric's name.

``fault`` plants one fault under the timed path, to see ``correct``
come out false:

- ``stale_state``: each decode step returns the cache it was given;
- ``token_altered``: each decode step's logits are rolled by one, so
  every decoded token is another than the program computed;
- ``answer_altered``: each classify call's logits are rolled by one;
- ``half_batch``: each decode step or classify call hands out the
  first half of its batch's logits for the second half as well.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import manifest

HERE = os.path.dirname(os.path.abspath(__file__))


def _limits(config: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{config}.json")) as f:
        return json.load(f)["limits"]


def lm_cell() -> manifest.Cell:
    from repro import configs
    pc = configs.get("qwen3-1.7b", smoke=True)
    config = {
        "name": pc.name, "kind": "lm", "program_config": "qwen3-1.7b",
        "program_smoke": True, "reference": "qwen3",
        "hidden_size": pc.d_model, "intermediate_size": pc.d_ff,
        "num_hidden_layers": pc.n_layers, "num_attention_heads": pc.n_heads,
        "num_key_value_heads": pc.n_kv_heads, "head_dim": pc.d_head,
        "vocab_size": pc.vocab, "rope_theta": pc.rope_theta,
        "rms_norm_eps": 1e-6, "max_position_embeddings": pc.max_seq,
        "route": {"mode": "serve_packed", "backend": "xla",
                  "a_bits": 8, "w_bits": 8},
        "limits": _limits("qwen3-1.7b")}
    mix = {"kind": "lm_open_poisson", "rate_rps": 30.0, "schedule_seed": 1,
           "prompt_lens": [8, 16], "prompt_p": [0.5, 0.5],
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 4, "max": 16},
           "max_batch": 2, "max_seq": 32, "preroll_s": 0.5, "tail_s": 5,
           "compare_requests": 64}
    return manifest.Cell("rehearsal-lm", 1, config, mix, [], [])


def cnn_cell() -> manifest.Cell:
    config = {
        "name": "paper-cnn-smoke", "kind": "cnn", "reference": "vgg",
        "image_size": 16, "in_channels": 3,
        "convs": [{"name": "conv1", "out": 32, "kernel": 3, "pool": 2}],
        "fcs": [32, 10],
        "route": {"mode": "serve_packed", "backend": "pallas_interpret",
                  "a_bits": 8, "w_bits": 8},
        "limits": _limits("vgg16-cifar")}
    mix = {"kind": "cnn_closed_loop", "batch": 8, "distinct_batches": 2,
           "compare_calls": 2, "ahead_s": 0.25}
    return manifest.Cell("rehearsal-cnn", 1, config, mix, [], [])


def _wrap(fault: str | None):
    if fault is None:
        return None
    import jax
    import jax.numpy as jnp

    def wrap(sess):
        if fault == "stale_state":
            real = sess._decode

            def decode(p, tok, pos, cache):
                kept = jax.tree.map(jnp.copy, cache)
                return real(p, tok, pos, cache)[0], kept
            return dataclasses.replace(sess, _decode=decode)
        if fault == "token_altered":
            real = sess._decode

            def decode(p, tok, pos, cache):
                logits, cache = real(p, tok, pos, cache)
                return jnp.roll(logits, 1, axis=-1), cache
            return dataclasses.replace(sess, _decode=decode)
        if fault == "answer_altered":
            real = sess._classify
            return dataclasses.replace(
                sess, _classify=lambda p, x: jnp.roll(real(p, x), 1, -1))
        if fault == "half_batch" and sess._decode is not None:
            real = sess._decode

            def decode(p, tok, pos, cache):
                logits, cache = real(p, tok, pos, cache)
                half = logits[: logits.shape[0] // 2]
                return jnp.concatenate([half, half]), cache
            return dataclasses.replace(sess, _decode=decode)
        if fault == "half_batch":
            real = sess._classify

            def classify(p, x):
                half = real(p, x[: x.shape[0] // 2])
                return jnp.concatenate([half, half])
            return dataclasses.replace(sess, _classify=classify)
        raise ValueError(f"unknown fault {fault!r}")
    return wrap


def rehearse(kind: str, *, seed: int = 2**31 + 11, seconds: float = 1.5,
             fault: str | None = None) -> dict:
    """Run the ``kind`` driver on the CPU at a tiny size; its record."""
    cell = lm_cell() if kind == "lm" else cnn_cell()
    driver = manifest.load_module("drivers", kind)
    return driver.run(cell, seed, seconds, None, t_start=time.perf_counter(),
                      log=lambda msg: None, wrap=_wrap(fault))
