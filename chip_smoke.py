"""Chip smoke test: the packed serving path on one TPU, end to end.

    python chip_smoke.py [--seed N]

Run from the root of the repository. In one process, on one chip:

1. qwen3-1.7b at its published widths (28 layers, d_model 2048, 16 query
   and 8 KV heads, d_ff 6144, vocab 151936; random weights from the
   seed) is compiled by ``loom.compile(mode="serve_packed",
   backend="pallas_tpu")`` at Pa = Pw = 8, unguarded, and served by a
   ``BatchingEngine``: 8 requests join the running batch one step apart
   and take 32 new tokens each. The same requests are served again on
   the ``xla`` route from the same dense weights; the token streams must
   be equal, and one decode step's logits must agree within
   ``LOGIT_TOL``.
2. The paper CNN (32 px) classifies one batch of 64 images through the
   fused bit-serial conv on ``pallas_tpu`` and on ``xla``; the
   predictions must be equal.

Lines before the last report the device, compile seconds per phase,
conversion and fingerprint seconds, peak device memory and what was
served. The last line is one JSON object naming the device. Without a
TPU the script refuses to run: its numbers would describe the host. Any
failed check exits non-zero; no phase failure is caught.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_REQUESTS = 8
GEN_LEN = 32
PROMPT_LENS = (16, 32)       # two prefill shapes, each compiled once
MAX_SEQ = 128                # KV slots per sequence: longest prompt + 32
CNN_BATCH = 64
# Both routes compute every packed linear's int32 accumulator exactly,
# and the serving programs keep every bf16 rounding the model states
# (``api.session._JIT``), so the float code around the linears rounds
# alike on both: the logits are bit-identical (0.0 measured on a v5e).
LOGIT_TOL = 0.0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _kernel_calls(jitted, *args) -> int:
    """Mosaic kernels in the compiled program (the scan body counts once).
    The lowered program would share one function among kernels of equal
    shape, so it is counted after compilation."""
    return jitted.lower(*args).compile().as_text().count("tpu_custom_call")


def _compile_lm(cfg, policy, backend, params, specs, expect_kernels):
    """loom.compile + warm-up of every shape the engine will run.

    Returns (session, timings). The first call of each entry point at
    each shape compiles it; later calls at those shapes reuse it."""
    import jax.numpy as jnp
    import numpy as np

    import repro.api as loom
    t0 = time.perf_counter()
    sess = loom.compile(cfg, policy, mode="serve_packed", backend=backend,
                        params=params, specs=specs)
    t_compile = time.perf_counter() - t0
    _, t_fp = _timed(sess.refingerprint)
    times = {"convert_s": t_compile - t_fp, "fingerprint_s": t_fp}
    check(sess.plan.backend.name == backend,
          f"plan runs on {sess.plan.backend.name}, not {backend}")
    # Each first call compiles (or loads from the compile cache) and is
    # timed; the kernel count compiles the same program once more.
    for s in PROMPT_LENS:
        toks = jnp.ones((1, s), jnp.int32)
        cache = sess.init_cache(1, cfg.max_seq)
        _, times[f"prefill{s}_compile_s"] = _timed(sess.prefill, toks, cache)
        if expect_kernels:
            n = _kernel_calls(sess._prefill, sess.params, toks,
                              sess.init_cache(1, cfg.max_seq), None)
            check(n >= expect_kernels,
                  f"{backend} prefill holds {n} Mosaic kernels, "
                  f"expected >= {expect_kernels}")
    tok = jnp.zeros((N_REQUESTS,), jnp.int32)
    pos = jnp.asarray(np.full(N_REQUESTS, 1, np.int32))
    _, times["decode_compile_s"] = _timed(
        sess.decode, tok, pos, sess.init_cache(N_REQUESTS, cfg.max_seq))
    if expect_kernels:
        n = _kernel_calls(sess._decode, sess.params, tok, pos,
                          sess.init_cache(N_REQUESTS, cfg.max_seq))
        check(n >= expect_kernels, f"{backend} decode holds {n} Mosaic "
              f"kernels, expected >= {expect_kernels}")
    return sess, times


def _prompt(cfg, seed: int, j: int):
    import numpy as np
    rng = np.random.default_rng(seed + j)
    return rng.integers(1, cfg.vocab, size=(PROMPT_LENS[j % 2],)
                        ).astype(np.int32)


def _serve(sess, cfg, seed: int):
    """Serve the request set with staggered joins; return the streams
    and the serving seconds."""
    import numpy as np

    from repro.runtime.batching import BatchingEngine, streams
    eng = BatchingEngine(sess, max_batch=N_REQUESTS, max_seq=cfg.max_seq)
    t0 = time.perf_counter()
    handles = []
    for j in range(N_REQUESTS):
        handles.append(eng.submit(_prompt(cfg, seed, j), GEN_LEN))
        eng.step()                 # the next request joins a running batch
    eng.run(max_steps=4 * GEN_LEN * N_REQUESTS)
    t_serve = time.perf_counter() - t0
    out = []
    for h in handles:
        check(h.state == streams.DONE, f"request {h.request_id} ended "
              f"{h.state}")
        out.append(h.result(timeout=0))
        check(len(out[-1]) == GEN_LEN, f"request {h.request_id}: "
              f"{len(out[-1])} of {GEN_LEN} tokens")
    return np.stack(out), t_serve


def _one_step_logits(sess, cfg, seed: int):
    """Logits of the first decode step after request 0's prompt."""
    import jax.numpy as jnp
    import numpy as np
    prompt = jnp.asarray(_prompt(cfg, seed, 0)[None, :])
    logits, cache = sess.prefill(prompt, sess.init_cache(1, cfg.max_seq))
    tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
    logits, _ = sess.decode(tok, prompt.shape[1], cache)
    return np.asarray(logits, np.float32)


def lm_phase(cfg, *, backend: str, ref_backend: str, seed: int = 0,
             expect_kernels: int = 0) -> dict:
    """Serve the request set on ``backend`` and on ``ref_backend`` from
    the same dense weights; check the streams and one step's logits.
    ``expect_kernels``: least number of Mosaic kernels each program of
    ``backend`` must hold (0 = no check; off the chip there are none)."""
    import jax
    import numpy as np

    from repro.core.policy import uniform_policy
    from repro.models import model as M
    policy = uniform_policy(8, 8)
    params, specs = M.init_params(jax.random.PRNGKey(seed), cfg)
    out = {}
    sess, out[backend] = _compile_lm(cfg, policy, backend, params, specs,
                                     expect_kernels)
    ref, out[ref_backend] = _compile_lm(cfg, policy, ref_backend, params,
                                        specs, 0)
    del params
    got, out[backend]["serve_s"] = _serve(sess, cfg, seed)
    want, out[ref_backend]["serve_s"] = _serve(ref, cfg, seed)
    n_equal = int(sum(np.array_equal(g, w) for g, w in zip(got, want)))
    fallbacks = sess.plan.fallback_report()
    delta = float(np.max(np.abs(_one_step_logits(sess, cfg, seed)
                                - _one_step_logits(ref, cfg, seed))))
    log(f"lm {backend} vs {ref_backend}: streams equal {n_equal}/"
        f"{N_REQUESTS}, max |dlogits| {delta} (tol {LOGIT_TOL})")
    check(n_equal == N_REQUESTS,
          f"{N_REQUESTS - n_equal} of {N_REQUESTS} token streams differ "
          f"between {backend} and {ref_backend}")
    check(fallbacks == {}, f"{backend}: ops fell back: {fallbacks}")
    check(delta <= LOGIT_TOL, f"max |dlogits| {delta} exceeds {LOGIT_TOL}")
    out.update(tokens=int(got.size), streams_equal=n_equal,
               max_dlogits=delta, logit_tol=LOGIT_TOL, fallbacks=fallbacks)
    return out


def cnn_phase(cfg, *, backend: str, ref_backend: str, seed: int = 0,
              batch: int = CNN_BATCH, expect_kernels: int = 0) -> dict:
    """Classify one batch on ``backend`` and on ``ref_backend`` from the
    same weights; the predictions must be equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.api as loom
    from repro.core.policy import uniform_policy
    from repro.models import cnn
    policy = uniform_policy(8, 8)
    params, specs = cnn.init_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (batch, cfg.img, cfg.img, cfg.in_ch), jnp.float32)
    out, preds = {}, {}
    for be in (backend, ref_backend):
        sess = loom.compile(cfg, policy, mode="serve_packed", backend=be,
                            params=params, specs=specs)
        logits, t = _timed(sess.classify, x)
        if be == backend and expect_kernels:
            n = _kernel_calls(sess._classify, sess.params, x)
            check(n >= expect_kernels, f"{be} classify holds {n} Mosaic "
                  f"kernels, expected >= {expect_kernels}")
        preds[be] = np.argmax(np.asarray(logits), axis=-1)
        out[be] = {"classify_compile_s": t}
        if be == backend:
            out["fallbacks"] = sess.plan.fallback_report()
            check(out["fallbacks"] == {},
                  f"{be}: ops fell back: {out['fallbacks']}")
    n_equal = int(np.sum(preds[backend] == preds[ref_backend]))
    check(n_equal == batch, f"{batch - n_equal} of {batch} predictions "
          f"differ between {backend} and {ref_backend}")
    out.update(images=batch, predictions_equal=n_equal)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and images")
    args = ap.parse_args(argv)

    import dataclasses

    import jax
    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found "
              f"{[d.platform for d in devices]}; refusing to report host "
              f"numbers", file=sys.stderr)
        return 2
    dev = devices[0]

    from repro import configs
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"device_kind={dev.device_kind} count={len(devices)} "
        f"compile_cache={cache_dir} entries_at_start={cached}")

    cfg = dataclasses.replace(configs.get("qwen3-1.7b"), max_seq=MAX_SEQ)
    log(f"lm {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} max_seq={cfg.max_seq}")
    lm = lm_phase(cfg, backend="pallas_tpu", ref_backend="xla",
                  seed=args.seed, expect_kernels=8)
    for be in ("pallas_tpu", "xla"):
        log(f"lm {be}: " + " ".join(f"{k}={v:.3f}"
                                    for k, v in lm[be].items()))
    log(f"lm served {lm['tokens']} tokens in {N_REQUESTS} requests, all "
        f"DONE; streams equal pallas_tpu vs xla: "
        f"{lm['streams_equal']}/{N_REQUESTS}; max |dlogits| one decode "
        f"step = {lm['max_dlogits']} (tol {lm['logit_tol']})")

    cnn_cfg = configs.get("paper-cnn")
    cnn = cnn_phase(cnn_cfg, backend="pallas_tpu", ref_backend="xla",
                    seed=args.seed, expect_kernels=len(cnn_cfg.convs))
    for be in ("pallas_tpu", "xla"):
        log(f"cnn {be}: classify_compile_s="
            f"{cnn[be]['classify_compile_s']:.3f}")
    log(f"cnn classified {cnn['images']} images at {cnn_cfg.img} px; "
        f"predictions equal pallas_tpu vs xla: "
        f"{cnn['predictions_equal']}/{cnn['images']}")
    log(f"fallback report: lm={lm['fallbacks']} cnn={cnn['fallbacks']}")
    log(f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
