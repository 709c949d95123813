"""Serving launcher: prefill + decode steps over the Loom execution plans.

``repro.api.session.compile`` (a.k.a. ``loom.compile``) is the primary
entry point — it owns param conversion, cache init, and the jitted
prefill/decode pair behind a ``ServingSession``. This module keeps:

  * ``make_serve_fns`` / ``jit_serve_steps``: thin launch-layer wrappers
    used by the multi-pod dry-run (which jits against ShapeDtypeStructs
    and production meshes rather than real params);
  * the CPU demo driver (``python -m repro.launch.serve``), which runs
    either through the session API (``--api session``, default) or the
    hand-wired launch layer (``--api plan``: ``build_plan`` + explicit
    param conversion + ``make_serve_fns``) — both produce identical
    generations for the same seed, which is what the CI serve-smoke job
    diffs.

Modes: dense (DPNN-equivalent baseline), serve_int8 (LM_8b), serve_packed
(bit-serial planes; Pw/16 weight bytes; ``--dynamic-a`` adds runtime
per-group activation-plane trimming — per group-of-rows on linears, per
group-of-output-windows on convs). ``--arch paper-cnn`` serves the CNN
classification cell, so the fused dynamic conv path runs end-to-end.
``--out-tokens FILE`` saves the generations/predictions as .npy — the CI
serve-smoke job diffs the session run against the plan run with it.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro import configs
from repro.api import backend as backendlib
from repro.api import plan as planlib
from repro.models import model as M


def make_serve_fns(cfg, plan):
    """(prefill_step, decode_step) closed over cfg + an ExecutionPlan."""
    def prefill_step(params, tokens, cache, img_embeds=None):
        return M.prefill(params, cfg, tokens, cache, plan, img_embeds)

    def decode_step(params, token, pos, cache):
        return M.decode_step(params, cfg, token, pos, cache, plan)

    return prefill_step, decode_step


def jit_serve_steps(cfg, plan, mesh, param_specs, cache_specs,
                    batch_structs_specs=None):
    """Sharding-jitted (prefill, decode). One implementation, shared with
    the session API (repro.api.session._jit_lm) so the wiring cannot
    drift between the launch layer and ServingSession."""
    from repro.api.session import _jit_lm
    return _jit_lm(cfg, plan, mesh, param_specs, cache_specs)


# ---------------------------------------------------------------------------
# CPU-scale batched-serving driver
# ---------------------------------------------------------------------------

def _generate_plan(cfg, args, policy):
    """The hand-wired launch-layer cell: build_plan + explicit conversion.

    Kept as the A/B cross-check of ``loom.compile`` — for the same seed
    its generations must be byte-identical to the session path."""
    import numpy as np

    params, specs = M.init_params(jax.random.PRNGKey(0), cfg)
    if args.mode != "dense":
        params, specs = M.convert_params_for_serving(params, specs, policy,
                                                     args.mode)
        print(f"[serve] packed weights for mode={args.mode} "
              f"(Pw={args.w_bits}: weight bytes x{args.w_bits}/16 of bf16)")
    plan = planlib.build_plan(cfg, policy, mode=args.mode,
                              backend=args.backend)
    if args.mode != "dense":
        plan.record_weight_groups({"lm_head": params.get("head", {})})
    prefill_fn, decode_fn = make_serve_fns(cfg, plan)
    prefill_fn = jax.jit(prefill_fn)
    decode_fn = jax.jit(decode_fn, donate_argnums=(3,))

    rng = np.random.default_rng(getattr(args, "prompt_seed", 0))
    b, s = args.batch, args.prompt_len
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, size=(b, s)), jnp.int32)
    cache = M.init_cache(cfg, b, cfg.max_seq)
    logits, cache = prefill_fn(params, tokens, cache)
    tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
    out = [np.asarray(tok)]
    for i in range(args.gen_len - 1):
        pos = jnp.asarray(s + i, jnp.int32)
        logits, cache = decode_fn(params, tok, pos, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
    return np.stack(out, axis=1)


def _generate_session(cfg, args, policy):
    """The same serving cell through loom.compile().

    ``--guarded`` compiles with a GuardedBackend and routes requests
    through a ServingSupervisor — byte-identical generations on the
    fault-free path (the CI serve-smoke job diffs guarded vs unguarded).
    """
    import numpy as np
    from repro.api import session as loom

    sess = loom.compile(cfg, policy, mode=args.mode, backend=args.backend,
                        rng=0, guarded=getattr(args, "guarded", False))
    if args.mode != "dense":
        print(f"[serve] packed weights for mode={args.mode} "
              f"(Pw={args.w_bits}: weight bytes x{args.w_bits}/16 of bf16)")
    rng = np.random.default_rng(getattr(args, "prompt_seed", 0))
    tokens = jnp.asarray(rng.integers(1, cfg.vocab,
                                      size=(args.batch, args.prompt_len)),
                         jnp.int32)
    if getattr(args, "guarded", False):
        from repro.runtime import ServingSupervisor
        sup = ServingSupervisor(sess)
        gen = sup.generate(tokens, args.gen_len)
        print(f"[serve] supervisor health: {sup.health()}")
        return gen
    return sess.generate(tokens, args.gen_len)


def _server_prompt(cfg, args, j: int):
    """Request ``j``'s prompt: seed prompt_seed + j, length prompt_len + j.

    Deterministic per request so CI can reproduce EXACTLY this prompt in
    a solo batch-1 run (``--batch 1 --prompt-seed <seed+j>
    --prompt-len <len+j>``) and diff the streams byte-for-byte."""
    import numpy as np
    rng = np.random.default_rng(args.prompt_seed + j)
    return rng.integers(1, cfg.vocab,
                        size=(args.prompt_len + j,)).astype(np.int32)


def _serve_server(cfg, args, policy):
    """Continuous-batching server mode: ``--server N`` staggered requests
    through a BatchingEngine (supervised when ``--guarded``); returns the
    streams of the requests that finished, stacked [n_done, gen_len] for
    the CI stream diff, and the number that did not finish.

    Lifecycle wiring: SIGINT/SIGTERM flips a stop flag checked at every
    step boundary; the engine then runs ``shutdown(--drain-timeout)`` —
    in-flight requests finish within the bound, residual streams fail
    loudly with a typed ``EngineClosedError``. ``--max-queue`` /
    ``--deadline-s`` / ``--step-timeout`` expose the overload knobs."""
    import signal

    import numpy as np
    from repro.api import session as loom
    from repro.runtime.batching import BatchingEngine, streams as streamlib

    sess = loom.compile(cfg, policy, mode=args.mode, backend=args.backend,
                        rng=0, guarded=args.guarded)
    target = sess
    sup = None
    if args.guarded:
        from repro.runtime import ServingSupervisor
        target = sup = ServingSupervisor(sess)
    eng = BatchingEngine(target, max_batch=args.batch,
                         max_seq=args.max_seq,
                         max_queue=args.max_queue,
                         step_timeout_s=args.step_timeout,
                         audit_rate=args.audit_rate,
                         audit_backend=args.audit_backend,
                         integrity_every=args.integrity_every)
    stop_requested = False

    def _on_signal(signum, frame):
        nonlocal stop_requested
        stop_requested = True
        print(f"[serve] caught {signal.Signals(signum).name}: draining "
              f"(bound {args.drain_timeout}s)", flush=True)

    old_handlers = {s: signal.signal(s, _on_signal)
                    for s in (signal.SIGINT, signal.SIGTERM)}
    deadline = args.deadline_s if args.deadline_s > 0 else None
    handles = []
    try:
        for j in range(args.server):
            handles.append(eng.submit(_server_prompt(cfg, args, j),
                                      args.gen_len, deadline_s=deadline))
            if stop_requested:
                break
            eng.step()   # staggered joins: requests join a running batch
        while not stop_requested and eng.step():
            pass
        summary = eng.shutdown(args.drain_timeout)
    finally:
        for s, h in old_handlers.items():
            signal.signal(s, h)
        if sup is not None:
            sup.close()
    done = [h for h in handles if h.state == streamlib.DONE]
    n_unfinished = args.server - len(done)
    streams = np.stack([np.asarray(h.tokens_so_far()) for h in done]) \
        if done else np.zeros((0, args.gen_len), np.int32)
    st = eng.stats
    print(f"[serve] server: {len(done)}/{args.server} requests done "
          f"unfinished={n_unfinished} "
          f"state={eng.health()['state']} "
          f"engine={eng.state} drained={summary['drained']} "
          f"occupancy={st.batch_occupancy:.2f} "
          f"tokens/s={st.tokens_per_s:.2f} "
          f"queue_depth={st.queue_depth} "
          f"latency p50={st.p50_request_latency_s:.3f}s "
          f"p95={st.p95_request_latency_s:.3f}s "
          f"queue_wait p50={st.p50_queue_wait_s:.3f}s "
          f"p95={st.p95_queue_wait_s:.3f}s "
          f"streamed={st.n_tokens_streamed} "
          f"rejected={st.n_rejected} shed={st.n_shed} "
          f"expired={st.n_deadline_expired} "
          f"restarts={st.n_engine_restarts} "
          f"audits={st.n_audits} divergences={st.n_divergences} "
          f"integrity_checks={st.n_integrity_checks} "
          f"quarantines={st.n_quarantines} "
          f"audit_lag_p95={st.p95_audit_lag_s:.3f}s")
    return streams, n_unfinished


def _cnn_inputs(cfg, args):
    import numpy as np
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(size=(args.batch, cfg.img, cfg.img,
                                        cfg.in_ch)), jnp.float32)


def _classify_plan(cfg, args, policy):
    """The CNN cell on the hand-wired launch-layer plan."""
    import numpy as np
    from repro.models import cnn, model as M

    params, specs = cnn.init_params(jax.random.PRNGKey(0), cfg)
    if args.mode != "dense":
        params, specs = M.convert_params_for_serving(params, specs, policy,
                                                     args.mode)
    plan = planlib.build_plan(cfg, policy, mode=args.mode,
                              backend=args.backend)
    if args.mode != "dense":
        plan.record_weight_groups(params)
    logits = jax.jit(lambda p, x: cnn.forward(p, cfg, x, plan))(
        params, _cnn_inputs(cfg, args))
    return np.argmax(np.asarray(logits), axis=-1)


def _classify_session(cfg, args, policy):
    """The same CNN cell through loom.compile()."""
    import numpy as np
    from repro.api import session as loom

    sess = loom.compile(cfg, policy, mode=args.mode, backend=args.backend,
                        rng=0, guarded=getattr(args, "guarded", False))
    if getattr(args, "guarded", False):
        from repro.runtime import ServingSupervisor
        sup = ServingSupervisor(sess)
        logits = sup.classify(_cnn_inputs(cfg, args))
        print(f"[serve] supervisor health: {sup.health()}")
    else:
        logits = sess.classify(_cnn_inputs(cfg, args))
    return np.argmax(np.asarray(logits), axis=-1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--full", action="store_true",
                    help="serve the architecture's published config "
                         "(default: its smoke preset)")
    ap.add_argument("--max-seq", type=int, default=None, metavar="N",
                    help="KV cache length per sequence (default: the "
                         "config's max_seq); a published config's 32k "
                         "cache does not fit one chip at batch > 1")
    ap.add_argument("--mode", default="serve_int8",
                    choices=["dense", "serve_int8", "serve_packed"])
    ap.add_argument("--api", default="session", choices=["session", "plan"],
                    help="session = loom.compile ServingSession; "
                         "plan = hand-wired build_plan + make_serve_fns")
    ap.add_argument("--backend", default="xla",
                    choices=list(backendlib.list_backends()))
    ap.add_argument("--dynamic-a", action="store_true",
                    help="runtime per-group activation-plane trimming "
                         "(serve_packed linears)")
    ap.add_argument("--guarded", action="store_true",
                    help="guarded backend (typed faults + fallback chain) "
                         "+ ServingSupervisor request wrapper; "
                         "bit-identical on the fault-free path")
    ap.add_argument("--group-size", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--server", type=int, default=0, metavar="N",
                    help="continuous-batching server mode: N staggered "
                         "requests through a BatchingEngine (--batch = "
                         "slot count; request j: seed prompt-seed+j, "
                         "length prompt-len+j); prints the serving "
                         "metrics summary line")
    ap.add_argument("--max-queue", type=int, default=None, metavar="N",
                    help="bound the server-mode request queue; a full "
                         "queue rejects submits with a typed "
                         "QueueFullError (default: unbounded)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request TTL in server mode: expired-while-"
                         "queued requests are shed before prefill, "
                         "in-flight ones retire at the next step "
                         "boundary (0 = no deadline)")
    ap.add_argument("--step-timeout", type=float, default=None,
                    metavar="SECONDS",
                    help="decode-watchdog deadline per engine step; a "
                         "stalled step restarts-and-replays instead of "
                         "freezing the queue (default: no watchdog)")
    ap.add_argument("--audit-rate", type=float, default=0.0,
                    metavar="FRACTION",
                    help="server-mode shadow-audit sampling rate in [0,1]: "
                         "that fraction of completed requests is replayed "
                         "off the hot path on the reference oracle and "
                         "byte-compared; a divergence quarantines the "
                         "backend and writes a replayable repro bundle "
                         "(0 = auditing off, byte-identical serving)")
    ap.add_argument("--audit-backend", default="xla",
                    choices=list(backendlib.list_backends()),
                    help="reference oracle backend for shadow audits")
    ap.add_argument("--integrity-every", type=int, default=0, metavar="N",
                    help="re-verify packed-weight CRC32 fingerprints every "
                         "N engine steps; a mismatch self-heals from the "
                         "hot checkpoint when one is armed, else fails "
                         "loudly with WeightIntegrityError (0 = off)")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    metavar="SECONDS",
                    help="server-mode shutdown bound: in-flight requests "
                         "get this long to finish before residual "
                         "streams are failed loudly")
    ap.add_argument("--prompt-seed", type=int, default=0,
                    help="seed of the random prompt(s); lets CI "
                         "reproduce one server request's prompt in a "
                         "solo batch-1 run")
    ap.add_argument("--a-bits", type=int, default=8)
    ap.add_argument("--w-bits", type=int, default=8)
    ap.add_argument("--out-tokens", default=None, metavar="FILE",
                    help="save the generations/predictions as .npy "
                         "(CI diffs session vs plan runs)")
    args = ap.parse_args(argv)

    import dataclasses

    import numpy as np
    from repro.core.policy import uniform_policy
    from repro.launch import compile_cache

    compile_cache.enable()
    cfg = configs.get(args.arch, smoke=not args.full)
    if args.max_seq is not None and hasattr(cfg, "max_seq"):
        cfg = dataclasses.replace(cfg, max_seq=args.max_seq)
    policy = uniform_policy(args.a_bits, args.w_bits,
                            dynamic_a=args.dynamic_a)
    if args.dynamic_a:
        policy = dataclasses.replace(policy, group_size=args.group_size)
    if hasattr(cfg, "convs"):            # CNN classification cell
        if args.server:
            raise SystemExit("--server is an LM decode mode; CNN configs "
                             "classify in one shot (drop --server)")
        cls_fn = _classify_session if args.api == "session" else _classify_plan
        gen = cls_fn(cfg, args, policy)
        print(f"[serve] classified {gen.shape[0]} images via {args.api} "
              f"({args.backend}{', dynamic-a' if args.dynamic_a else ''}); "
              f"predictions: {gen}")
    elif args.server:
        gen, n_unfinished = _serve_server(cfg, args, policy)
        print(f"[serve] generated {gen.shape} tokens via batching engine "
              f"({args.backend}{', dynamic-a' if args.dynamic_a else ''})")
    else:
        gen_fn = _generate_session if args.api == "session" else _generate_plan
        gen = gen_fn(cfg, args, policy)
        print(f"[serve] generated {gen.shape} tokens via {args.api} "
              f"({args.backend}{', dynamic-a' if args.dynamic_a else ''}); "
              f"first row: {gen[0][:8]}...")
    if args.out_tokens:
        np.save(args.out_tokens, gen)
        print(f"[serve] saved outputs to {args.out_tokens}")
    if args.server and n_unfinished:
        raise SystemExit(f"[serve] {n_unfinished} of {args.server} requests "
                         f"did not finish")
    print("done")


if __name__ == "__main__":
    main()
