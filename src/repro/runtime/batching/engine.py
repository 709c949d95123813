"""BatchingEngine: continuous-batching decode loop over a ServingSession.

The step loop (one :meth:`step` per decode-step boundary):

  1. retire requests whose callers cancelled since the last step;
  2. retire in-flight requests whose deadline passed — typed
     :class:`~repro.api.guards.RequestTimeoutError` on the stream,
     partial tokens retained;
  3. admit queued requests into free slots — expired-while-queued
     requests are shed BEFORE prefill (typed timeout, never silent);
     each admission is a batch-1 prefill (bit-identical to a solo
     prefill of the same prompt) scattered into its pool slot, so
     running requests never wait behind a drain barrier;
  4. run ONE batched decode over the full ``max_batch``-wide pool with
     per-slot positions (``pos: [B]``) and scatter the argmax tokens to
     the per-request :class:`~repro.runtime.batching.streams.StreamHandle`
     objects; inactive rows decode garbage into their own row only, and
     admission rewrites the whole row anyway;
  5. feed the serving gauges (queue depth, occupancy, tokens/s,
     p50/p95 latency + queue wait) into
     :class:`~repro.runtime.serving.ServeStats`.

Overload protection and lifecycle (ISSUE 9):

  * **admission control** — ``max_queue`` bounds the request queue;
    a full queue raises a typed ``QueueFullError`` (or blocks with a
    timeout in ``submit(block=True)``); per-request ``deadline_s``
    sheds/retires requests that can no longer be served in time.
  * **graceful lifecycle** — the engine walks ``accepting -> draining ->
    stopped``: :meth:`drain` stops admissions and finishes in-flight
    work; :meth:`shutdown` drains within a wall-clock bound and then
    fails residual streams loudly with a typed ``EngineClosedError``.
    A step loop that dies with an unexpected exception fails every live
    stream with the typed cause — ``result()``/iterators never hang.
  * **decode watchdog** — ``step_timeout_s`` bounds one decode step;
    a stuck step (chaos point ``engine.step_stall``) trips a typed
    ``StepStallError`` and routes into restart-and-replay below, so a
    hung backend degrades the session instead of freezing the queue.
  * **hot checkpoint swap** — :meth:`reload` validates a new dense param
    tree against the plan (tree/shape/dtype + packed weight-group
    counts; :meth:`reload_checkpoint` adds CRC via the ckpt manifest)
    and re-prefills survivors under the new weights between steps.
    Hard bar: every post-swap token is byte-identical to what a fresh
    engine started on the new checkpoint would emit at that position.

Silent-corruption defense (ISSUE 10):

  * **integrity cadence** — ``integrity_every=N`` re-verifies the weight
    CRC32 fingerprint (``core.integrity``) every N steps, BEFORE the
    decode, so a flipped bit (chaos point ``weights.bitflip``) is
    detected within one cadence and never serves a token; ``heal_dir``
    self-heals through :meth:`reload_checkpoint`, else the engine fails
    loudly with a typed ``WeightIntegrityError``.
  * **shadow audit** — ``audit_rate=r`` samples completed requests and
    replays them on the reference oracle (``runtime.audit``) at step
    boundaries; a divergence (chaos point ``backend.silent_corrupt``)
    quarantines the backend down the sticky fallback chain, re-jits the
    session, degrades health, and writes a replayable repro bundle.
    ``audit_rate=0`` (default) builds nothing: the step loop is the PR 9
    loop unchanged.

Byte-identity: every cross-row coupling in the decode path has been
removed (per-ROW activation quantization scales; per-slot causal masks;
value-preserving dynamic plane truncation), so row ``r`` of the batched
decode is bit-identical to a solo batch-1 ``session.generate`` of the
same prompt — regardless of co-batched traffic. The parity tests in
``tests/test_batching.py`` pin this across backends and trim configs,
and the fault-free, no-deadline path is byte-identical with or without
the watchdog (the watched call is the same computation).

Profiler spans: every step writes host spans into the profiler's trace
(``jax.profiler.TraceAnnotation``; without a running profiler a span
costs about a microsecond of host time, so they are always on). ``loom.engine.step``
(a step annotation, stats ``step_num``, ``n_active``) holds one span
per phase:

  * ``loom.engine.admit`` (``n_admitted``): retirements and the
    scheduler's assembly, holding per admission ``loom.engine.prefill``
    (``request_id``, ``slot``, ``prompt_len``: batch-1 cache, prefill
    and first-token read) and ``loom.engine.scatter`` (``request_id``,
    ``slot``: the copy into the pool);
  * ``loom.engine.decode`` (``n_active``): the host inputs and the
    batched decode's dispatch;
  * ``loom.engine.sync``: the argmax and its copy to the host, where the
    host waits for the device;
  * ``loom.engine.emit``: the slot loop, retirements, the shadow audit's
    drain when it is on, and the serving gauges.

Fault composition (with or without a :class:`ServingSupervisor`): the
decode jit DONATES the cache, so a fault that surfaces after execution
(e.g. NaN poisoning) leaves the old pool unusable — a naive step retry
is impossible. Instead the engine RESTARTS-AND-REPLAYS: fresh pool,
re-prefill every active request, regenerate deterministically while
suppressing tokens the streams already received (replayed tokens are
byte-identical by the parity property). Restarts are bounded by
``max_restarts`` consecutive failures; prefill faults retry per-request
and fail only that request's stream. Either way the QUEUE survives —
a faulted step degrades the session, never the engine.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import time
import warnings
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.api import guards
from repro.runtime import faults
from repro.runtime.batching import streams
from repro.runtime.batching.kvpool import KVPool
from repro.runtime.batching.scheduler import FCFSScheduler, Request

# Engine lifecycle states (see runtime/README.md for the state machine).
ACCEPTING, DRAINING, STOPPED = "accepting", "draining", "stopped"


def _retryable():
    from repro.runtime.serving import _RETRYABLE
    return _RETRYABLE


def _pct(ring, q: float) -> float:
    if not ring:
        return 0.0
    return float(np.percentile(np.asarray(ring, np.float64), q))


class BatchingEngine:
    """Continuous-batching front end over a compiled ServingSession.

    ``session``: a :class:`~repro.api.session.ServingSession` (LM), or a
    :class:`~repro.runtime.serving.ServingSupervisor` wrapping one — the
    engine then runs the supervisor's instrumented entry points (fault
    points + numeric-integrity checks fire per step), shares its
    :class:`ServeStats`, and degrades its health state on restarts.

    ``max_queue``: bound on queued requests (None = unbounded, the
    pre-ISSUE-9 behavior). ``step_timeout_s``: decode-watchdog deadline
    per step (None = no watchdog). ``overload_window_s``: how long after
    the last overload event (shed / rejection / deadline expiry /
    restart) the engine-local health stays ``degraded`` before
    recovering.
    """

    def __init__(self, session, *, max_batch: int = 8,
                 max_seq: int | None = None, max_restarts: int = 2,
                 prefill_retries: int = 2, backoff_s: float = 0.02,
                 max_queue: int | None = None,
                 step_timeout_s: float | None = None,
                 overload_window_s: float = 5.0,
                 latency_ring: int = 512,
                 audit_rate: float = 0.0,
                 audit_backend: str = "xla",
                 audit_bundle_dir: str = "audit_bundles",
                 integrity_every: int | None = None,
                 heal_dir: str | None = None):
        from repro.runtime import serving
        if isinstance(session, serving.ServingSupervisor):
            self.supervisor = session
            self.stats = session.stats
        else:
            self.supervisor = None
            self.stats = serving.ServeStats()
            self._bare_session = session
        if self.session._decode is None:
            raise ValueError(f"{self.session.cfg.name}: not an LM session "
                             f"(the batching engine serves decode loops)")
        self.max_batch = int(max_batch)
        self.max_restarts = int(max_restarts)
        self.prefill_retries = int(prefill_retries)
        self.backoff_s = float(backoff_s)
        self.step_timeout_s = step_timeout_s
        self.overload_window_s = float(overload_window_s)
        self.scheduler = FCFSScheduler(max_queue)
        self.pool = KVPool(self.session, self.max_batch, max_seq)
        self.max_seq = self.pool.max_seq
        self.active: dict[int, Request] = {}
        self.state = ACCEPTING
        self.last_drain_s = 0.0
        self._tok = np.zeros(self.max_batch, np.int32)
        self._pos = np.zeros(self.max_batch, np.int32)
        self._watchdog: concurrent.futures.ThreadPoolExecutor | None = None
        self._n_steps = 0
        self._n_decode_steps = 0
        self._occ_sum = 0
        self._busy_s = 0.0
        self._n_streamed = 0
        self._n_restarts = 0
        self._consec_restarts = 0
        self._n_reloads = 0
        self._last_overload_t = -float("inf")
        self._lat_sum = 0.0
        self._lat_n = 0
        self._lat_ring: deque[float] = deque(maxlen=int(latency_ring))
        self._wait_ring: deque[float] = deque(maxlen=int(latency_ring))
        # -- silent-corruption defense (ISSUE 10) ---------------------------
        # audit_rate > 0 attaches a ShadowAuditor: completed requests are
        # sampled and replayed on the reference oracle at step boundaries.
        # audit_rate == 0 builds NOTHING — the audit-off step loop is the
        # PR 9 step loop, byte for byte.
        self.auditor = None
        if audit_rate > 0.0:
            from repro.runtime.audit import ShadowAuditor
            self.auditor = ShadowAuditor(rate=audit_rate,
                                         ref_backend=audit_backend,
                                         bundle_dir=audit_bundle_dir)
        # integrity_every = N re-verifies the weight fingerprint every N
        # steps (None = off); heal_dir names the checkpoint dir a
        # violation self-heals from (else the engine fails loudly).
        self.integrity_every = None if integrity_every in (None, 0) \
            else int(integrity_every)
        self.heal_dir = heal_dir
        self._step_idx = 0

    @property
    def session(self):
        """The serving session (the supervisor's instrumented one when
        composed — so a rebuilt/degraded session is picked up live)."""
        if self.supervisor is not None:
            return self.supervisor.session
        return self._bare_session

    @property
    def max_queue(self) -> int | None:
        return self.scheduler.max_queue

    # -- public surface ------------------------------------------------------

    def submit(self, prompt, gen_len: int, *, deadline_s: float | None = None,
               block: bool = False,
               timeout: float | None = None) -> streams.StreamHandle:
        """Enqueue one request; returns its stream immediately.

        ``deadline_s``: per-request TTL — expired-while-queued requests
        are shed before prefill, in-flight requests past deadline retire
        at the next step boundary (typed ``RequestTimeoutError`` either
        way; partial tokens stay on the stream). ``block``/``timeout``:
        wait up to ``timeout`` seconds for a queue slot instead of
        raising ``QueueFullError`` immediately when the bounded queue is
        full (the engine must be stepping on another thread for a slot
        to free).
        """
        if self.state != ACCEPTING:
            raise guards.EngineClosedError(
                f"engine is {self.state}: not accepting new requests")
        try:
            req = self.scheduler.submit(prompt, gen_len,
                                        deadline_s=deadline_s,
                                        block=block, timeout=timeout)
        except guards.QueueFullError:
            self.stats.n_rejected += 1
            self._note_overload()
            raise
        self.stats.n_requests += 1
        self.stats.queue_depth = self.scheduler.depth
        return req.stream

    def step(self) -> bool:
        """One engine step (retire + admit + one batched decode). Returns
        True while there is work left (active slots or queued requests).
        An unexpected (non-healable) exception fails every live stream
        with the typed cause before propagating — streams never hang on
        a dead engine."""
        try:
            return self._step_inner()
        except Exception as exc:  # noqa: BLE001 — healable faults already
            #                       handled inside; anything here is fatal
            self._fail_all(exc)
            self._stop()
            raise

    def _step_inner(self) -> bool:
        with StepTraceAnnotation("loom.engine.step", step_num=self._n_steps,
                                 n_active=len(self.active)):
            self._n_steps += 1
            t0 = time.monotonic()
            self._integrity_tick()
            with TraceAnnotation("loom.engine.admit") as span:
                self._retire_cancelled()
                self._retire_expired(t0)
                span.set_metadata(n_admitted=self._admit(t0))
            toks = self._decode_once() if self.active else None
            with TraceAnnotation("loom.engine.emit"):
                if toks is not None:
                    self._emit_step(toks)
                self._audit_tick()
                self._busy_s += time.monotonic() - t0
                self._feed_stats()
            return bool(self.active) or self.scheduler.depth > 0

    def run(self, max_steps: int | None = None) -> None:
        """Drive :meth:`step` until the queue and the batch drain."""
        steps = 0
        while self.step():
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"engine did not drain within {max_steps} steps "
                    f"({len(self.active)} active, "
                    f"{self.scheduler.depth} queued)")

    # -- lifecycle: accepting -> draining -> stopped -------------------------

    def drain(self, max_steps: int | None = None) -> None:
        """Stop admissions, finish every queued + in-flight request, then
        stop. Terminal state: ``engine.state == "stopped"`` — submits
        afterwards raise a typed ``EngineClosedError``."""
        if self.state == STOPPED:
            return
        self.state = DRAINING
        t0 = time.monotonic()
        self.run(max_steps=max_steps)
        self.last_drain_s = time.monotonic() - t0
        self._stop()

    def shutdown(self, timeout: float) -> dict:
        """Drain with a wall-clock bound; fail residual streams loudly.

        Steps the engine until it drains or ``timeout`` seconds elapse;
        any request still live at the bound is failed with a typed
        ``EngineClosedError`` (partial tokens stay on its stream).
        Returns ``{"drained", "n_failed_residual", "elapsed_s"}``.
        """
        if self.state == STOPPED:
            return {"drained": True, "n_failed_residual": 0, "elapsed_s": 0.0}
        self.state = DRAINING
        t0 = time.monotonic()
        deadline = t0 + float(timeout)
        drained = False
        while time.monotonic() < deadline:
            if not self.step():
                drained = True
                break
        n_residual = 0
        if not drained:
            exc = guards.EngineClosedError(
                f"engine shut down after {timeout}s with work in flight")
            n_residual = self._fail_all(exc)
        self.last_drain_s = time.monotonic() - t0
        self._stop()
        return {"drained": drained, "n_failed_residual": n_residual,
                "elapsed_s": self.last_drain_s}

    def _stop(self) -> None:
        self.state = STOPPED
        if self._watchdog is not None:
            # cancel_futures + no join: an abandoned (stalled) decode
            # cannot be interrupted; its worker exits once it drains.
            self._watchdog.shutdown(wait=False, cancel_futures=True)
            self._watchdog = None

    def _fail_all(self, exc: BaseException) -> int:
        """Fail every live stream (active + queued) with the typed cause
        so ``result()``/iterators never block on a dead engine."""
        n = 0
        for req in [self.active[s] for s in sorted(self.active)]:
            self._retire(req, streams.FAILED, exc)
            n += 1
        for req in self.scheduler.drain_queue():
            if req.stream.cancel_requested:
                req.stream._finish(streams.CANCELLED)
            else:
                req.stream._finish(streams.FAILED, exc)
                n += 1
        self.stats.queue_depth = 0
        self.state = STOPPED
        return n

    def health(self) -> dict:
        """Supervisor health when composed, else an engine-local view:
        ``degraded`` while a restart has ever happened or an overload
        event (shed / rejection / deadline expiry) is within
        ``overload_window_s``; recovers to ``healthy`` once the window
        passes with clean serving."""
        if self.supervisor is not None:
            h = self.supervisor.health()
            h["engine_state"] = self.state
            return h
        from repro.runtime import serving
        overloaded = (time.monotonic() - self._last_overload_t
                      < self.overload_window_s)
        state = serving.DEGRADED if (self._n_restarts or overloaded) \
            else serving.HEALTHY
        return {"state": state, "backend": self.session.plan.backend.name,
                "engine_state": self.state, "fallbacks": {},
                "stats": dataclasses.asdict(self.stats)}

    # -- request lifecycle ---------------------------------------------------

    def _note_overload(self) -> None:
        self._last_overload_t = time.monotonic()

    def _retire(self, req: Request, state: str,
                error: BaseException | None = None) -> None:
        if req.slot in self.active:
            del self.active[req.slot]
            self.pool.free(req.slot)
        req.stream._finish(state, error)
        if state == streams.DONE:
            self.stats.n_ok += 1
            lat = time.monotonic() - req.submit_t
            self._lat_sum += lat
            self._lat_n += 1
            self._lat_ring.append(lat)
            if self.auditor is not None:
                self.auditor.observe(req)
        elif state == streams.FAILED:
            self.stats.n_failed += 1
            self.stats.last_error = f"{type(error).__name__}: {error}"

    def _retire_cancelled(self) -> None:
        for req in [r for r in self.active.values()
                    if r.stream.cancel_requested]:
            self._retire(req, streams.CANCELLED)

    def _retire_expired(self, now: float) -> None:
        """In-flight requests past deadline retire at this step boundary;
        partial tokens stay available on the stream."""
        for req in [r for r in self.active.values() if r.expired(now)]:
            self.stats.n_deadline_expired += 1
            self._note_overload()
            del self.active[req.slot]
            self.pool.free(req.slot)
            req.stream._finish(streams.FAILED, guards.RequestTimeoutError(
                f"request {req.request_id}: deadline exceeded in flight "
                f"after {req.n_emitted}/{req.gen_len} tokens (partial "
                f"tokens retained on the stream)"))

    def _admit(self, now: float | None = None) -> int:
        """Shed, drop and admit queued requests; the number admitted."""
        admitted, dropped, expired = self.scheduler.assemble(
            self.pool.n_free, now)
        for req in dropped:
            req.stream._finish(streams.CANCELLED)
        for req in expired:
            self.stats.n_shed += 1
            self._note_overload()
            req.stream._finish(streams.FAILED, guards.RequestTimeoutError(
                f"request {req.request_id}: deadline exceeded while queued "
                f"— shed before prefill"))
        for req in admitted:
            self._wait_ring.append(time.monotonic() - req.submit_t)
            self._place(req)
        if admitted or dropped or expired:
            self.stats.queue_depth = self.scheduler.depth
        return len(admitted)

    def _place(self, req: Request) -> None:
        """Prefill ``req`` into a free slot (bounded per-request retries);
        a prefill that cannot heal fails ONLY this request's stream."""
        slot = self.pool.alloc()
        req.slot = slot
        req.stream._set_state(streams.PREFILLING)
        try:
            self._prefill_into(req)
        except Exception as exc:  # noqa: BLE001 — typed/classified upstream
            self.pool.free(slot)
            req.slot = -1
            self._retire(req, streams.FAILED, exc)
            return
        self.active[slot] = req
        self._tok[slot] = req.token
        self._pos[slot] = req.pos
        req.stream._set_state(streams.DECODING)
        if req.finished:           # gen_len == 1: the prefill token is all
            self._retire(req, streams.DONE)

    def _prefill_into(self, req: Request) -> None:
        s = int(req.prompt.shape[0])
        if s + req.gen_len > self.max_seq:
            raise ValueError(
                f"request {req.request_id}: prompt_len {s} + gen_len "
                f"{req.gen_len} exceeds the pool's max_seq {self.max_seq}")
        with TraceAnnotation("loom.engine.prefill",
                             request_id=req.request_id, slot=req.slot,
                             prompt_len=s):
            tokens = jnp.asarray(req.prompt[None, :], jnp.int32)
            attempt = 0
            while True:
                try:
                    cache1 = self.session.init_cache(1, self.max_seq)
                    logits, cache1 = self.session.prefill(tokens,
                                                          cache=cache1)
                    tok0 = int(jnp.argmax(logits[:, 0], axis=-1)[0])
                    break
                except _retryable() as exc:
                    if attempt >= self.prefill_retries:
                        raise
                    attempt += 1
                    self.stats.n_retries += 1
                    self._degrade(exc)
                    time.sleep(self.backoff_s * (2 ** (attempt - 1)))
        with TraceAnnotation("loom.engine.scatter",
                             request_id=req.request_id, slot=req.slot):
            self.pool.scatter_prefill(req.slot, cache1)
        req.pos = s
        self._emit(req, tok0)

    def _emit(self, req: Request, token: int) -> None:
        if req.emit(token):
            self._n_streamed += 1
            self.stats.n_tokens_streamed = self._n_streamed

    # -- the batched decode step ---------------------------------------------

    def _watched_decode(self):
        """One batched decode, optionally under the watchdog's per-step
        deadline. The watched call is the SAME computation either way
        (fault-free numerics are byte-identical with or without the
        watchdog); a step that exceeds ``step_timeout_s`` surfaces as a
        typed ``StepStallError`` — the cache was donated to the stalled
        call, so the caller routes into restart-and-replay."""
        tok = jnp.asarray(self._tok)
        pos = jnp.asarray(self._pos)
        cache = self.pool.cache

        def call():
            faults.fire("engine.step_stall", detail="decode")
            return self.session.decode(tok, pos, cache)

        if self.step_timeout_s is None:
            return call()
        if self._watchdog is None:
            # >1 worker so the step after an abandoned stall is not
            # queued behind the still-draining stalled call.
            self._watchdog = concurrent.futures.ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="engine-watchdog")
        fut = self._watchdog.submit(call)
        try:
            return fut.result(timeout=self.step_timeout_s)
        except concurrent.futures.TimeoutError:
            # The stalled call cannot be cancelled; its worker drains in
            # the background. The STEP times out, with a typed error.
            raise guards.StepStallError(
                f"decode step exceeded step_timeout_s="
                f"{self.step_timeout_s}") from None

    def _decode_once(self) -> np.ndarray | None:
        """One batched decode; its argmax tokens on the host, or None
        when the step faulted and the engine restarted instead."""
        try:
            with TraceAnnotation("loom.engine.decode",
                                 n_active=len(self.active)):
                logits, cache = self._watched_decode()
                self.pool.cache = cache
            with TraceAnnotation("loom.engine.sync"):
                toks = np.asarray(
                    jnp.argmax(logits, axis=-1).astype(jnp.int32))
        except _retryable() as exc:
            self._restart(exc)
            return None
        return toks

    def _emit_step(self, toks: np.ndarray) -> None:
        """Stream each active slot's token; retire finished requests."""
        self._consec_restarts = 0
        self._n_decode_steps += 1
        self._occ_sum += len(self.active)
        for slot in sorted(self.active):
            req = self.active[slot]
            self._emit(req, int(toks[slot]))
            req.pos += 1
            self._tok[slot] = req.token
            self._pos[slot] = req.pos
            if req.finished:
                self._retire(req, streams.DONE)
            elif req.stream.cancel_requested:
                self._retire(req, streams.CANCELLED)

    # -- silent-corruption defense (integrity cadence + shadow audit) ---------

    def _integrity_tick(self) -> None:
        """Every ``integrity_every`` steps: re-verify the weight CRC32
        fingerprint (+ pass-law plan metadata). A violation (e.g. the
        ``weights.bitflip`` chaos point, applied right here so the
        corrupted planes NEVER serve a decode undetected) self-heals
        through the existing CRC-verified :meth:`reload_checkpoint` path
        when ``heal_dir`` is configured, else fails the engine loudly —
        corrupt weights are never served silently either way."""
        if self.integrity_every is None \
                or self.session.fingerprint is None:
            return
        tick = self._step_idx % self.integrity_every == 0
        self._step_idx += 1
        if not tick:
            return
        if faults.take("weights.bitflip"):
            from repro.core import integrity as integ
            self.session.params, leaf = integ.flip_one_bit(
                self.session.params)
            warnings.warn(f"[chaos] weights.bitflip: flipped one bit of "
                          f"leaf {leaf!r}", RuntimeWarning, stacklevel=2)
        self.stats.n_integrity_checks += 1
        try:
            self.session.verify_integrity("engine integrity tick")
        except guards.WeightIntegrityError as exc:
            self._note_overload()
            self._degrade(exc)
            self.stats.last_error = f"{type(exc).__name__}: {exc}"
            if self.heal_dir is None:
                raise
            warnings.warn(
                f"[engine] weight integrity violation — self-healing from "
                f"the last good checkpoint in {self.heal_dir!r} ({exc})",
                RuntimeWarning, stacklevel=2)
            self.reload_checkpoint(self.heal_dir)

    def _audit_tick(self) -> None:
        """Drain the shadow auditor's sampled requests (off the hot path:
        after the batched decode, never inside it). Any divergence
        quarantines the serving backend once — every further token comes
        off the fallback chain — and counts in the stats; the repro
        bundle was already written by the auditor."""
        if self.auditor is None or not self.auditor.n_pending:
            return
        n, results = self.auditor.drain(self.session)
        self.stats.n_audits += n
        failures = [r for r in results if not r.ok]
        if failures:
            self.stats.n_divergences += len(failures)
            self._quarantine(failures[0].error)

    def _quarantine(self, exc: BaseException) -> None:
        """Silent divergence response: sticky-demote the serving backend
        (``GuardedBackend.quarantine``), re-jit the session so the next
        trace re-dispatches through the degraded chain, and replay the
        survivors — their post-quarantine suffix comes from the trusted
        substrate. Unguarded sessions cannot demote a backend; health
        still degrades and the divergence stays counted + bundled."""
        self.stats.n_quarantines += 1
        self.stats.last_error = f"{type(exc).__name__}: {exc}"
        self._note_overload()
        self._degrade(exc)
        be = self.session.plan.backend
        if hasattr(be, "quarantine"):
            be.quarantine(str(exc))
        self._rejit_session()
        self._replay_survivors()

    def _rejit_session(self) -> None:
        """Swap in fresh jit wrappers for the current session (same
        cfg/plan/params) — re-instrumented when supervised, so the fault
        points and numeric-integrity checks stay attached."""
        fresh = self.session.rejit()
        if self.supervisor is not None:
            self.supervisor._session = self.supervisor._instrument(fresh)
        else:
            self._bare_session = fresh

    # -- restart-and-replay ----------------------------------------------------

    def _degrade(self, exc: BaseException) -> None:
        self.stats.last_error = f"{type(exc).__name__}: {exc}"
        if self.supervisor is not None:
            from repro.runtime import serving
            if self.supervisor.state == serving.HEALTHY:
                self.supervisor.state = serving.DEGRADED

    def _replay_survivors(self) -> None:
        """Rebuild the pool and REPLAY every active request from its
        prompt, suppressing already-delivered tokens (deterministic
        regeneration => the suppressed prefix is byte-identical to what
        the streams already saw — under unchanged weights; after a hot
        swap the suffix is the new checkpoint's stream)."""
        survivors = [self.active[s] for s in sorted(self.active)]
        self.active.clear()
        self._tok[:] = 0
        self._pos[:] = 0
        self.pool = KVPool(self.session, self.max_batch, self.max_seq)
        for req in survivors:
            req.n_generated = 0
            req.token = 0
            req.pos = 0
            self._place(req)

    def _restart(self, exc: BaseException) -> None:
        """A decode step faulted. The decode jit donates the cache, so the
        pool may be gone either way — rebuild it and replay the
        survivors (:meth:`_replay_survivors`)."""
        self._consec_restarts += 1
        self._n_restarts += 1
        self.stats.n_engine_restarts = self._n_restarts
        self._note_overload()
        self._degrade(exc)
        if self._consec_restarts > self.max_restarts:
            from repro.runtime import serving
            if self.supervisor is not None:
                self.supervisor.state = serving.FAILED
            survivors = [self.active[s] for s in sorted(self.active)]
            self.active.clear()
            self._tok[:] = 0
            self._pos[:] = 0
            self.pool = KVPool(self.session, self.max_batch, self.max_seq)
            for req in survivors:
                req.slot = -1
                self._retire(req, streams.FAILED, exc)
            return
        self._replay_survivors()

    # -- hot checkpoint swap ---------------------------------------------------

    def reload(self, params, *, specs=None) -> None:
        """Hot-swap serving weights between steps (no engine restart).

        ``params``: a DENSE-layout param tree (the training/checkpoint
        layout, as produced by ``model.init_params`` or restored by
        ``ckpt.restore_checkpoint``); it is run through the same serving
        conversion ``loom.compile`` uses, validated against the compiled
        plan (tree structure, per-leaf shape/dtype, and — when the plan
        recorded pack-time weight-group counts — count equality, since
        those are trace-time constants a swap cannot change), and only
        then swapped in. Survivors are re-prefilled under the new
        weights via restart-and-replay: every token emitted after the
        swap is byte-identical to what a fresh engine started on the new
        checkpoint would emit at that position. A typed
        ``ReloadMismatchError`` leaves the engine serving the old
        weights untouched.
        """
        from repro.api.session import _SERVING_MODES
        from repro.models import model as M
        if self.state == STOPPED:
            raise guards.EngineClosedError("engine is stopped: cannot reload")
        plan = self.session.plan
        if specs is None:
            _, specs = M.init_params(jax.random.PRNGKey(0), self.session.cfg)
        if plan.mode in _SERVING_MODES:
            try:
                converted, _ = M.convert_params_for_serving(
                    params, specs, plan.policy, plan.mode)
            except Exception as exc:  # noqa: BLE001 — conversion rejects
                raise guards.ReloadMismatchError(
                    f"new param tree failed the serving conversion for "
                    f"mode={plan.mode!r}: {type(exc).__name__}: {exc}"
                ) from exc
        else:
            converted = params
        self._validate_swap(converted)
        self._check_weight_groups(converted)
        self.session.params = converted
        # The swap is intentional: re-anchor the integrity fingerprint to
        # the new weights, and drop the auditor's reference session +
        # pending records (they were produced by the old weights).
        if self.session.fingerprint is not None:
            self.session.refingerprint()
        if self.auditor is not None:
            self.auditor.invalidate_reference()
        self._n_reloads += 1
        self.stats.n_reloads = self._n_reloads
        self._replay_survivors()

    def reload_checkpoint(self, ckpt_dir: str, step: int | None = None) -> int:
        """Hot-swap from an on-disk checkpoint: CRC/shape/dtype-verified
        restore (``ckpt`` manifest; corrupt steps fall back to the
        previous good one) followed by :meth:`reload`. Returns the step
        actually loaded."""
        from repro.ckpt import checkpoint as ckpt
        from repro.models import model as M
        skel, specs = M.init_params(jax.random.PRNGKey(0), self.session.cfg)
        if step is None:
            params, got = ckpt.restore_latest(ckpt_dir, skel)
            if params is None:
                raise guards.ReloadMismatchError(
                    f"no checkpoints found in {ckpt_dir!r}")
        else:
            params, got = ckpt.restore_checkpoint(ckpt_dir, step, skel)
        self.reload(params, specs=specs)
        return got

    def _validate_swap(self, converted) -> None:
        """New tree must match the compiled plan's param tree exactly in
        structure, per-leaf shape, and dtype (the jit traces are keyed on
        those; a mismatch would retrace or miscompute)."""
        cur = jax.tree_util.tree_flatten_with_path(self.session.params)
        new = jax.tree_util.tree_flatten_with_path(converted)
        if cur[1] != new[1]:
            raise guards.ReloadMismatchError(
                "new param tree structure does not match the compiled "
                "plan's (different layers/keys) — recompile instead of "
                "hot-swapping")
        for (path, c), (_, n) in zip(cur[0], new[0]):
            key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                           for p in path)
            if tuple(np.shape(c)) != tuple(np.shape(n)):
                raise guards.ReloadMismatchError(
                    f"leaf {key!r}: shape {tuple(np.shape(n))} != plan's "
                    f"{tuple(np.shape(c))}")
            c_dt, n_dt = np.asarray(c).dtype, np.asarray(n).dtype
            if c_dt != n_dt:
                raise guards.ReloadMismatchError(
                    f"leaf {key!r}: dtype {n_dt} != plan's {c_dt}")

    def _check_weight_groups(self, converted) -> None:
        """Pack-time weight-group counts are TRACE-TIME constants baked
        into the plan — a swap that changes them silently would execute
        the wrong plane partitions. Recompute from the new packed head
        and require equality; a mismatch means the new checkpoint needs
        a recompile, not a hot swap."""
        plan = self.session.plan
        if not getattr(plan.policy, "w_group", 0):
            return
        from repro.core import weightgroups
        named = {"lm_head": converted.get("head", {})} \
            if isinstance(converted, dict) else {}
        for (name, kind), lp in plan.layers.items():
            if not lp.w_group_counts:
                continue
            p = named.get(name)
            wp = p.get("w_packed") if isinstance(p, dict) else None
            if wp is None or getattr(wp, "ndim", 0) != 3:
                continue
            counts = tuple(int(v) for v in np.asarray(
                weightgroups.packed_group_counts(wp, wp.shape[0],
                                                 lp.w_group)))
            if counts != lp.w_group_counts:
                raise guards.ReloadMismatchError(
                    f"layer {name!r} ({kind}): packed weight-group counts "
                    f"{counts} != the plan's trace-time "
                    f"{lp.w_group_counts} — the new checkpoint changes "
                    f"the execution plan; recompile instead of hot-"
                    f"swapping")

    # -- metrics ---------------------------------------------------------------

    def _feed_stats(self) -> None:
        occ = self._occ_sum / max(1, self._n_decode_steps)
        self.stats.note_serving(
            queue_depth=self.scheduler.depth,
            batch_occupancy=occ,
            tokens_per_s=self._n_streamed / max(self._busy_s, 1e-9),
            mean_request_latency_s=self._lat_sum / max(1, self._lat_n),
            n_tokens_streamed=self._n_streamed,
            n_engine_restarts=self._n_restarts,
            p50_request_latency_s=_pct(self._lat_ring, 50),
            p95_request_latency_s=_pct(self._lat_ring, 95),
            p50_queue_wait_s=_pct(self._wait_ring, 50),
            p95_queue_wait_s=_pct(self._wait_ring, 95),
            p95_audit_lag_s=self.auditor.lag_p95()
            if self.auditor is not None else 0.0)
