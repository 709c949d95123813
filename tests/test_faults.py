"""Chaos suite: every registered fault point heals or fails loudly.

The acceptance contract of the fault-tolerant serving runtime
(``repro.runtime.faults.FAULT_POINTS``):

    backend.op         -> sticky fallback down the chain, or a typed
                          FallbackExhaustedError; transients re-raise
    serve.step         -> supervisor retry (kill-and-resume byte-identical)
                          / typed RequestTimeoutError on slow steps
    serve.nan_poison   -> typed NumericIntegrityError, healed by retry
    ckpt.leaf_corrupt  -> CRC reject + fallback to the previous good step
    ckpt.crash_rename  -> torn save never shadows the previous checkpoint

plus the bit-transparency invariant: guarded serving (GuardedBackend +
ServingSupervisor) is byte-identical to unguarded serving on the
fault-free path, across {xla, pallas_interpret} for both the LM and the
paper-CNN sessions.

Every test here is also tier-1 (the chaos marker selects, it does not
deselect): faults are injected deterministically, so nothing is flaky.
"""
import functools
import os
import signal
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from repro import configs
from repro.api import backend as backendlib
from repro.api import guards
from repro.api import session as loom
from repro.ckpt import checkpoint as ck
from repro.core import bitpack
from repro.core.policy import uniform_policy
from repro.runtime import faults
from repro.runtime.serving import (DEGRADED, FAILED, HEALTHY,
                                   ServingSupervisor)
from repro.runtime.supervisor import Supervisor, TransientWorkerError

pytestmark = pytest.mark.chaos


# Fault-registry hygiene (reset + leak check) is the repo-root autouse
# fixture ``_no_fault_leaks`` in conftest.py.

# -- shared compiled sessions (cached: compiles dominate the suite) ---------

POLICY = uniform_policy(8, 8)


@functools.lru_cache(maxsize=None)
def _cnn_session(backend: str, guarded: bool):
    cfg = configs.get("paper_cnn", smoke=True)
    return loom.compile(cfg, POLICY, mode="serve_packed", backend=backend,
                        guarded=guarded, rng=0)


@functools.lru_cache(maxsize=None)
def _lm_session(backend: str, guarded: bool):
    cfg = configs.get("qwen3-1.7b", smoke=True)
    return loom.compile(cfg, POLICY, mode="serve_packed", backend=backend,
                        guarded=guarded, rng=0)


def _cnn_inputs(batch: int = 2):
    cfg = configs.get("paper_cnn", smoke=True)
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(size=(batch, cfg.img, cfg.img, cfg.in_ch)),
                       jnp.float32)


def _lm_tokens(batch: int = 2, s: int = 8):
    cfg = configs.get("qwen3-1.7b", smoke=True)
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(1, cfg.vocab, size=(batch, s)), jnp.int32)


def _matmul_operands(m: int = 4, k: int = 16, n: int = 8, w_bits: int = 8):
    rng = np.random.default_rng(0)
    xq = jnp.asarray(rng.integers(-128, 128, size=(m, k)), jnp.int8)
    wq = jnp.asarray(rng.integers(-(1 << (w_bits - 1)), 1 << (w_bits - 1),
                                  size=(k, n)), jnp.int32)
    return xq, bitpack.pack_weights(wq, w_bits)


# -- fault registry semantics ----------------------------------------------


def test_unknown_fault_point_rejected():
    with pytest.raises(faults.UnknownFaultPoint):
        with faults.inject("no.such.point"):
            pass
    with pytest.raises(faults.UnknownFaultPoint):
        faults.fire("no.such.point")          # fast path still validates
    with pytest.raises(faults.UnknownFaultPoint):
        faults.take("no.such.point")
    with pytest.raises(faults.UnknownFaultPoint):
        faults.active("no.such.point")


def test_fault_times_match_and_fired_counter():
    with faults.inject("serve.step", exc=RuntimeError("boom"), times=2,
                       match="decode") as fault:
        faults.fire("serve.step", detail="prefill")       # match filter
        for _ in range(2):
            with pytest.raises(RuntimeError):
                faults.fire("serve.step", detail="decode")
        faults.fire("serve.step", detail="decode")        # times exhausted
        assert fault.fired == 2
    assert faults.active("serve.step") is None            # context exit


def test_take_counts_without_raising():
    with faults.inject("ckpt.leaf_corrupt") as fault:     # no exc: effect
        assert faults.take("ckpt.leaf_corrupt") is True   # site applies it
        assert faults.take("ckpt.leaf_corrupt") is False  # times=1 default
        assert fault.fired == 1
    assert faults.take("ckpt.leaf_corrupt") is False


def test_inject_restores_registry_when_body_raises():
    """Regression for the leak the autouse conftest fixture polices: a
    body that raises must still disarm the fault on context exit."""
    with pytest.raises(RuntimeError, match="body died"):
        with faults.inject("weights.bitflip", times=None):
            assert faults.active_points() == ("weights.bitflip",)
            raise RuntimeError("body died")
    assert faults.active("weights.bitflip") is None
    assert faults.active_points() == ()


# -- typed error taxonomy ---------------------------------------------------


def test_classify_error_taxonomy():
    assert guards.classify_error(TransientWorkerError("kill")) \
        == guards.TRANSIENT
    assert guards.classify_error(RuntimeError("connection reset by peer")) \
        == guards.TRANSIENT
    assert guards.classify_error(RuntimeError("Mosaic lowering failed")) \
        == guards.COMPILE
    assert guards.classify_error(RuntimeError("RESOURCE_EXHAUSTED: vmem")) \
        == guards.RESOURCE
    assert guards.classify_error(guards.BackendShapeError("bad")) \
        == guards.SHAPE
    assert guards.classify_error(ValueError("operand shape mismatch")) \
        == guards.SHAPE
    assert guards.classify_error(RuntimeError("???")) == guards.FATAL


def test_accum_bound_math_agrees_with_kernels():
    from repro.kernels.ops import conv_accum_fits_f32
    for k, a, w in [(9 * 9 * 64, 8, 8), (576, 4, 4), (1 << 20, 8, 11),
                    (27, 2, 2), (4096, 8, 8)]:
        assert guards.accum_fits_f32(k, a, w) == conv_accum_fits_f32(k, a, w)
    guards.check_accum_bound(4096, 8, 8)                  # fits int32
    with pytest.raises(guards.AccumulatorOverflowError):
        guards.check_accum_bound(1 << 20, 8, 11)          # 37 bits > 31


def test_guarded_accum_overflow_fails_loudly():
    # a_bits is operand metadata, so a deep-precision claim over a tiny
    # reduction exercises the guard without a giant operand.
    xq, wp = _matmul_operands()
    gb = backendlib.GuardedBackend("xla")
    with pytest.raises(guards.AccumulatorOverflowError):
        gb.matmul_planes(xq, wp, w_bits=8, a_bits=25)
    assert gb.fallbacks_by_op == {}       # fail-loud, never fall back


@pytest.mark.parametrize("platform,chain", [
    ("cpu", ["pallas_interpret", "xla"]),
    ("tpu", ["xla"]),
])
def test_default_fallback_chain_has_no_interpreter_on_tpu(monkeypatch,
                                                          platform, chain):
    """On a TPU a kernel Mosaic refuses falls straight to XLA: running
    the Pallas interpreter there is never a recovery."""
    monkeypatch.setattr(backendlib.jax, "default_backend", lambda: platform)
    gb = backendlib.GuardedBackend("pallas_tpu")
    assert [b.name for b in gb.chain[1:]] == chain
    sess = loom.compile(configs.get("qwen3-1.7b", smoke=True),
                        uniform_policy(8, 8), mode="serve_packed")
    assert ServingSupervisor(sess).fallback_backends == chain


def test_guarded_shape_guard_fails_loudly():
    xq, wp = _matmul_operands(k=16)
    gb = backendlib.GuardedBackend("xla")
    bad_x = jnp.zeros((4, 32), jnp.int8)  # logical K=32 vs packed K=16
    with pytest.raises(guards.BackendShapeError):
        gb.matmul_planes(bad_x, wp, w_bits=8)
    assert gb.fallbacks_by_op == {}


def test_guarded_dynamic_quant_rejects_nonfinite_input():
    gb = backendlib.GuardedBackend("xla")
    x = jnp.asarray(np.array([[1.0, np.nan, 2.0, 3.0]], np.float32))
    with pytest.raises(guards.NumericIntegrityError):
        gb.dynamic_quant(x, group_size=4, bits=8)


# -- backend.op: fallback chain --------------------------------------------


def test_backend_op_transient_reraises_then_heals():
    xq, wp = _matmul_operands()
    gb = backendlib.GuardedBackend("xla")
    with faults.inject("backend.op", exc=TransientWorkerError("preempted"),
                       times=1, match="matmul_planes"):
        with pytest.raises(TransientWorkerError):
            gb.matmul_planes(xq, wp, w_bits=8)
        assert gb.fallbacks_by_op == {}   # transient: substrate is fine
        out = gb.matmul_planes(xq, wp, w_bits=8)          # retry heals
    ref = backendlib.get_backend("xla").matmul_planes(xq, wp, w_bits=8)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_backend_op_fallback_exhausted_typed_error():
    xq, wp = _matmul_operands()
    gb = backendlib.GuardedBackend("xla")     # chain is [xla] only
    with faults.inject("backend.op", exc=RuntimeError("mosaic fail"),
                       times=None, match="matmul_planes"):
        with pytest.raises(guards.FallbackExhaustedError):
            gb.matmul_planes(xq, wp, w_bits=8)


def test_backend_op_sticky_fallback_is_exact():
    """A permanent pallas_interpret failure degrades every op to xla —
    recorded on the plan — and the degraded output is exactly the xla
    reference (fallback must never change values)."""
    cfg = configs.get("paper_cnn", smoke=True)
    sess = loom.compile(cfg, POLICY, mode="serve_packed",
                        backend="pallas_interpret", guarded=True, rng=0)
    ref = _cnn_session("xla", False).classify(_cnn_inputs())
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with faults.inject("backend.op",
                           exc=RuntimeError("mosaic lowering failed"),
                           times=None, match=":pallas_interpret") as fault:
            out = sess.classify(_cnn_inputs())
    assert fault.fired >= 1
    report = sess.plan.fallback_report()
    assert report and all(v == "xla" for v in report.values())
    assert sess.plan.backend.active_backend(next(iter(report))).name == "xla"
    assert any("falling back" in str(w.message) for w in caught)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# -- bit-transparency acceptance: guarded == unguarded ----------------------


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_guarded_cnn_bit_identical(backend):
    base = _cnn_session(backend, False).classify(_cnn_inputs())
    sess = _cnn_session(backend, True)
    assert np.array_equal(np.asarray(base),
                          np.asarray(sess.classify(_cnn_inputs())))
    assert sess.plan.fallback_report() == {}
    sup = ServingSupervisor(sess)
    assert np.array_equal(np.asarray(base),
                          np.asarray(sup.classify(_cnn_inputs())))
    assert sup.health()["state"] == HEALTHY


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_guarded_lm_bit_identical(backend):
    base = _lm_session(backend, False).generate(_lm_tokens(), 4)
    sess = _lm_session(backend, True)
    assert np.array_equal(base, sess.generate(_lm_tokens(), 4))
    assert sess.plan.fallback_report() == {}
    sup = ServingSupervisor(sess)
    assert np.array_equal(base, sup.generate(_lm_tokens(), 4))
    assert sup.health()["state"] == HEALTHY


# -- serve.step: kill-and-resume / timeout / health -------------------------


def test_kill_and_resume_generate_byte_identical():
    """Satellite: a TransientWorkerError mid-generate is retried and the
    healed token stream is byte-identical to an uninterrupted run."""
    sess = _lm_session("xla", False)
    base = sess.generate(_lm_tokens(), 4)
    sup = ServingSupervisor(sess, backoff_s=0.001)
    with faults.inject("serve.step",
                       exc=TransientWorkerError("worker killed mid-decode"),
                       times=1, match="decode") as fault:
        out = sup.generate(_lm_tokens(), 4)
    assert fault.fired == 1
    assert np.array_equal(base, out)
    assert sup.stats.n_retries == 1 and sup.stats.n_ok == 1
    assert sup.state == DEGRADED          # the episode stays visible


def test_slow_step_times_out_typed_then_heals():
    sess = _cnn_session("xla", False)
    base = sess.classify(_cnn_inputs())
    sup = ServingSupervisor(sess, timeout_s=0.75, backoff_s=0.001)
    sup2 = ServingSupervisor(sess, timeout_s=0.5, max_retries=0)
    try:
        with faults.inject("serve.step", delay=3.0, times=1,
                           match="classify"):
            out = sup.classify(_cnn_inputs())
        assert sup.stats.n_timeouts == 1 and sup.stats.n_retries == 1
        assert np.array_equal(np.asarray(base), np.asarray(out))
        # exhausted retries surface the typed error, not a hang
        with faults.inject("serve.step", delay=3.0, times=None,
                           match="classify"):
            with pytest.raises(guards.RequestTimeoutError):
                sup2.classify(_cnn_inputs())
        assert sup2.state == FAILED
    finally:
        sup.close()
        sup2.close()


def test_nan_poison_caught_and_healed():
    sess = _cnn_session("xla", False)
    base = sess.classify(_cnn_inputs())
    sup = ServingSupervisor(sess, backoff_s=0.001)
    with faults.inject("serve.nan_poison", times=1, match="classify"):
        out = sup.classify(_cnn_inputs())
    assert sup.stats.n_numeric_faults == 1
    assert np.array_equal(np.asarray(base), np.asarray(out))


def test_nan_poison_exhausted_fails_loudly_then_degraded():
    """Persistent poisoning -> typed error (never argmax over NaN); a
    later clean request moves failed -> degraded, never back to healthy."""
    sess = _cnn_session("xla", False)
    sup = ServingSupervisor(sess, max_retries=1, backoff_s=0.001)
    with faults.inject("serve.nan_poison", times=None, match="classify"):
        with pytest.raises(guards.NumericIntegrityError):
            sup.classify(_cnn_inputs())
    assert sup.state == FAILED
    out = sup.classify(_cnn_inputs())     # fault gone: serving works again
    assert np.array_equal(np.asarray(out),
                          np.asarray(sess.classify(_cnn_inputs())))
    assert sup.state == DEGRADED


def test_session_level_degrade_rebuilds_on_compile_fault():
    """A permanent (compile-class) fault escaping the session degrades the
    WHOLE session down fallback_backends via the rebuild hook, and the
    rebuilt backend serves the same answer (cross-backend invariant)."""
    base = np.asarray(_cnn_session("xla", False).classify(_cnn_inputs()))
    sup = ServingSupervisor(
        _cnn_session("pallas_interpret", False),
        rebuild=lambda name: _cnn_session(name, False),
        fallback_backends=("pallas_interpret", "xla"))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with faults.inject("serve.step",
                           exc=RuntimeError("XLA compilation failed"),
                           times=1, match="classify"):
            out = sup.classify(_cnn_inputs())
    assert np.array_equal(base, np.asarray(out))
    assert sup.stats.n_session_fallbacks == 1
    assert sup.health()["backend"] == "xla"
    assert sup.state == DEGRADED
    assert any("rebuilding" in str(w.message) for w in caught)


# -- checkpoint integrity + durability --------------------------------------


def _tree(seed: int = 0):
    rng = np.random.default_rng(seed)
    return {"w": rng.normal(size=(4, 4)).astype(np.float32),
            "b": np.arange(4, dtype=np.float32)}


def _assert_tree_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


def test_ckpt_leaf_corrupt_falls_back_to_previous_good(tmp_path):
    d = str(tmp_path)
    good = _tree(1)
    ck.save_checkpoint(d, 1, good)
    with faults.inject("ckpt.leaf_corrupt"):
        ck.save_checkpoint(d, 2, _tree(2))
    with pytest.raises(ck.CheckpointCorruptError):
        ck.restore_checkpoint(d, 2, _tree(0))
    with pytest.warns(RuntimeWarning, match="corrupt"):
        state, step = ck.restore_latest(d, _tree(0))
    assert step == 1
    _assert_tree_equal(state, good)


def test_ckpt_all_corrupt_fails_loudly(tmp_path):
    d = str(tmp_path)
    with faults.inject("ckpt.leaf_corrupt"):
        ck.save_checkpoint(d, 1, _tree(1))
    with pytest.warns(RuntimeWarning):
        with pytest.raises(ck.CheckpointCorruptError):
            ck.restore_latest(d, _tree(0))
    assert ck.restore_latest(str(tmp_path / "empty"), _tree(0)) == (None,
                                                                    None)


def test_ckpt_crash_before_rename_never_shadows_previous(tmp_path):
    d = str(tmp_path)
    good = _tree(1)
    ck.save_checkpoint(d, 1, good)
    with faults.inject("ckpt.crash_rename",
                       exc=RuntimeError("simulated crash")):
        with pytest.raises(RuntimeError, match="simulated crash"):
            ck.save_checkpoint(d, 2, _tree(2))
    assert ck.latest_step(d) == 1         # torn save is invisible
    state, step = ck.restore_latest(d, _tree(0))
    assert step == 1
    _assert_tree_equal(state, good)
    ck.save_checkpoint(d, 2, _tree(2))    # clean retry reuses the tmp dir
    assert ck.latest_step(d) == 2


def test_ckpt_async_save_exception_surfaces_on_wait(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path), every=1, keep_n=2)
    with faults.inject("ckpt.crash_rename", exc=RuntimeError("disk died"),
                       times=None):
        mgr.save_async(1, _tree(1))
        with pytest.raises(RuntimeError, match="disk died"):
            mgr.wait()
    mgr.save_async(2, _tree(2))           # manager still usable after
    mgr.wait()
    assert ck.latest_step(str(tmp_path)) == 2


def test_ckpt_manifest_has_crc_and_bf16_roundtrips(tmp_path):
    import json
    import ml_dtypes
    d = str(tmp_path)
    tree = _tree(3)
    path = ck.save_checkpoint(d, 5, tree, compress="bf16")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    assert all("crc32" in meta for meta in manifest["leaves"].values())
    state, step = ck.restore_latest(d, tree)
    assert step == 5
    for k in tree:
        expect = tree[k].astype(ml_dtypes.bfloat16).astype(np.float32)
        assert np.array_equal(np.asarray(state[k]), expect), k


# -- training supervisor: spike-guard seeding + SIGTERM handoff -------------


def test_spike_guard_survives_nonfinite_seed():
    """A non-finite FIRST loss must not seed the EMA (that used to disarm
    the spike guard forever) — it is counted and its update dropped."""
    losses = {0: float("nan"), 1: float("inf"), 4: 100.0}
    sup = Supervisor(step_fn=lambda s, i: (s + 1, losses.get(i, 1.0)),
                     save_fn=lambda step, s: None,
                     restore_fn=lambda: (None, None), save_every=1000)
    final, run = sup.train(0, 7)
    assert run.n_skipped_nonfinite == 2   # nan + inf before the EMA seeded
    assert run.n_skipped_spikes == 1      # 100.0 vs EMA ~1.0: still armed
    assert np.isfinite(run.loss_ema)
    assert final == 4                     # 7 steps, 3 dropped updates


def test_sigterm_handoff_checkpoints_and_resumes():
    saved = {}

    def save_fn(step, state):
        saved["step"], saved["state"] = step, state

    def restore_fn():
        return saved.get("state"), saved.get("step")

    def step_fn(state, idx):
        if idx == 4 and "state" not in saved:     # preempt the first run
            os.kill(os.getpid(), signal.SIGTERM)
        return state + 1, 1.0

    old = signal.getsignal(signal.SIGTERM)
    try:
        sup = Supervisor(step_fn=step_fn, save_fn=save_fn,
                         restore_fn=restore_fn, save_every=1000,
                         handle_sigterm=True)
        state, run = sup.train(0, 10)
        assert run.step == 5 and state == 5       # stopped at the boundary
        assert saved["step"] == 5                 # ...with a handoff save
        sup2 = Supervisor(step_fn=step_fn, save_fn=save_fn,
                          restore_fn=restore_fn, save_every=1000)
        final, run2 = sup2.train(0, 10)
    finally:
        signal.signal(signal.SIGTERM, old)
    assert run2.n_restarts == 1                   # resumed, not restarted
    assert final == 10 and run2.step == 10
