"""Execution-plan API: plans, backends, sessions, and dynamic serving.

Covers the acceptance bar of the api_redesign PR:
  * build_plan resolves per-layer plans once (kind, route, precision,
    conv geometry, dynamic-trim config);
  * as_plan rejects anything that is not an ExecutionPlan (the old
    string-mode shim is retired);
  * serve_packed + dynamic_a=True is bit-identical to the static path on
    both the xla and pallas_interpret backends across (Pa, Pw) in
    {(8,8), (4,4), (8,11)}, at the ops level and end-to-end through
    loom.compile();
  * dynamic_stats reports plane_fraction_executed < 1 on skewed
    activations (the runtime trimming actually saves planes);
  * group_effective_bits handles ragged trailing groups (CNN heads,
    odd-K linears);
  * the ServingSession path generates identically to the legacy
    launch/serve.py shim wiring for the same seed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as loom
from repro import configs
from repro.api import plan as planlib
from repro.core import bitpack, dynamic, quantize as q
from repro.core.policy import LayerPrecision, PrecisionPolicy, uniform_policy
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import cnn, layers as L

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Plans and backends
# ---------------------------------------------------------------------------

def test_build_plan_resolves_cnn_layers_once():
    cfg = cnn.CNNConfig()
    policy = PrecisionPolicy(default=LayerPrecision(8, 8),
                             per_layer={"conv2": LayerPrecision(6, 7)},
                             dynamic_a=True, group_size=64)
    plan = loom.build_plan(cfg, policy, mode="serve_packed",
                           backend="pallas_interpret")
    lp = plan.layer("conv2", kind="conv")
    assert (lp.kind, lp.route) == ("conv", planlib.PACKED)
    assert (lp.a_bits, lp.w_bits) == (6, 7)
    assert (lp.kernel, lp.stride) == (3, 1)
    assert lp.dynamic_a and lp.group_size == 64
    # resolved once: the same object comes back, no re-lookup
    assert plan.layer("conv2", kind="conv") is lp
    assert plan.layer("fc0").kind == "linear"
    assert plan.backend.name == "pallas_interpret"


def test_build_plan_lm_classes_and_modes():
    cfg = configs.get("qwen3-1.7b", smoke=True)
    for mode, route in [("dense", planlib.DENSE),
                        ("fake_quant", planlib.FAKE_QUANT),
                        ("serve_int8", planlib.INT8),
                        ("serve_packed", planlib.PACKED)]:
        plan = loom.build_plan(cfg, uniform_policy(8, 8), mode=mode)
        assert plan.layer("attn_q").route == route
        assert plan.layer("lm_head").route == route
    with pytest.raises(ValueError):
        loom.build_plan(cfg, uniform_policy(8, 8), mode="bogus").layer("x")


def test_backend_registry_round_trip():
    be = loom.get_backend("xla")
    assert loom.resolve_backend("xla") is be
    assert loom.resolve_backend(be) is be
    assert loom.resolve_backend(None, use_pallas=True, interpret=True).name \
        == "pallas_interpret"
    assert loom.resolve_backend(None, use_pallas=False).name == "xla"
    with pytest.raises(KeyError):
        loom.get_backend("no_such_backend")
    # registration admits out-of-tree backends and replacement
    class Mine(loom.Backend):
        name = "mine"
    loom.register_backend("mine", Mine())
    try:
        assert loom.get_backend("mine").name == "mine"
    finally:
        loom.backend._REGISTRY.pop("mine")


def test_as_plan_accepts_only_execution_plans():
    """The retired string-mode shim no longer exists; apply paths accept
    exactly one config type, and reject anything else loudly."""
    policy = uniform_policy(8, 8)
    plan = loom.build_plan(None, policy, "serve_packed")
    assert planlib.as_plan(plan) is plan
    with pytest.raises(TypeError):
        planlib.as_plan(object())
    assert not hasattr(L, "Exec" + "Config")     # the shim class is gone


def test_xla_dynamic_linear_group_mask_matches_oracle():
    """The production XLA matmul_planes_dynamic (per-column-group
    arithmetic mask) must match the truncating plane oracle for ARBITRARY
    counts — including insufficient ones that really truncate."""
    rng = np.random.default_rng(5)
    pa, m, k, n, bn = 8, 16, 64, 32, 8
    wq = jnp.asarray(rng.integers(q.qmin(pa), q.qmax(pa) + 1, size=(k, n)),
                     jnp.int32)
    wp = bitpack.pack_weights(wq, pa)
    x = jnp.asarray(rng.integers(-128, 128, size=(m, k)), jnp.int8)
    counts = jnp.asarray(rng.integers(1, pa - 2, size=(n // bn,)), jnp.int32)
    from repro.kernels import ref
    y_ref = ref.bitserial_matmul_dynamic_ref(x, wp, counts, pa, bn)
    y_xla = loom.get_backend("xla").matmul_planes_dynamic(
        x, wp, counts, w_bits=pa, bn=bn)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_xla))
    # the low counts really truncate: differs from the full-width matmul
    assert not np.array_equal(np.asarray(y_ref),
                              np.asarray(ref.bitserial_matmul_ref(x, wp, pa)))


# ---------------------------------------------------------------------------
# Ragged groups (satellite: CNN heads / odd-K linears can enable dynamic_a)
# ---------------------------------------------------------------------------

def test_group_effective_bits_ragged_tail():
    g = 256
    x = np.zeros((2, 300), dtype=np.int32)
    x[0, :256] = 64            # group 0 of row 0: 8 bits
    x[0, 280] = 3              # ragged tail group of row 0: 3 bits
    x[1, 10] = -1              # group 0 of row 1: 1 bit magnitude + sign
    eff = dynamic.group_effective_bits(jnp.asarray(x), g)
    assert eff.shape == (2, 2)
    assert int(eff[0, 0]) == 8 and int(eff[0, 1]) == 3
    assert int(eff[1, 0]) == 2
    assert int(eff[1, 1]) == 1          # all-padding/zero group: 1-bit floor
    # K < group_size: a single ragged group
    eff_small = dynamic.group_effective_bits(jnp.asarray(x[:, :10]), g)
    assert eff_small.shape == (2, 1)


def test_dynamic_stats_ragged_and_skewed():
    """plane_fraction_executed < 1 on skewed activations — the runtime
    trimming below the static profile that drives Loom's 4.38x headline."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 300)).astype(np.float32) * 0.01
    x[:, :4] = 30.0            # one hot group sets the per-tensor scale
    xq, _ = q.quantize(jnp.asarray(x), 8)
    stats = dynamic.dynamic_stats(xq, 8, 256)
    assert float(stats["plane_fraction_executed"]) < 1.0
    assert float(stats["mean_effective_bits"]) < 8.0


# ---------------------------------------------------------------------------
# Dynamic serving parity (ops level)
# ---------------------------------------------------------------------------

def _skewed(rng, m, k):
    """Activations whose row groups have very different magnitudes."""
    row_scale = np.where(rng.random(m) < 0.75, 0.02, 1.0)
    return jnp.asarray(rng.normal(size=(m, k)) * row_scale[:, None],
                       jnp.float32)


@pytest.mark.parametrize("pa,pw", [(8, 8), (4, 4), (8, 11)])
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_dynamic_linear_bit_identical_to_static(pa, pw, backend):
    rng = np.random.default_rng(pa * 31 + pw)
    for m, k, n in [(33, 100, 24), (64, 256, 32)]:  # ragged M, odd K
        x = _skewed(rng, m, k)
        wq, ws = q.quantize(jnp.asarray(rng.normal(size=(k, n)), jnp.float32),
                            pw)
        wp = bitpack.pack_weights(wq, pw)
        y_static = ops.loom_linear_serve(x, wp, ws, a_bits=pa, w_bits=pw,
                                         backend=backend)
        y_dyn = ops.loom_linear_serve_dynamic(x, wp, ws, a_bits=pa, w_bits=pw,
                                              group_size=64, backend=backend)
        np.testing.assert_array_equal(np.asarray(y_static), np.asarray(y_dyn))
        # the two backends also agree with each other (oracle == kernel)
        y_xla = ops.loom_linear_serve_dynamic(x, wp, ws, a_bits=pa, w_bits=pw,
                                              group_size=64, backend="xla")
        np.testing.assert_array_equal(np.asarray(y_dyn), np.asarray(y_xla))


def test_dynamic_linear_actually_trims_planes():
    """The counts fed to the kernel must drop below the static profile on
    skewed data (otherwise the 'dynamic' path is static with extra steps)."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(128, 64)).astype(np.float32)
    x[:64] *= 0.02             # first row group quiet, second loud
    xq, _ = q.quantize(jnp.asarray(x), 8)
    counts = dynamic.serve_group_counts(xq, 64, 8)
    assert counts.shape == (2,)
    assert int(counts[1]) == 8
    assert int(counts[0]) < 8          # the quiet group executes fewer planes
    assert int(counts.min()) >= 1


# ---------------------------------------------------------------------------
# Dynamic serving parity (end-to-end through loom.compile)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_compile_dynamic_end_to_end_lm(backend):
    """serve_packed + dynamic_a through loom.compile: logits bit-identical
    to the static plan on the same packed params."""
    cfg = configs.get("qwen3-1.7b", smoke=True)
    static_pol = uniform_policy(8, 8)
    dyn_pol = uniform_policy(8, 8, dynamic_a=True)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab, size=(2, 8)), jnp.int32)
    s_static = loom.compile(cfg, static_pol, mode="serve_packed",
                            backend=backend, rng=0)
    s_dyn = loom.compile(cfg, dyn_pol, mode="serve_packed", backend=backend,
                         rng=0)
    l_static, _ = s_static.prefill(toks)
    l_dyn, _ = s_dyn.prefill(toks)
    np.testing.assert_array_equal(np.asarray(l_static), np.asarray(l_dyn))
    gen_static = s_static.generate(toks, 4)
    gen_dyn = s_dyn.generate(toks, 4)
    np.testing.assert_array_equal(gen_static, gen_dyn)


def test_compile_dynamic_cnn_classify():
    """CNN session with dynamic_a: head FC layers have odd K (ragged
    groups) and must match the static plan exactly."""
    cfg = cnn.CNNConfig()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 32, 32, 3)),
                    jnp.float32)
    y_static = loom.compile(cfg, uniform_policy(8, 8), mode="serve_packed",
                            rng=0).classify(x)
    y_dyn = loom.compile(cfg, uniform_policy(8, 8, dynamic_a=True),
                         mode="serve_packed", rng=0).classify(x)
    np.testing.assert_array_equal(np.asarray(y_static), np.asarray(y_dyn))


def test_session_dynamic_stats_report():
    cfg = configs.get("qwen3-1.7b", smoke=True)
    sess = loom.compile(cfg, uniform_policy(8, 8, dynamic_a=True),
                        mode="serve_packed", rng=0)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 512)).astype(np.float32) * 0.01
    x[:, 0] = 20.0
    stats = sess.dynamic_stats(jnp.asarray(x), "ffn_up")
    assert float(stats["plane_fraction_executed"]) < 1.0


# ---------------------------------------------------------------------------
# ServingSession vs legacy serve wiring
# ---------------------------------------------------------------------------

def test_session_matches_hand_wired_serve_generations():
    """Identical generations for the same seed: loom.compile vs the
    hand-wired build_plan + make_serve_fns launch-layer cell."""
    import argparse
    from repro.launch import serve as serve_mod

    cfg = configs.get("qwen3-1.7b", smoke=True)
    policy = uniform_policy(8, 8)
    args = argparse.Namespace(mode="serve_packed", backend="xla", batch=2,
                              prompt_len=8, gen_len=4, a_bits=8, w_bits=8)
    gen_plan = serve_mod._generate_plan(cfg, args, policy)
    gen_session = serve_mod._generate_session(cfg, args, policy)
    np.testing.assert_array_equal(gen_plan, gen_session)


def test_serve_cli_session_dynamic(capsys):
    """The demo driver end-to-end on the session API with dynamic trimming."""
    from repro.launch import serve as serve_mod
    serve_mod.main(["--arch", "qwen3-1.7b", "--mode", "serve_packed",
                    "--api", "session", "--dynamic-a", "--batch", "2",
                    "--prompt-len", "8", "--gen-len", "3"])
    out = capsys.readouterr().out
    assert "generated" in out and "done" in out


def test_compile_with_mesh_shardings():
    """The mesh wiring of loom.compile (and, via delegation, the launch
    layer's jit_serve_steps) must serve identically to the plain path."""
    cfg = configs.get("qwen3-1.7b", smoke=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    policy = uniform_policy(8, 8)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab, size=(2, 8)), jnp.int32)
    gen_mesh = loom.compile(cfg, policy, mode="serve_packed", rng=0,
                            mesh=mesh).generate(toks, 3)
    gen_plain = loom.compile(cfg, policy, mode="serve_packed",
                             rng=0).generate(toks, 3)
    np.testing.assert_array_equal(gen_mesh, gen_plain)


def test_layer_plan_conv_geometry_memo():
    """A geometry-less early resolution must not bake kernel=None into the
    plan; conflicting geometry for the same layer name is an error."""
    plan = loom.build_plan(None, uniform_policy(8, 8), "serve_packed")
    lp0 = plan.layer("conv1", kind="conv")          # introspection, no geometry
    assert lp0.kernel is None
    lp = plan.layer("conv1", kind="conv", kernel=3, stride=1)
    assert (lp.kernel, lp.stride) == (3, 1)
    assert plan.layer("conv1", kind="conv").kernel == 3
    with pytest.raises(ValueError):
        plan.layer("conv1", kind="conv", kernel=5, stride=1)


# ---------------------------------------------------------------------------
# Acceptance: no string-mode dispatch left in models/kernels
# ---------------------------------------------------------------------------

def test_no_string_mode_dispatch_in_apply_paths():
    import os
    import re
    root = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    pat = re.compile(r'mode == "serve')
    offenders = []
    for sub in ("models", "kernels"):
        for dirpath, _, files in os.walk(os.path.join(root, sub)):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(dirpath, f)
                    with open(path) as fh:
                        if pat.search(fh.read()):
                            offenders.append(path)
    assert not offenders, offenders
