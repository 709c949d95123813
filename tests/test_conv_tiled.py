"""Output-row-tiled bit-serial convolution: banding is output-invariant.

The specification: for EVERY band size, the banded static kernel, the
banded oracle (``ref.bitserial_conv_banded_ref``) and the untiled kernel
(one band) must be bit-identical to the XLA conv — ragged last bands,
stride-2 overlapping input bands, and all-zero bands included. The
dynamic kernel's bands are its window groups; its band-local prologue
must match both truncating oracles (full-image and band-local) for
ARBITRARY counts, including groups that start mid-row (band boundary
crossing a window group). The plan layer resolves ``conv_tile`` from the
backend's VMEM budget, so a map whose untiled footprint exceeds the
budget transparently runs banded — and still bit-identically.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.api as loom
from repro.api.backend import PallasBackend
from repro.api.plan import conv_rows_per_band
from repro.core import bitpack, dynamic, quantize as q
from repro.core.policy import uniform_policy
from repro.kernels import ops, ref
from repro.kernels.bitserial_conv import (band_geometry, bitserial_conv,
                                          bitserial_conv_dynamic,
                                          conv_vmem_bytes, dyn_band_geometry)
from repro.models import cnn, layers as L

jax.config.update("jax_platform_name", "cpu")


def _conv_case(rng, kernel, stride, pa, pw, b, h, c, n):
    x = jnp.asarray(rng.integers(q.qmin(pa), q.qmax(pa) + 1,
                                 size=(b, h, h, c)), jnp.int8)
    kkc = kernel * kernel * c
    wq = jnp.asarray(rng.integers(q.qmin(pw), q.qmax(pw) + 1, size=(kkc, n)),
                     jnp.int32)
    return x, bitpack.pack_weights(wq, pw)


# ---------------------------------------------------------------------------
# Static banded kernel: every band size == untiled == XLA, bit for bit
# ---------------------------------------------------------------------------

# The acceptance grid, with a band size (4) that leaves a ragged last band
# for every kernel/stride combination (ho in {9, 5, 3, 2}).
@pytest.mark.parametrize("kernel", [1, 3, 5])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("pa,pw", [(8, 8), (4, 4), (8, 11)])
def test_banded_static_exact_grid(kernel, stride, pa, pw):
    rng = np.random.default_rng(kernel * 100 + stride * 10 + pw)
    x, wp = _conv_case(rng, kernel, stride, pa, pw, b=2, h=9, c=5, n=16)
    oracle = ref.bitserial_conv_ref(x, wp, kernel=kernel, stride=stride,
                                    w_bits=pw)
    y_untiled = bitserial_conv(x, wp, kernel=kernel, stride=stride,
                               w_bits=pw, bn=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_untiled), np.asarray(oracle))
    for rpb in (1, 4):
        y_band = bitserial_conv(x, wp, kernel=kernel, stride=stride,
                                w_bits=pw, bn=8, rows_per_band=rpb, interpret=True)
        np.testing.assert_array_equal(np.asarray(y_band), np.asarray(oracle))
        y_ref = ref.bitserial_conv_banded_ref(x, wp, kernel=kernel,
                                              stride=stride, w_bits=pw,
                                              rows_per_band=rpb)
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(oracle))


def test_banded_static_stride2_overlapping_bands():
    """k=5 stride=2: adjacent bands' input windows overlap by 3 rows (the
    halo) — band boundaries must not drop or double-count rows."""
    rng = np.random.default_rng(7)
    x, wp = _conv_case(rng, 5, 2, 8, 8, b=3, h=11, c=3, n=8)
    oracle = ref.bitserial_conv_ref(x, wp, kernel=5, stride=2, w_bits=8)
    for rpb in (2, 3, 5):
        y = bitserial_conv(x, wp, kernel=5, stride=2, w_bits=8, bn=8,
                           rows_per_band=rpb, interpret=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(oracle))


def test_banded_static_all_zero_band():
    """A band of all-zero input rows contributes exactly zero (its patch
    rows are zeros) and neighbouring bands are unaffected."""
    rng = np.random.default_rng(9)
    pa = pw = 8
    xr = rng.integers(q.qmin(pa), q.qmax(pa) + 1, size=(2, 12, 12, 4))
    xr[:, 4:8] = 0                       # rows 4..7 = one whole band of 4
    x = jnp.asarray(xr, jnp.int8)
    wq = jnp.asarray(rng.integers(q.qmin(pw), q.qmax(pw) + 1,
                                  size=(3 * 3 * 4, 8)), jnp.int32)
    wp = bitpack.pack_weights(wq, pw)
    oracle = ref.bitserial_conv_ref(x, wp, kernel=3, stride=1, w_bits=pw)
    y = bitserial_conv(x, wp, kernel=3, stride=1, w_bits=pw, bn=8,
                       rows_per_band=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(oracle))


def test_band_geometry_and_vmem_accounting():
    """The geometry/accounting laws the plan heuristic and the benchmark
    rely on: band input rows include the halo, clamping, and the VMEM
    model shrinks monotonically with the band."""
    assert band_geometry(16, 16, None, 3, 1) == (16, 1, 18)
    assert band_geometry(16, 16, 4, 3, 1) == (4, 4, 6)
    assert band_geometry(9, 9, 4, 5, 2) == (4, 3, 11)    # ragged: 4+4+1
    assert band_geometry(9, 9, 64, 3, 1) == (9, 1, 11)   # clamped to Ho
    v_full = conv_vmem_bytes(64, 64, 32, 64, kernel=3, stride=1, w_bits=8)
    v_half = conv_vmem_bytes(64, 64, 32, 64, kernel=3, stride=1, w_bits=8,
                             rows_per_band=32)
    v_one = conv_vmem_bytes(64, 64, 32, 64, kernel=3, stride=1, w_bits=8,
                            rows_per_band=1)
    assert v_full > v_half > v_one


def test_conv_rows_per_band_heuristic():
    """Budget None or ample -> one band; tight budgets halve the band
    until the footprint fits; the floor is one row."""
    assert conv_rows_per_band(32, 32, 8, 32, kernel=3, stride=1, w_bits=8,
                              budget=None) == 32
    big = conv_vmem_bytes(32, 32, 8, 32, kernel=3, stride=1, w_bits=8)
    assert conv_rows_per_band(32, 32, 8, 32, kernel=3, stride=1, w_bits=8,
                              budget=big) == 32
    rpb = conv_rows_per_band(32, 32, 8, 32, kernel=3, stride=1, w_bits=8,
                             budget=big // 4)
    assert 1 <= rpb < 32
    assert conv_vmem_bytes(32, 32, 8, 32, kernel=3, stride=1, w_bits=8,
                           rows_per_band=rpb) <= big // 4
    assert conv_rows_per_band(32, 32, 8, 32, kernel=3, stride=1, w_bits=8,
                              budget=1) == 1


# ---------------------------------------------------------------------------
# Dynamic kernel: band-local prologue == both truncating oracles
# ---------------------------------------------------------------------------

def test_dynamic_band_crossing_window_group():
    """gsz % Wo != 0: window groups start mid-row, so their input bands
    cross output-row boundaries — forced-low (really truncating) counts
    must still match the full-image oracle AND the band-local oracle."""
    rng = np.random.default_rng(11)
    b, h, c, n, pa, pw, gsz = 2, 10, 4, 8, 8, 8, 16   # wo=10, 100 windows
    xq = jnp.asarray(rng.integers(q.qmin(pa), q.qmax(pa) + 1,
                                  size=(b, h, h, c)), jnp.int32)
    wq = jnp.asarray(rng.integers(q.qmin(pw), q.qmax(pw) + 1,
                                  size=(3 * 3 * c, n)), jnp.int32)
    wp = bitpack.pack_weights(wq, pw)
    ng = -(-(h * h) // gsz)
    counts = jnp.asarray(rng.integers(1, 6, size=(b, ng)), jnp.int32)
    y_full = ref.bitserial_conv_dynamic_ref(xq, wp, counts, kernel=3,
                                            stride=1, w_bits=pw,
                                            group_size=gsz)
    y_band = ref.bitserial_conv_dynamic_banded_ref(xq, wp, counts, kernel=3,
                                                   stride=1, w_bits=pw,
                                                   group_size=gsz)
    np.testing.assert_array_equal(np.asarray(y_full), np.asarray(y_band))
    wdense = bitpack.unpack_weights(wp, pw).astype(jnp.int8)
    y_k = bitserial_conv_dynamic(xq.astype(jnp.int8), wdense, counts,
                                 kernel=3, stride=1, a_bits=pa,
                                 group_size=gsz, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_full))


@pytest.mark.parametrize("kernel,stride", [(3, 1), (5, 2)])
def test_dynamic_banded_oracle_matches_full_oracle(kernel, stride):
    rng = np.random.default_rng(13)
    b, h, c, n, pa, pw, gsz = 2, 9, 3, 8, 8, 11, 8
    xq = jnp.asarray(rng.integers(q.qmin(pa), q.qmax(pa) + 1,
                                  size=(b, h, h, c)), jnp.int32)
    wq = jnp.asarray(rng.integers(q.qmin(pw), q.qmax(pw) + 1,
                                  size=(kernel * kernel * c, n)), jnp.int32)
    wp = bitpack.pack_weights(wq, pw)
    nwin = (-(-h // stride)) ** 2
    ng = -(-nwin // gsz)
    counts = jnp.asarray(rng.integers(1, 6, size=(b, ng)), jnp.int32)
    y_full = ref.bitserial_conv_dynamic_ref(xq, wp, counts, kernel=kernel,
                                            stride=stride, w_bits=pw,
                                            group_size=gsz)
    y_band = ref.bitserial_conv_dynamic_banded_ref(
        xq, wp, counts, kernel=kernel, stride=stride, w_bits=pw,
        group_size=gsz)
    np.testing.assert_array_equal(np.asarray(y_full), np.asarray(y_band))


def test_dynamic_all_zero_band_one_bit_floor():
    """A window group whose band is all zeros reports the 1-bit floor and
    executes one plane of zeros — still bit-identical to static on both
    backends."""
    rng = np.random.default_rng(15)
    xr = rng.normal(size=(2, 8, 8, 4)).astype(np.float32)
    xr[:, 4:] = 0.0                      # bottom half: zero window groups
    x = jnp.asarray(xr)
    wqf, ws = q.quantize(jnp.asarray(rng.normal(size=(3 * 3 * 4, 8)),
                                     jnp.float32), 8)
    wp = bitpack.pack_weights(wqf, 8)
    xq, _ = q.quantize(x, 8)
    counts = dynamic.conv_window_group_counts(xq, 3, 1, 16, 8)
    assert int(counts.min()) == 1        # the zero groups floor at 1 bit
    y_static = ops.loom_conv_serve(x, wp, ws, kernel=3, stride=1, a_bits=8)
    for backend in ("xla", "pallas_interpret"):
        y_dyn = ops.loom_conv_serve_dynamic(x, wp, ws, kernel=3, stride=1,
                                            a_bits=8, group_size=16,
                                            backend=backend)
        np.testing.assert_array_equal(np.asarray(y_static), np.asarray(y_dyn))


def test_dyn_band_geometry_bounds_group_work():
    """The dynamic band covers every window of a group and no more than
    Wo-1 alignment rows — per-group work is O(gsz + Wo), not O(Ho*Wo)."""
    for wo, gsz in [(10, 16), (32, 256), (9, 8), (5, 88)]:
        rows_pg, band_rows = dyn_band_geometry(wo, gsz, 3, 1)
        assert rows_pg * wo >= gsz + wo - 1      # any mid-row start fits
        assert rows_pg * wo < gsz + 2 * wo       # ...with bounded slack
        assert band_rows == rows_pg - 1 + 3


# ---------------------------------------------------------------------------
# VMEM budget: maps infeasible untiled run banded, transparently via plan
# ---------------------------------------------------------------------------

def test_budget_forces_banding_on_128px_map():
    """A 128x128 map whose untiled footprint exceeds the backend's VMEM
    budget: the plan resolves a smaller conv_tile, the banded kernel runs
    within budget, and the result equals the XLA route bit for bit."""
    budget = 2 * 2 ** 20
    be = PallasBackend("pallas_tiny_vmem", True, vmem_budget=budget)
    rng = np.random.default_rng(17)
    h, c, n, kernel = 128, 8, 32, 3
    assert conv_vmem_bytes(h, h, c, n, kernel=kernel, stride=1,
                           w_bits=8) > budget      # untiled does NOT fit
    x = jnp.asarray(rng.normal(size=(1, h, h, c)), jnp.float32)
    p, spec = L.linear_init(jax.random.PRNGKey(0), kernel * kernel * c, n,
                            dtype=jnp.float32)
    pol = uniform_policy(8, 8)
    packed, _ = L.convert_linear_for_serving(p, spec, pol.lookup("conv1"),
                                             "serve_packed")
    plan = loom.build_plan(None, pol, "serve_packed", be)
    y_band = L.conv_apply(packed, x, kernel, 1, plan, "conv1")
    lp = plan.layer("conv1", kind="conv")
    assert lp.conv_tile is not None and lp.conv_tile < h
    assert conv_vmem_bytes(h, h, c, n, kernel=kernel, stride=1, w_bits=8,
                           rows_per_band=lp.conv_tile) <= budget
    y_xla = L.conv_apply(packed, x, kernel, 1,
                         loom.build_plan(None, pol, "serve_packed", "xla"),
                         "conv1")
    np.testing.assert_array_equal(np.asarray(y_band), np.asarray(y_xla))


def test_plan_resolves_conv_tile_once_per_geometry():
    """conv_tile is memoized into the stored LayerPlan keyed to the
    activation geometry: same shapes read it back, a different geometry
    re-runs the budget check (a tile sized for a small map must not be
    reused on a big one, where it could bust the VMEM budget)."""
    pol = uniform_policy(8, 8)
    plan = loom.build_plan(None, pol, "serve_packed", "pallas_interpret")
    lp = plan.layer("convX", kind="conv", kernel=3, stride=1)
    t1 = plan.conv_tile(lp, 16, 16, 4, 8, 8)
    lp2 = plan.layer("convX", kind="conv")
    assert lp2.conv_tile == t1
    assert plan.conv_tile(lp2, 16, 16, 4, 8, 8) == t1         # memoized
    budget = plan.backend.vmem_budget
    t2 = plan.conv_tile(plan.layer("convX", kind="conv"),
                        256, 256, 64, 128, 8)
    assert conv_vmem_bytes(256, 256, 64, 128, kernel=3, stride=1, w_bits=8,
                           rows_per_band=t2) <= budget
    assert t2 < 256                              # the big map really bands


@pytest.mark.parametrize("backend", ["pallas_interpret"])
def test_cnn_forward_banded_equals_xla_end_to_end(backend):
    """Model-level: the full CNN under a tiny VMEM budget (every conv
    banded) equals the un-banded XLA plan bit for bit."""
    cfg = cnn.CNNConfig()
    params, specs = cnn.init_params(jax.random.PRNGKey(0), cfg)
    pol = uniform_policy(8, 8)
    params = {k: (L.convert_linear_for_serving(v, specs[k], pol.lookup(k),
                                               "serve_packed")[0]
                  if L.is_linear(v) else v)
              for k, v in params.items()}
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 32, 32, 3)),
                    jnp.float32)
    y_xla = cnn.forward(params, cfg, x,
                        loom.build_plan(cfg, pol, "serve_packed", "xla"))
    tiny = PallasBackend("pallas_tiny_vmem2", True, vmem_budget=100_000)
    plan = loom.build_plan(cfg, pol, "serve_packed", tiny)
    y_band = cnn.forward(params, cfg, x, plan)
    # the budget really forced banding on at least one conv
    tiles = [plan.layer(c.name, kind="conv").conv_tile for c in cfg.convs]
    assert any(t is not None and t < 32 for t in tiles), tiles
    np.testing.assert_array_equal(np.asarray(y_xla), np.asarray(y_band))
