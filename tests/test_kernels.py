"""Per-kernel sweeps: pallas_call(interpret=True) vs the ref.py oracle."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitpack, quantize as q
from repro.kernels import ref
from repro.kernels.bitserial_conv import (bitserial_conv,
                                          bitserial_conv_dynamic,
                                          bitserial_conv_wgroup)
from repro.kernels.bitserial_matmul import bitserial_matmul, bitserial_matmul_dynamic
from repro.kernels.dynamic_quant import dynamic_quant
from repro.kernels.flash_attention import flash_attention

jax.config.update("jax_platform_name", "cpu")


@pytest.mark.parametrize("kernel", [
    bitserial_matmul, bitserial_matmul_dynamic, bitserial_conv,
    bitserial_conv_wgroup, bitserial_conv_dynamic, dynamic_quant,
    flash_attention], ids=lambda f: f.__name__)
def test_kernel_interpret_has_no_default(kernel):
    """Callers choose the interpreter or Mosaic: a default would let a
    chip run fall back to the interpreter unnoticed."""
    p = inspect.signature(kernel).parameters["interpret"]
    assert p.default is inspect.Parameter.empty


def make_packed(k, n, w_bits, seed):
    w = jnp.asarray(np.random.default_rng(seed).normal(size=(k, n)).astype(np.float32))
    wq, ws = q.quantize(w, w_bits)
    return bitpack.pack_weights(wq, w_bits), wq, ws


# ---------------------------------------------------------------------------
# bitserial_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(8, 32, 16), (16, 64, 32), (32, 128, 8),
                                   (128, 256, 128)])
@pytest.mark.parametrize("w_bits", [1, 4, 7, 8, 11, 16])
def test_bitserial_matmul_shape_sweep(m, k, n, w_bits):
    if (m, k, n) == (128, 256, 128) and w_bits not in (8, 11):
        pytest.skip("big shape: 2 precisions suffice")
    rng = np.random.default_rng(w_bits)
    x = jnp.asarray(rng.integers(-128, 128, size=(m, k)), dtype=jnp.int8)
    wp, wq, _ = make_packed(k, n, w_bits, w_bits + 1)
    y = bitserial_matmul(x, wp, w_bits=w_bits, bm=min(8, m), bn=min(8, n),
                         bk=min(32, k), interpret=True)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.bitserial_matmul_ref(x, wp, w_bits)))


@pytest.mark.parametrize("bm,bn,bk", [(8, 8, 8), (16, 32, 64), (8, 16, 32)])
def test_bitserial_matmul_block_sweep(bm, bn, bk):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-128, 128, size=(16, 64)), dtype=jnp.int8)
    wp, _, _ = make_packed(64, 32, 9, 7)
    y = bitserial_matmul(x, wp, w_bits=9, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.bitserial_matmul_ref(x, wp, 9)))


@given(st.integers(1, 12), st.sampled_from([(8, 16, 8), (8, 32, 16)]))
@settings(max_examples=12, deadline=None)
def test_bitserial_matmul_property(w_bits, mkn):
    m, k, n = mkn
    rng = np.random.default_rng(w_bits * 7)
    x = jnp.asarray(rng.integers(-128, 128, size=(m, k)), dtype=jnp.int8)
    wq = jnp.asarray(rng.integers(q.qmin(w_bits), q.qmax(w_bits) + 1, size=(k, n)),
                     dtype=jnp.int32)
    wp = bitpack.pack_weights(wq, w_bits)
    y = bitserial_matmul(x, wp, w_bits=w_bits, bm=m, bn=n, bk=k, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y),
        np.asarray(jnp.matmul(x.astype(jnp.int32), wq)))


def test_bitserial_matmul_dynamic_skips_planes():
    """Per-N-tile plane counts: values quantized to tile precision give the
    same result as the full-precision matmul, with fewer planes executed."""
    rng = np.random.default_rng(3)
    m, k, n, pw, bn = 8, 64, 32, 11, 8
    x = jnp.asarray(rng.integers(-128, 128, size=(m, k)), dtype=jnp.int8)
    counts = jnp.asarray([3, 6, 9, 11], dtype=jnp.int32)
    cols = []
    for c in np.asarray(counts):
        cols.append(rng.integers(-(1 << (int(c) - 1)), (1 << (int(c) - 1)), size=(k, bn)))
    wq = jnp.asarray(np.concatenate(cols, axis=1), dtype=jnp.int32)
    wp = bitpack.pack_weights(wq, pw)
    y = bitserial_matmul_dynamic(x, wp, counts, w_bits=pw, bm=m, bn=bn, bk=32,
                                 interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(ref.bitserial_matmul_dynamic_ref(x, wp, counts, pw, bn)))
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(jnp.matmul(x.astype(jnp.int32), wq)))


@pytest.mark.parametrize("counts,pw", [
    ((0, 8, 3, 5), 8),        # a zero-plane tile and a full-width tile
    ((11, 0, 11, 1), 11),     # full-width entries at Pw=11, zeros between
    ((2, 4, 6, 8), 8),
])
def test_bitserial_matmul_dynamic_vs_ref(counts, pw):
    """Direct kernel-vs-oracle coverage for the dynamic-precision kernel
    (only the static kernel was exercised before). plane_counts == 0 must
    produce an all-zero N-tile; full-width counts must reproduce the
    static kernel's result for that tile."""
    rng = np.random.default_rng(sum(counts) + pw)
    m, k, bn = 8, 64, 8
    n = bn * len(counts)
    x = jnp.asarray(rng.integers(-128, 128, size=(m, k)), dtype=jnp.int8)
    cols = []
    for c in counts:
        if c == 0:
            cols.append(np.zeros((k, bn), dtype=np.int64))
        else:
            cols.append(rng.integers(-(1 << (c - 1)), 1 << (c - 1),
                                     size=(k, bn)))
    wq = jnp.asarray(np.concatenate(cols, axis=1), dtype=jnp.int32)
    wp = bitpack.pack_weights(wq, pw)
    counts_arr = jnp.asarray(counts, dtype=jnp.int32)
    y = bitserial_matmul_dynamic(x, wp, counts_arr, w_bits=pw, bm=m, bn=bn,
                                 bk=32, interpret=True)
    expect = ref.bitserial_matmul_dynamic_ref(x, wp, counts_arr, pw, bn)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(expect))
    # zero-count tiles are exactly zero; the whole thing matches the
    # plain integer matmul (values fit their per-tile widths).
    for j, c in enumerate(counts):
        if c == 0:
            assert not np.asarray(y[:, j * bn:(j + 1) * bn]).any()
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(jnp.matmul(x.astype(jnp.int32), wq)))


def test_bitserial_matmul_dynamic_ref_zero_and_full():
    """The oracle itself: counts=0 tiles contribute nothing even when the
    packed planes hold garbage above the effective width."""
    rng = np.random.default_rng(0)
    m, k, bn, pw = 4, 32, 8, 8
    x = jnp.asarray(rng.integers(-128, 128, size=(m, k)), dtype=jnp.int8)
    wq = jnp.asarray(rng.integers(-128, 128, size=(k, 2 * bn)), jnp.int32)
    wp = bitpack.pack_weights(wq, pw)
    counts = jnp.asarray([0, pw], dtype=jnp.int32)
    y = ref.bitserial_matmul_dynamic_ref(x, wp, counts, pw, bn)
    assert not np.asarray(y[:, :bn]).any()
    np.testing.assert_array_equal(
        np.asarray(y[:, bn:]),
        np.asarray(jnp.matmul(x.astype(jnp.int32), wq[:, bn:])))


def test_pack_roundtrip_pw16():
    """Pw=16 round-trip: the MSB plane weight is -2^15; the unpack must
    stay in int32 (an int64 intermediate silently truncates under jax's
    default x64-disabled config)."""
    rng = np.random.default_rng(16)
    wq = jnp.asarray(rng.integers(q.qmin(16), q.qmax(16) + 1, size=(64, 32)),
                     jnp.int32)
    packed = bitpack.pack_weights(wq, 16)
    back = bitpack.unpack_weights(packed, 16)
    assert back.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(back), np.asarray(wq))
    # extremes: qmin has only the MSB plane set, qmax all lower planes
    ex = jnp.asarray([[q.qmin(16)], [q.qmax(16)], [0], [-1]], jnp.int32)
    ex = jnp.tile(ex, (2, 8))  # K=8 rows, N=8
    back2 = bitpack.unpack_weights(bitpack.pack_weights(ex, 16), 16)
    np.testing.assert_array_equal(np.asarray(back2), np.asarray(ex))


# ---------------------------------------------------------------------------
# dynamic_quant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,g", [(4, 512, 256), (8, 256, 128), (16, 1024, 256)])
@pytest.mark.parametrize("bits", [4, 8])
def test_dynamic_quant_sweep(m, k, g, bits):
    x = jnp.asarray(np.random.default_rng(m * k).normal(size=(m, k)).astype(np.float32))
    xq, scale, eff = dynamic_quant(x, group_size=g, bits=bits, bm=min(4, m),
                                   interpret=True)
    rq, rs, re = ref.dynamic_quant_ref(x, g, bits)
    np.testing.assert_array_equal(np.asarray(xq), np.asarray(rq))
    np.testing.assert_allclose(np.asarray(scale), np.asarray(rs), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(eff), np.asarray(re))


def test_dynamic_quant_eff_bits_detects_small_groups():
    x = np.ones((1, 512), dtype=np.float32)
    x[0, 256:] = 100.0  # group 1 large, group 0 small relative to its own max
    xq, scale, eff = dynamic_quant(jnp.asarray(x), group_size=256, bits=8, bm=1,
                                   interpret=True)
    # per-group scaling -> both groups hit full 8-bit range
    assert int(eff[0, 0]) == 8 and int(eff[0, 1]) == 8
    np.testing.assert_allclose(float(scale[0, 1]) / float(scale[0, 0]), 100.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,d,bq,bk", [(64, 16, 16, 16), (128, 32, 32, 64),
                                       (256, 64, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(s, d, bq, bk, causal):
    rng = np.random.default_rng(s + d)
    shape = (2, 2, s, d)
    q_ = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    k_ = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    v_ = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    out = flash_attention(q_, k_, v_, causal=causal, bq=bq, bk=bk, interpret=True)
    expect = ref.flash_attention_ref(q_, k_, v_, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(window)
    shape = (1, 2, 128, 16)
    q_ = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    k_ = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    v_ = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    out = flash_attention(q_, k_, v_, causal=True, window=window, bq=32, bk=32,
                          interpret=True)
    expect = ref.flash_attention_ref(q_, k_, v_, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(1)
    shape = (1, 1, 64, 32)
    q_ = jnp.asarray(rng.normal(size=shape), dtype=jnp.bfloat16)
    k_ = jnp.asarray(rng.normal(size=shape), dtype=jnp.bfloat16)
    v_ = jnp.asarray(rng.normal(size=shape), dtype=jnp.bfloat16)
    out = flash_attention(q_, k_, v_, bq=32, bk=32, interpret=True)
    expect = ref.flash_attention_ref(q_, k_, v_)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(expect, dtype=np.float32),
                               rtol=0.05, atol=0.05)
