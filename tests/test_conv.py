"""Fused bit-serial convolution: integer-exactness vs the im2col oracle.

The specification: for every geometry, bitserial_conv (Pallas interpret)
and bitserial_conv_ref (one XLA integer conv) must equal im2col +
reference_int_matmul on the SAME quantized operands, bit for bit. Then
the model-level wiring: cnn.forward under conv_route="fused" must equal
conv_route="im2col" in every exec mode.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.api as loom
from repro.core import bitpack, engine, quantize as q
from repro.core.policy import uniform_policy
from repro.kernels import ref
from repro.kernels.bitserial_conv import bitserial_conv
from repro.models import cnn, layers as L

jax.config.update("jax_platform_name", "cpu")


def _im2col(x, kernel, stride):
    b, h, w, c = x.shape
    pad = kernel // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    cols = []
    for di in range(kernel):
        for dj in range(kernel):
            cols.append(xp[:, di:di + h:stride, dj:dj + w:stride, :])
    return jnp.concatenate(cols, axis=-1)


def _conv_case(kernel, stride, pa, pw, b=2, h=9, c=5, n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(q.qmin(pa), q.qmax(pa) + 1, size=(b, h, h, c)),
                    jnp.int8)
    kkc = kernel * kernel * c
    wq = jnp.asarray(rng.integers(q.qmin(pw), q.qmax(pw) + 1, size=(kkc, n)),
                     jnp.int32)
    wp = bitpack.pack_weights(wq, pw)
    patches = _im2col(x.astype(jnp.int32), kernel, stride)
    oracle = engine.reference_int_matmul(
        patches.reshape(-1, kkc), wq).reshape(b, -(-h // stride),
                                              -(-h // stride), n)
    return x, wq, wp, oracle


# The acceptance grid: kernels {1,3,5} x strides {1,2} x (Pa, Pw) in
# {(8,8), (4,4), (8,11)}; both the Pallas interpret kernel and the XLA
# fused conv must be integer-exact vs im2col + reference_int_matmul.
@pytest.mark.parametrize("kernel", [1, 3, 5])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("pa,pw", [(8, 8), (4, 4), (8, 11)])
def test_fused_conv_exact_both_paths(kernel, stride, pa, pw):
    x, wq, wp, oracle = _conv_case(kernel, stride, pa, pw,
                                   seed=kernel * 100 + stride * 10 + pw)
    y_pal = bitserial_conv(x, wp, kernel=kernel, stride=stride, w_bits=pw,
                           bn=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(oracle))
    y_xla = ref.bitserial_conv_ref(x, wp, kernel=kernel, stride=stride,
                                   w_bits=pw)
    np.testing.assert_array_equal(np.asarray(y_xla), np.asarray(oracle))


@pytest.mark.parametrize("h,c,n,bn", [(6, 8, 8, 8), (32, 3, 32, 16),
                                      (7, 16, 24, 8)])
def test_fused_conv_shapes_and_tiles(h, c, n, bn):
    """Odd maps, K%8 padding rows, and N-tiling all stay exact."""
    x, wq, wp, oracle = _conv_case(3, 2, 8, 8, b=3, h=h, c=c, n=n, seed=h + n)
    y = bitserial_conv(x, wp, kernel=3, stride=2, w_bits=8, bn=bn, interpret=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(oracle))


def test_fused_conv_batch_one_and_wide():
    x, wq, wp, oracle = _conv_case(5, 1, 8, 8, b=1, h=12, c=4, n=32, seed=9)
    y = bitserial_conv(x, wp, kernel=5, stride=1, w_bits=8, bn=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(oracle))


# ---------------------------------------------------------------------------
# Model-level wiring: fused == im2col in every exec mode
# ---------------------------------------------------------------------------

def _cnn_setup(mode):
    cfg = cnn.CNNConfig()
    params, specs = cnn.init_params(jax.random.PRNGKey(0), cfg)
    pol = uniform_policy(8, 8)
    if mode.startswith("serve"):
        params = {k: (L.convert_linear_for_serving(v, specs[k],
                                                   pol.lookup(k), mode)[0]
                      if L.is_linear(v) else v)
                  for k, v in params.items()}
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 32, 32, 3)),
                    jnp.float32)
    return cfg, params, pol, x


@pytest.mark.parametrize("mode", ["dense", "fake_quant", "serve_int8",
                                  "serve_packed"])
def test_cnn_fused_equals_im2col_every_mode(mode):
    cfg, params, pol, x = _cnn_setup(mode)
    yf = cnn.forward(params, cfg, x,
                     loom.build_plan(cfg, pol, mode, conv_route="fused"))
    yi = cnn.forward(params, cfg, x,
                     loom.build_plan(cfg, pol, mode, conv_route="im2col"))
    np.testing.assert_array_equal(np.asarray(yf), np.asarray(yi))


def test_cnn_serve_packed_pallas_equals_xla():
    cfg, params, pol, x = _cnn_setup("serve_packed")
    y_xla = cnn.forward(params, cfg, x,
                        loom.build_plan(cfg, pol, "serve_packed", "xla"))
    y_pal = cnn.forward(params, cfg, x,
                        loom.build_plan(cfg, pol, "serve_packed",
                                        "pallas_interpret"))
    np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(y_xla))


def test_conv_serve_clamps_wide_activation_profiles():
    """Table-1 profiles go to Pa=13-16; the int8 kernel ABI clamps to 8,
    and the Pallas and XLA serve paths must stay bit-identical there
    (an unclamped astype(int8) would wrap the Pallas path only)."""
    from repro.kernels import ops
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 4)) * 50, jnp.float32)
    wq = jnp.asarray(rng.integers(q.qmin(8), q.qmax(8) + 1, size=(3 * 3 * 4, 8)),
                     jnp.int32)
    wp = bitpack.pack_weights(wq, 8)
    ws = jnp.float32(0.01)
    y_xla = ops.loom_conv_serve(x, wp, ws, kernel=3, stride=1, a_bits=16)
    y_pal = ops.loom_conv_serve(x, wp, ws, kernel=3, stride=1, a_bits=16,
                                backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(y_xla))


def test_conv_weight_packing_pads_k():
    """conv1's K = 3*3*3 = 27 packs into ceil(27/8)=4 byte rows and
    round-trips exactly through the padded representation."""
    rng = np.random.default_rng(5)
    wq = jnp.asarray(rng.integers(q.qmin(8), q.qmax(8) + 1, size=(27, 16)),
                     jnp.int32)
    packed = bitpack.pack_weights(wq, 8)
    assert packed.shape == (8, 4, 16)
    np.testing.assert_array_equal(
        np.asarray(bitpack.unpack_weights(packed, 8, k=27)), np.asarray(wq))
