"""Common layers: RMSNorm, RoPE, embeddings, and the Loom linear/conv.

Every matmul and convolution in every architecture flows through
``linear_apply`` / ``conv_apply``. Dispatch is NOT a string-mode if/elif
chain anymore: each call asks the model's ``ExecutionPlan``
(repro.api.plan) for the layer's resolved ``LayerPlan`` — kind, route,
(Pa, Pw), dynamic-trim group config, conv geometry, backend — and jumps
straight to that route's handler. Plans are resolved once per layer at
compile/conversion time; the per-call policy string matching and the
``use_pallas``/``interpret`` boolean threading of the seed repo are gone.

Routes (see repro.api.plan):

    dense        bf16 matmul              (DPNN-equivalent TPU baseline)
    fake_quant   QAT: STE fake-quant of activations (Pa) and weights (Pw),
                 then a dense matmul — the training-time integration of the
                 per-layer precision profiles.
    int8         LM_8b: dynamic activation quant + int8 weights stored in
                 the param tree, one int8 MXU pass. Weight bytes = 8/16.
    packed       paper-faithful bit-serial path: weights stored bit-packed
                 [Pw, K/8, N] in the param tree; bytes = Pw/16 of bf16;
                 Pw plane passes on the plan's backend. With
                 ``policy.dynamic_a`` BOTH routes trim ACTIVATION planes
                 at runtime (Lascorz OR-tree; bit-identical to static):
                 linears per group of concurrently-processed rows, convs
                 per group of output windows.

Serving routes require ``convert_params_for_serving`` to be run once over
the trained param tree (it replaces each linear's "w" with the quantized /
packed representation — the paper's offline weight packing step).

The seed-era string-mode + boolean-kernel-flags shim is GONE: every
apply call takes an ``ExecutionPlan`` from ``repro.api.build_plan`` (or
``loom.compile`` for serving).

Params are plain nested dicts; a parallel dict of PartitionSpec with
LOGICAL axis names ("fsdp"/"tp"/None, resolved by repro.dist.sharding)
is built by the same constructors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.api import plan as planlib
from repro.core import bitpack, quantize as q
from repro.kernels import ops


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding. x: [..., S, H, D]; positions: [..., S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs        # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]                              # [..., S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":  # squared ReLU (nemotron)
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# ---------------------------------------------------------------------------
# Param construction. Each init returns (params_dict, specs_dict) with
# logical-axis PartitionSpecs.
# ---------------------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, in_axis=None, out_axis=None,
                dtype=jnp.bfloat16):
    scale = d_in ** -0.5
    w = jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale
    return {"w": w.astype(dtype)}, {"w": PS(in_axis, out_axis)}


# ---------------------------------------------------------------------------
# Route handlers: one function per LayerPlan route, dispatch by dict.
# ---------------------------------------------------------------------------

def _linear_dense(p, x, lp, be):
    return x @ p["w"].astype(x.dtype)


def _linear_fake_quant(p, x, lp, be):
    xq = q.fake_quant(x, lp.a_bits)
    wq = q.fake_quant(p["w"].astype(jnp.float32), lp.w_bits).astype(x.dtype)
    return xq @ wq


def _token_quant_axis(x) -> int | None:
    """Activation-quant axis for the serving linears. Token-shaped inputs
    ([B, D] / [B, S, D]) get per-ROW scales, so a row's quantization grid
    never depends on what it is co-batched with (the batching engine's
    byte-identity bar; for batch-1 the row scale IS the tensor scale).
    Conv-as-im2col patch tensors ([B, Ho, Wo, k*k*C]) keep the single
    per-tensor scale the fused conv lowering uses — the two conv routes
    stay bit-identical."""
    return -1 if x.ndim <= 3 else None


def _linear_int8(p, x, lp, be):
    # LM_8b: one int8 MXU pass against pre-quantized weights. Token-shaped
    # inputs quantize per ROW — no cross-row grid leakage under batching;
    # conv-as-im2col patch tensors keep the fused conv's per-tensor grid.
    xq, x_scale = q.quantize(x.astype(jnp.float32), min(lp.a_bits, 8),
                             axis=_token_quant_axis(x))
    y = jax.lax.dot_general(
        xq.astype(jnp.int8), p["wq"],
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (y.astype(jnp.float32) * (x_scale * p["w_scale"])).astype(x.dtype)


def _linear_packed(p, x, lp, be):
    # Paper-faithful bit-serial path over pre-packed planes. The weight
    # precision is intrinsic to the packed tensor (its plane dim) — the
    # plan only sets the activation precision. ``dynamic_a`` routes
    # through the runtime activation-plane-trimming kernel.
    # ``lp.w_group_counts`` (pack-time per-filter-group weight plane
    # counts, recorded ONCE by ExecutionPlan.record_weight_groups) makes
    # both routes execute only each filter group's effective weight
    # planes — bit-identical to the untrimmed path.
    if lp.dynamic_a:
        return ops.loom_linear_serve_dynamic(
            x, p["w_packed"], p["w_scale"], a_bits=lp.a_bits,
            w_bits=p["w_packed"].shape[0], group_size=lp.group_size,
            backend=be, w_counts=lp.w_group_counts, w_group=lp.w_group,
            a_axis=_token_quant_axis(x), layer=lp.name)
    return ops.loom_linear_serve(
        x, p["w_packed"], p["w_scale"], a_bits=lp.a_bits,
        w_bits=p["w_packed"].shape[0], backend=be,
        w_counts=lp.w_group_counts, w_group=lp.w_group,
        a_axis=_token_quant_axis(x), layer=lp.name)


_LINEAR_ROUTES = {
    planlib.DENSE: _linear_dense,
    planlib.FAKE_QUANT: _linear_fake_quant,
    planlib.INT8: _linear_int8,
    planlib.PACKED: _linear_packed,
}


def linear_apply(p: dict, x: jax.Array, exec_cfg, layer_name: str = "") -> jax.Array:
    """Dispatch a linear through its resolved LayerPlan.

    ``exec_cfg``: an ``ExecutionPlan`` (``repro.api.build_plan``)."""
    xplan = planlib.as_plan(exec_cfg)
    lp = xplan.layer(layer_name, kind="linear")
    return _LINEAR_ROUTES[lp.route](p, x, lp, xplan.backend)


def _conv_same(x: jax.Array, w4: jax.Array, stride: int,
               preferred=None) -> jax.Array:
    """"same"-padded NHWC/HWIO conv, Ho = ceil(H/stride) (odd kernels)."""
    pad = w4.shape[0] // 2
    return jax.lax.conv_general_dilated(
        x, w4, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=preferred)


def _as_hwio(w2, kernel, c_in):
    return w2.reshape(kernel, kernel, c_in, -1)


def _conv_dense(p, x, kernel, stride, lp, xplan):
    return _conv_same(x, _as_hwio(p["w"], kernel, x.shape[-1]).astype(x.dtype),
                      stride)


def _conv_fake_quant(p, x, kernel, stride, lp, xplan):
    xq = q.fake_quant(x, lp.a_bits)
    wq = q.fake_quant(p["w"].astype(jnp.float32), lp.w_bits).astype(x.dtype)
    return _conv_same(xq, _as_hwio(wq, kernel, x.shape[-1]), stride)


def _conv_int8(p, x, kernel, stride, lp, xplan):
    c_in = x.shape[-1]
    a_bits = min(lp.a_bits, 8)
    xq, x_scale = q.quantize(x.astype(jnp.float32), a_bits)
    y = ops.int_conv_same(
        xq, _as_hwio(p["wq"], kernel, c_in), stride,
        exact_f32=ops.conv_accum_fits_f32(kernel * kernel * c_in, a_bits, 8))
    return (y * (x_scale * p["w_scale"]).astype(jnp.float32)).astype(x.dtype)


def _conv_packed(p, x, kernel, stride, lp, xplan):
    # Paper-faithful bit-serial conv over pre-packed planes. ``dynamic_a``
    # trims serial ACTIVATION planes per group of ``lp.group_size`` output
    # windows at runtime (bit-identical to the static plane count; its
    # bands ARE the window groups, so no separate tile is resolved). The
    # static kernel's band size comes from the plan's VMEM-budget
    # heuristic, resolved once per layer from the activation geometry.
    if lp.dynamic_a:
        return ops.loom_conv_serve_dynamic(
            x, p["w_packed"], p["w_scale"], kernel=kernel, stride=stride,
            a_bits=lp.a_bits, group_size=lp.group_size,
            backend=xplan.backend, w_counts=lp.w_group_counts,
            w_group=lp.w_group, layer=lp.name)
    tile = xplan.conv_tile(lp, x.shape[1], x.shape[2], x.shape[3],
                           p["w_packed"].shape[-1], p["w_packed"].shape[0])
    return ops.loom_conv_serve(
        x, p["w_packed"], p["w_scale"], kernel=kernel, stride=stride,
        a_bits=lp.a_bits, backend=xplan.backend, conv_tile=tile,
        w_counts=lp.w_group_counts, w_group=lp.w_group, layer=lp.name)


_CONV_ROUTES = {
    planlib.DENSE: _conv_dense,
    planlib.FAKE_QUANT: _conv_fake_quant,
    planlib.INT8: _conv_int8,
    planlib.PACKED: _conv_packed,
}


def conv_apply(p: dict, x: jax.Array, kernel: int, stride: int,
               exec_cfg, layer_name: str = "") -> jax.Array:
    """Dispatch a convolution through its resolved LayerPlan.

    Weights live in the param tree in the SAME 2-D [k*k*Cin, Cout] matrix
    layout as linears (row order (di, dj, c)), so precision profiling,
    serving conversion, and bit-packing are shared with the linear path.
    All routes run FUSED convs — the window walk happens inside
    lax.conv_general_dilated or the Pallas kernel, never as an HBM patch
    tensor.
    """
    xplan = planlib.as_plan(exec_cfg)
    lp = xplan.layer(layer_name, kind="conv", kernel=kernel, stride=stride)
    return _CONV_ROUTES[lp.route](p, x, kernel, stride, lp, xplan)


# ---------------------------------------------------------------------------
# Offline weight packing (the paper's bit-interleaved storage step).
# Converters are registered per serving mode — no mode string comparisons.
# ---------------------------------------------------------------------------

def _convert_linear_int8(p, prec):
    wq, w_scale = q.quantize(p["w"].astype(jnp.float32), 8)
    return {"wq": wq.astype(jnp.int8), "w_scale": w_scale.astype(jnp.float32)}


@functools.partial(jax.jit, static_argnums=1)
def _quantize_pack(w, bits: int):
    # One program per weight shape: run op by op, the bit-plane stack of
    # a 2048 x 151936 head alone would take 10 GB of int32 temporaries.
    wq, w_scale = q.quantize(w.astype(jnp.float32), bits)
    return bitpack.pack_weights(wq, bits), w_scale.astype(jnp.float32)


def _convert_linear_packed(p, prec):
    w_packed, w_scale = _quantize_pack(p["w"], prec.w_bits)
    return {"w_packed": w_packed, "w_scale": w_scale}


_LINEAR_CONVERTERS = {"serve_int8": _convert_linear_int8,
                      "serve_packed": _convert_linear_packed}

# The ONLY place the packed/int8 linear PartitionSpecs are written down:
# the param converter and the spec-only walk both read this table, so the
# real-conversion and eval_shape/dry-run paths cannot drift.
_LINEAR_SPEC_CONVERTERS = {
    "serve_int8": lambda in_ax, out_ax: {"wq": PS(in_ax, out_ax),
                                         "w_scale": PS(None, None)},
    "serve_packed": lambda in_ax, out_ax: {"w_packed": PS(None, in_ax, out_ax),
                                           "w_scale": PS(None, None)},
}


def convert_linear_for_serving(p: dict, spec: dict, prec, mode: str):
    """Offline weight packing for one linear. Returns (params, specs).

    For serve_packed the packed tensor's K/8 axis inherits the input
    sharding and N the output sharding; planes replicated.
    """
    try:
        converter = _LINEAR_CONVERTERS[mode]
    except KeyError:
        raise ValueError(mode) from None
    return converter(p, prec), convert_linear_specs(spec, mode)


def convert_linear_specs(spec: dict, mode: str) -> dict:
    """Spec-only counterpart of convert_linear_for_serving."""
    try:
        converter = _LINEAR_SPEC_CONVERTERS[mode]
    except KeyError:
        raise ValueError(mode) from None
    return converter(spec["w"][0], spec["w"][1])


def is_linear(p) -> bool:
    return isinstance(p, dict) and ("w" in p and isinstance(p["w"], (jax.Array, jax.ShapeDtypeStruct))
                                    and getattr(p["w"], "ndim", 0) == 2)


def embed_init(key, vocab: int, d_model: int, dtype=jnp.bfloat16):
    w = jax.random.normal(key, (vocab, d_model), dtype=jnp.float32) * 0.02
    return {"emb": w.astype(dtype)}, {"emb": PS("tp", "fsdp")}


def embed_apply(p: dict, tokens: jax.Array) -> jax.Array:
    return p["emb"][tokens]


def norm_init(d: int, dtype=jnp.bfloat16):
    return {"g": jnp.zeros((d,), dtype)}, {"g": PS(None)}
