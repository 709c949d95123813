"""Serving-path orchestration around the backend op surface.

Model code dispatches through resolved ``LayerPlan``s (repro.api.plan);
the functions here own the numeric orchestration that is identical on
every backend — dynamic activation quantization, K-padding against the
packed layout, plane-count detection, and the final dequantizing cast —
and delegate the integer core to a ``repro.api.backend.Backend``.

All entry points accept ``backend=`` (a Backend object or registered
name; None resolves to the XLA built-in). The deprecated boolean kernel
flags were retired with the seed-era string-mode shim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.api.backend import resolve_backend
from repro.core import bitpack, dynamic, quantize as q, weightgroups
from repro.kernels import ref


def kernel_name(kernel: str, layer: str) -> str | None:
    """The name of ``kernel`` serving ``layer`` in the compiled program
    (``bitserial_matmul_attn_q``); no layer keeps the kernel's own."""
    return f"{kernel}_{layer}" if layer else None


def loom_linear_serve(x: jax.Array, w_packed: jax.Array, w_scale: jax.Array,
                      *, a_bits: int, w_bits: int, backend=None,
                      w_counts=None, w_group: int = 16,
                      a_axis: int | None = -1, layer: str = "") -> jax.Array:
    """Serving-path linear: activations dynamically quantized to a_bits,
    weights pre-packed bit-serially. Output in x.dtype.

    x: [..., K]; w_packed: uint8 [Pw, K//8, N]; w_scale: per-tensor f32.
    ``w_counts``/``w_group``: pack-time per-filter-group weight plane
    counts (``LayerPlan.w_group_counts`` — Python ints, never recomputed
    here); the backend then executes only each group's effective planes,
    bit-identically to the untrimmed path.
    ``a_axis``: activation-quantization axis. Default -1 = per-row scales
    (each token row on its own grid — continuous batching's byte-identity
    bar); None = one per-tensor scale (the conv/im2col lowering's grid).
    ``layer`` names the kernel after the layer (:func:`kernel_name`).
    """
    be = resolve_backend(backend)
    lead = x.shape[:-1]
    k = x.shape[-1]
    # Already-flat inputs skip the reshape round-trip entirely (XLA does
    # not always elide the pair across the quantize boundary).
    x2 = x if x.ndim == 2 else x.reshape(-1, k)
    k8 = w_packed.shape[1] * 8
    if k8 != k:  # pack_weights zero-pads K%8 rows; mirror on activations
        x2 = jnp.pad(x2, ((0, 0), (0, k8 - k)))
    a_bits = min(a_bits, 8)  # int8 kernel ABI; Pa>8 would wrap in astype
    # Per-ROW scales (default): each token row quantizes on its own grid,
    # so a row's result is invariant to whatever it is co-batched with
    # (continuous batching's byte-identity bar). For batch-1 the row scale
    # IS the tensor scale.
    xq, x_scale = q.quantize(x2, a_bits, axis=a_axis)
    # Trimming kwargs only travel when counts exist; ``name`` always
    # does, so a Backend's plane ops must accept it.
    trim = {} if w_counts is None else dict(a_bits=a_bits, w_counts=w_counts,
                                            w_group=w_group)
    y = be.matmul_planes(xq.astype(jnp.int8), w_packed, w_bits=w_bits,
                         name=kernel_name("bitserial_matmul", layer), **trim)
    # Single cast at the end: the int32 accumulate is scaled in f32 and
    # dropped straight to x.dtype (bf16 in, bf16 out — no double round).
    out = (y * (x_scale * w_scale).astype(jnp.float32)).astype(x.dtype)
    return out if x.ndim == 2 else out.reshape(*lead, -1)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def loom_linear_serve_dynamic(x: jax.Array, w_packed: jax.Array,
                              w_scale: jax.Array, *, a_bits: int,
                              w_bits: int, group_size: int = 256,
                              backend=None, w_counts=None,
                              w_group: int = 16,
                              a_axis: int | None = -1,
                              layer: str = "") -> jax.Array:
    """Dynamic-precision serving linear: runtime activation-plane trimming.

    Loom's Lascorz-style path: activations are quantized on the SAME
    grid as the static path (per-row by default — see ``a_axis`` on
    :func:`loom_linear_serve`), then an OR-tree finds each group's
    minimum sufficient precision and only that many ACTIVATION bit planes
    execute — trimming below the static per-layer profile at runtime,
    value-preserving (2's-complement truncation), so the result is
    bit-identical to :func:`loom_linear_serve`.

    Realization on the TPU kernel ABI: the matmul is transposed so the
    activations become the plane-serial packed operand —

        y.T[N, M] = Wq.T[N, K] @ Xq[K, M]

    with ``Xq`` bit-interleaved [Pa, K/8, M] at runtime (the paper's
    transposer writing ABout to AM) and per-group-of-``group_size``
    columns plane counts fed to the scalar-prefetch kernel
    (``bitserial_matmul_dynamic``), which skips whole planes per group.
    Weights ride int8 MXU passes; Pw > 8 splits them into int8-safe
    subplanes whose shifted partials accumulate exactly.

    ``w_counts``/``w_group`` compose static per-filter-group weight
    trimming in: the dense weight operand is truncated per group of
    output columns at its pack-time effective width (value-preserving
    for OR-tree counts, so the composition stays bit-identical to the
    static path); the modeled pass count becomes
    mean_Pa_eff x mean_Pw_eff over the group intersections.
    ``layer`` names the kernel after the layer (:func:`kernel_name`).
    """
    be = resolve_backend(backend)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x if x.ndim == 2 else x.reshape(-1, k)
    k8 = w_packed.shape[1] * 8
    if k8 != k:
        x2 = jnp.pad(x2, ((0, 0), (0, k8 - k)))
    a_bits = min(a_bits, 8)
    # same grid as the static path (per-row by default): bit-identical
    # composition, no cross-row leakage of the quant grid under batching
    xq, x_scale = q.quantize(x2, a_bits, axis=a_axis)
    m = xq.shape[0]
    # Group = group_size concurrently-processed rows; tiny batches clamp
    # to one 8-row-aligned group rather than padding 256x.
    g = min(group_size, _round_up(m, 8))
    mp = _round_up(m, g)
    if mp != m:
        xq = jnp.pad(xq, ((0, mp - m), (0, 0)))   # zero rows: 1-bit floor
    counts = dynamic.serve_group_counts(xq, g, a_bits)          # [mp/g]
    x_packed = bitpack.pack_weights(xq.T, a_bits)  # [Pa, k8/8, mp]
    wq = bitpack.unpack_weights(w_packed, w_bits)               # [k8, N]
    if w_counts is not None:
        wq = weightgroups.truncate_columns_grouped(wq, w_counts, w_group)
    if w_bits <= 8:
        w_planes, shifts = wq[None], jnp.ones((1,), jnp.int32)
    else:
        # int8 MXU ABI: 7-bit subplanes keep every plane value in int8
        # range (an unsigned 8-bit low plane would not fit).
        w_planes, shifts = q.group_planes(wq, w_bits, 7)
    name = kernel_name("bitserial_matmul", layer)
    yt = None
    for i in range(w_planes.shape[0]):
        part = be.matmul_planes_dynamic(
            w_planes[i].T.astype(jnp.int8), x_packed, counts,
            w_bits=a_bits, bn=g, name=name)                     # [N, mp]
        part = part * shifts[i]
        yt = part if yt is None else yt + part
    y = yt.T[:m]
    out = (y * (x_scale * w_scale).astype(jnp.float32)).astype(x.dtype)
    return out if x.ndim == 2 else out.reshape(*lead, -1)


def conv_accum_fits_f32(kkc: int, a_bits: int, w_bits: int) -> bool:
    """True when every partial sum of the integer conv is <= 2^24 in
    magnitude, i.e. exactly representable in a float32 mantissa."""
    return kkc << (a_bits - 1 + w_bits - 1) <= 1 << 24


# Stems with C <= this fold their k*k window offsets into the channel
# dim (one GEMM over K = k*k*C) instead of walking k*k tiny-K passes:
# below ~64 reduction elements per pass the XLA:CPU GEMM is launch-
# overhead-bound and the k*k walk loses to a single wider matmul.
STEM_FOLD_MAX_C = 4


def int_conv_same(x_int: jax.Array, w4: jax.Array, stride: int,
                  exact_f32: bool = False,
                  fold_kk: bool | None = None) -> jax.Array:
    """Integer "same"-padded conv as k*k shift-and-matmul passes.

    x_int: int [B, H, W, C]; w4: int [k, k, C, N] -> exact int32
    [B, ceil(H/stride), ceil(W/stride), N]. Each window offset (di, dj)
    contributes one strided slice of the RAW map matmul'd against its
    [C, N] weight slab — the SIP sliding-window wiring expressed as
    matmuls. No k*k*C-wide patch tensor exists at any point, and every
    pass hits XLA's fast matmul path (XLA:CPU lowers integer
    conv_general_dilated to a slow generic loop — 2-7x slower on the
    paper CNN's layer shapes).

    ``exact_f32``: run the passes in float32 — callers must guarantee
    conv_accum_fits_f32, which makes the result bit-identical while
    hitting the (much faster on CPU) f32 GEMM; small-K stems gain ~4x.

    ``fold_kk``: fold the k*k window offsets into the channel dim and run
    ONE GEMM over K = k*k*C instead of k*k passes of K = C. Default
    (None) folds small-C stems (C <= ``STEM_FOLD_MAX_C``, e.g. a 3x3 RGB
    conv1: 9 GEMMs of K=3 -> 1 GEMM of K=27) where the walk is
    GEMM-overhead-bound; bit-identical either way (same products, and
    under ``exact_f32`` every partial sum is mantissa-exact regardless
    of summation order).
    """
    k, _, c, n = w4.shape
    pad = k // 2
    b, h, w_, _ = x_int.shape
    ho, wo = -(-h // stride), -(-w_ // stride)
    dt = jnp.float32 if exact_f32 else jnp.int32
    xp = jnp.pad(x_int.astype(dt),
                 ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    if fold_kk is None:
        fold_kk = c <= STEM_FOLD_MAX_C
    slices = ref.conv_window_slices(xp, k, stride, ho, wo)
    if fold_kk:
        patches = jnp.concatenate(slices, axis=-1)      # [B, Ho, Wo, kkC]
        acc = jax.lax.dot_general(
            patches, w4.astype(dt).reshape(k * k * c, n),
            dimension_numbers=(((3,), (0,)), ((), ())),
            preferred_element_type=dt)
        return acc.astype(jnp.int32)
    wc = w4.astype(dt).reshape(k * k, c, n)
    acc = jnp.zeros((b, ho, wo, n), dt)
    for sl, wslab in zip(slices, wc):
        acc = acc + jax.lax.dot_general(
            sl, wslab,
            dimension_numbers=(((3,), (0,)), ((), ())),
            preferred_element_type=dt)
    return acc.astype(jnp.int32)


def loom_conv_serve(x: jax.Array, w_packed: jax.Array, w_scale: jax.Array,
                    *, kernel: int, stride: int, a_bits: int, backend=None,
                    conv_tile: int | None = None, w_counts=None,
                    w_group: int = 16, layer: str = "") -> jax.Array:
    """Serving-path fused conv: the CVL execution path.

    x: [B, H, W, C] float; w_packed: uint8 [Pw, ceil(k*k*C/8), N] in the
    (di, dj, c)-row order of pack_weights(im2col weights). Activations are
    dynamically quantized to a_bits; the conv runs integer-exact over the
    packed planes (banded Pallas kernel on the pallas backends, one XLA integer
    conv otherwise — neither materializes an im2col patch tensor in HBM).
    Output in x.dtype. ``w_counts``/``w_group``: pack-time per-filter-group
    weight plane counts from the plan — each filter group then executes
    only its effective planes, bit-identically to the untrimmed path.
    ``layer`` names the kernel after the layer (:func:`kernel_name`).
    """
    be = resolve_backend(backend)
    w_bits = w_packed.shape[0]
    # int8 is the kernel ABI (one MXU pass per weight plane); higher
    # profile precisions clamp to 8 like serve_int8 — without this the
    # astype below would wrap Pa>8 values modulo 256.
    a_bits = min(a_bits, 8)
    xq, x_scale = q.quantize(x.astype(jnp.float32), a_bits)
    trim = {} if w_counts is None else dict(w_counts=w_counts,
                                            w_group=w_group)
    y = be.conv_planes(xq, w_packed, kernel=kernel, stride=stride,
                       w_bits=w_bits, a_bits=a_bits, conv_tile=conv_tile,
                       name=kernel_name("bitserial_conv", layer), **trim)
    return (y * (x_scale * w_scale).astype(jnp.float32)).astype(x.dtype)


def loom_conv_serve_dynamic(x: jax.Array, w_packed: jax.Array,
                            w_scale: jax.Array, *, kernel: int, stride: int,
                            a_bits: int, group_size: int = 256,
                            backend=None, w_counts=None,
                            w_group: int = 16, layer: str = "") -> jax.Array:
    """Dynamic-precision serving conv: runtime activation-plane trimming.

    The CVL analogue of :func:`loom_linear_serve_dynamic`: activations are
    quantized on the SAME per-tensor grid as the static path, then the
    OR-tree (``core.dynamic.conv_window_group_counts``) finds the minimum
    sufficient precision of each group of ``group_size`` output windows —
    the paper's "much smaller than a layer" granularity — and only that
    many serial ACTIVATION planes execute per group
    (``backend.conv_planes_dynamic``). 2's-complement truncation at the
    effective width is value-preserving, so the result is bit-identical
    to :func:`loom_conv_serve`. Tiny output maps clamp the group to one
    8-window-aligned group rather than padding 256x.

    ``w_counts``/``w_group`` compose static per-filter-group weight
    trimming in (pack-time counts from the plan): the backend truncates
    each filter group's weights at its effective width — bit-identical
    composition for OR-tree counts, modeled passes
    mean_Pa_eff x mean_Pw_eff. ``layer`` names the kernel after the
    layer (:func:`kernel_name`).
    """
    be = resolve_backend(backend)
    w_bits = w_packed.shape[0]
    a_bits = min(a_bits, 8)  # int8 kernel ABI, as in loom_conv_serve
    xq, x_scale = q.quantize(x.astype(jnp.float32), a_bits)  # static grid
    h, w_ = x.shape[1], x.shape[2]
    nwin = -(-h // stride) * -(-w_ // stride)
    gsz = min(group_size, _round_up(nwin, 8))
    counts = dynamic.conv_window_group_counts(xq, kernel, stride, gsz,
                                              a_bits)
    trim = {} if w_counts is None else dict(w_counts=w_counts,
                                            w_group=w_group)
    y = be.conv_planes_dynamic(xq, w_packed, counts, kernel=kernel,
                               stride=stride, w_bits=w_bits, a_bits=a_bits,
                               group_size=gsz,
                               name=kernel_name("bitserial_conv", layer),
                               **trim)
    return (y * (x_scale * w_scale).astype(jnp.float32)).astype(x.dtype)


def quantize_activations(x: jax.Array, *, group_size: int = 256,
                         bits: int = 8, backend=None):
    """Dynamic per-group activation quantization (Loom's runtime path)."""
    be = resolve_backend(backend)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    xq, scale, eff = be.dynamic_quant(x2, group_size=group_size, bits=bits)
    return (xq.reshape(*lead, -1), scale.reshape(*lead, -1),
            eff.reshape(*lead, -1))


def attention(q_: jax.Array, k_: jax.Array, v_: jax.Array, *,
              causal: bool = True, window: int | None = None,
              backend=None) -> jax.Array:
    """Full-sequence attention ([B,H,S,D], KV already head-repeated)."""
    be = resolve_backend(backend)
    return be.attention(q_, k_, v_, causal=causal, window=window)
