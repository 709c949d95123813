"""Pallas TPU kernels: fused bit-serial convolution on an Ho-banded grid.

This is the CVL execution path of the paper done properly on the TPU
memory hierarchy. The old lowering (models/cnn.py `_im2col` + matmul)
materialized [B, Ho, Wo, k*k*C] patch tensors in HBM — a k*k-fold
activation-bandwidth blowup that inverted the paper's bandwidth law.
Here the patch tensor never exists outside VMEM, and the grid is tiled
over OUTPUT ROWS (Tartan's tile-serial dataflow) so VMEM never has to
hold a whole feature map:

  * The grid is (B, n_bands, N/bn): each step covers ``rows_per_band``
    output rows of one image. Activations stream as overlapping input
    row bands ``[(r0*stride - pad) .. ((r0+rows_per_band-1)*stride +
    k - 1 - pad)]`` — materialized once by a row gather (the halo) so
    each BlockSpec block IS the band; the ragged tail band reads
    zero-padded rows whose outputs are discarded.
  * Weights stay bit-packed in HBM: uint8 [Pw, ceil(k*k*C/8), N]
    (repro.core.bitpack layout, zero-padded K rows when k*k*C % 8 != 0).
    HBM weight traffic is Pw/16 of the bf16 baseline.
  * Implicit im2col: the kernel walks the k*k window offsets with static
    strided slices of the VMEM-resident row band — the SIP array's
    sliding-window wiring — and assembles the band-local
    [rows_per_band*Wo, k*k*C] patch matrix directly in registers/VMEM.
  * The serial plane loop is UNROLLED IN THE KERNEL BODY: all Pw packed
    plane tiles are staged per grid step, each plane is unpacked in turn
    and issues one int8 MXU pass whose partial product is shift/negate-
    folded into the int32 accumulator (2's-complement MSB negation — the
    paper's negation block).

VMEM accounting (see :func:`conv_vmem_bytes`, the single source of
truth shared with the ``repro.api.plan`` tile heuristic and the
``bench_conv_tiled`` benchmark law). Per grid step, int8 unless noted:

    band input      ((rows_per_band-1)*stride + k) * Wp * C
    packed planes   Pw * ceil(kkC/8) * bn            (uint8)
    unpacked planes Pw * ceil(kkC/8)*8 * bn          ({0,1} int8)
    patch matrix    rows_per_band * Wo * ceil(kkC/8)*8
    accumulator     rows_per_band * Wo * bn * 4      (int32)

With ``rows_per_band = Ho`` (one band) this degenerates to the previous
whole-map kernel; shrinking the band divides the two dominant terms
(patch matrix + accumulator) by n_bands, which is what admits
large-resolution maps into a 16 MB VMEM. Band size is resolved once per
layer by ``repro.api.plan`` from the backend's VMEM budget — it is not
a hot-path kwarg.

`bitserial_conv_wgroup` is the STATIC per-filter-group precision variant
(the paper's Sec 4.6 / DPRed): the serial weight-plane loop moves from
the kernel body onto the grid — (B, n_bands, N/bn, Pw), plane innermost
— and the pack-time count per group of ``w_group`` output filters
(computed ONCE from the OR-tree over the group's weights, carried by
``LayerPlan.w_group_counts``) gates it: ``pl.when`` skips whole
(plane x N-tile) grid steps above the tile's largest count, and a
per-filter plane-weight row zeroes the planes above each group's own
count and negates its (count-1)-th plane (2's-complement truncation at
the group's effective width — value-preserving for OR-tree counts, so
the result is bit-identical to `bitserial_conv`). The band's patch
matrix is assembled once per band and reused from scratch across the
N-tiles and plane steps.

`bitserial_conv_dynamic` is the DYNAMIC-PRECISION transpose of the same
design (Lascorz et al., the paper's runtime trimming): the serial axis
becomes the ACTIVATION planes, weights ride as one dense int8 operand,
and a scalar-prefetch count per group of `group_size` output windows
gates the plane grid axis — `pl.when(p < count)` skips the whole grid
step for planes above the group's OR-tree effective width, with the
(count-1)-th plane negated (2's-complement truncation at the effective
width, value-preserving, so the result is bit-identical to the static
kernel). Its bands are the WINDOW GROUPS themselves: a group's windows
are contiguous in row-major order, so grid step (b, g, 0) loads only
group g's input row band and assembles exactly the patch rows the group
consumes (plus at most Wo-1 alignment rows when the group starts
mid-row) — per-group prologue work no longer scales with Ho*Wo, which
removes the factor-G patch redundancy the whole-map prologue had.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitserial_matmul import (column_plane_weights, live_plane,
                                            tile_plane_counts, unpack_plane)


def _patches(xv: jax.Array, kernel: int, stride: int, ho: int,
             wo: int) -> jax.Array:
    """Implicit im2col of one VMEM-resident padded row band: static window-
    offset strided slices, feature order (di, dj, c) — the pack_weights
    row order."""
    c = xv.shape[-1]
    cols = []
    for di in range(kernel):
        for dj in range(kernel):
            cols.append(jax.lax.slice(
                xv,
                (di, dj, 0),
                (di + (ho - 1) * stride + 1, dj + (wo - 1) * stride + 1, c),
                (stride, stride, 1)))               # [Ho, Wo, C]
    return jnp.concatenate(cols, axis=-1).reshape(ho * wo, kernel * kernel * c)


def band_geometry(ho: int, wo: int, rows_per_band: int | None, kernel: int,
                  stride: int) -> tuple[int, int, int]:
    """(rows_per_band, n_bands, band_input_rows) of the static banded grid.

    ``rows_per_band=None`` means one band covering the whole map (the
    untiled degenerate case); values are clamped to [1, Ho]."""
    rpb = ho if rows_per_band is None else max(1, min(rows_per_band, ho))
    return rpb, -(-ho // rpb), (rpb - 1) * stride + kernel


def dyn_band_geometry(wo: int, group_size: int, kernel: int,
                      stride: int) -> tuple[int, int]:
    """(output_rows_per_group, band_input_rows) of the dynamic kernel's
    group-aligned bands. A group of ``group_size`` row-major windows spans
    at most ceil((group_size + wo - 2)/wo) + 1 ... precisely
    (group_size + wo - 2)//wo + 1 output rows (the +Wo-1 slack covers a
    group starting mid-row)."""
    rows_pg = (group_size + wo - 2) // wo + 1
    return rows_pg, (rows_pg - 1) * stride + kernel


def conv_vmem_bytes(h: int, w: int, c: int, n: int, *, kernel: int,
                    stride: int = 1, w_bits: int, bn: int = 128,
                    rows_per_band: int | None = None) -> int:
    """Modeled per-grid-step VMEM footprint (bytes) of the banded static
    kernel — the accounting law the plan heuristic and the
    ``bench_conv_tiled`` benchmark both evaluate. See the module
    docstring for the five terms."""
    pad = kernel // 2
    wp_ = w + 2 * pad
    ho = -(-h // stride)
    wo = -(-w // stride)
    rpb, _, band_rows = band_geometry(ho, wo, rows_per_band, kernel, stride)
    kkc = kernel * kernel * c
    k8 = -(-kkc // 8) * 8
    bn = min(bn, n)
    return (band_rows * wp_ * c            # int8 input row band
            + w_bits * (k8 // 8) * bn      # packed planes (uint8)
            + w_bits * k8 * bn             # unpacked {0,1} planes (int8)
            + rpb * wo * k8                # band-local patch matrix (int8)
            + rpb * wo * bn * 4)           # int32 accumulator


def _banded(xp: jax.Array, starts: np.ndarray, band_rows: int) -> jax.Array:
    """[B, Hp, Wp, C] -> [B, n_bands, band_rows, Wp, C] overlapping bands.

    One gather materializes the halo (rows shared by adjacent bands) so a
    plain BlockSpec stages exactly one band per grid step. Rows past the
    padded map (ragged tail bands) are zero — their outputs are sliced
    off by the caller."""
    b, hp, wp_, c = xp.shape
    need = int(starts[-1]) + band_rows
    if need > hp:
        xp = jnp.pad(xp, ((0, 0), (0, need - hp), (0, 0), (0, 0)))
    if len(starts) == 1:    # single band (fits-in-VMEM case): no gather
        return xp[:, None, :band_rows]
    idx = starts[:, None] + np.arange(band_rows)[None, :]
    return xp[:, idx]


def _kernel(x_ref, wp_ref, out_ref, *, kernel: int, stride: int, w_bits: int,
            rows: int, wo: int, kpad: int):
    """Grid = (B, n_bands, N/bn). One row band, one channel tile per step."""
    patches = _patches(x_ref[0, 0], kernel, stride, rows, wo)
    if kpad:                                        # match packed K rows
        patches = jnp.pad(patches, ((0, 0), (0, kpad)))

    # The unrolled serial plane loop: Pw int8 MXU passes, each plane
    # unpacked from the staged tile, shift/negate folded into the int32
    # accumulate.
    bn = wp_ref.shape[-1]
    acc = jnp.zeros((patches.shape[0], bn), jnp.int32)
    for p in range(w_bits):
        part = jax.lax.dot_general(
            patches, unpack_plane(wp_ref[p]),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)       # int8 x {0,1} MXU pass
        sign = -1 if p == w_bits - 1 else 1         # MSB negation block
        acc += part * (sign * (1 << p))
    out_ref[0, 0] = acc.reshape(rows, wo, bn)


@functools.partial(jax.jit, static_argnames=("kernel", "stride", "w_bits",
                                             "bn", "rows_per_band",
                                             "interpret", "name"))
def bitserial_conv(x: jax.Array, w_packed: jax.Array, *, kernel: int,
                   stride: int = 1, w_bits: int, bn: int = 128,
                   rows_per_band: int | None = None,
                   interpret: bool, name: str | None = None) -> jax.Array:
    """Fused bit-serial "same"-padded conv over packed weight planes.

    x: int8 [B, H, W, C]; w_packed: uint8 [Pw, ceil(k*k*C/8), N].
    Returns int32 [B, ceil(H/stride), ceil(W/stride), N], integer-exact
    vs im2col + reference_int_matmul. Odd kernel sizes only ("same"
    geometry, pad = k//2). ``rows_per_band`` tiles the grid over output
    rows (None = one band = the whole map); banding never changes the
    result — it only bounds the per-step VMEM footprint
    (:func:`conv_vmem_bytes`). ``interpret=True`` validates on CPU.
    ``name`` names the kernel's HLO instruction (a profiler trace lists
    it as ``<name>.<n>``); None keeps ``bitserial_conv``.
    """
    assert kernel % 2 == 1, f"odd kernels only, got {kernel}"
    b, h, w, c = x.shape
    pw, k8, n = w_packed.shape
    kkc = kernel * kernel * c
    assert pw == w_bits and k8 == -(-kkc // 8), (w_packed.shape, kkc, w_bits)
    bn = min(bn, n)
    assert n % bn == 0, (n, bn)

    pad = kernel // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    wp_ = w + 2 * pad
    ho = -(-h // stride)
    wo = -(-w // stride)
    rpb, nb, band_rows = band_geometry(ho, wo, rows_per_band, kernel, stride)
    xb = _banded(xp, np.arange(nb) * rpb * stride, band_rows)

    grid = (b, nb, n // bn)
    out = pl.pallas_call(
        functools.partial(_kernel, kernel=kernel, stride=stride,
                          w_bits=w_bits, rows=rpb, wo=wo, kpad=k8 * 8 - kkc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, band_rows, wp_, c),
                         lambda i, j, l: (i, j, 0, 0, 0)),
            pl.BlockSpec((pw, k8, bn), lambda i, j, l: (0, 0, l)),
        ],
        out_specs=pl.BlockSpec((1, 1, rpb, wo, bn),
                               lambda i, j, l: (i, j, 0, 0, l)),
        out_shape=jax.ShapeDtypeStruct((b, nb, rpb, wo, n), jnp.int32),
        interpret=interpret,
        name=name,
    )(xb, w_packed)
    return out.reshape(b, nb * rpb, wo, n)[:, :ho]


def _kernel_wg(counts_ref, x_ref, wp_ref, pw_ref, out_ref, patch_ref,
               acc_ref, *, kernel: int, stride: int, w_bits: int, rows: int,
               wo: int, kpad: int):
    """Grid = (B, n_bands, N/bn, Pw): serial WEIGHT-plane axis innermost.

    counts_ref (scalar prefetch) holds the largest pack-time effective
    weight precision among each N-tile's filter groups (the paper's Sec
    4.6 per-group metadata). Plane grid steps with p >= that count are
    skipped entirely via pl.when — no patch matmul, and no HBM fetch of
    that plane's tile. pw_ref holds this plane's signed weight per
    output filter (``weightgroups.group_plane_weights`` spread over the
    filters), which applies each group's own count inside the tile: 0
    above it, the (count-1)-th plane negated (2's complement at the
    group's effective width). The band's patch rows are assembled once
    and reused from scratch (counts have a 1-bit floor, so plane 0
    always executes)."""
    l = pl.program_id(2)
    p = pl.program_id(3)

    # The band's patch matrix depends only on (batch, band): assemble it
    # once at the FIRST N-tile and reuse the scratch across all N/bn
    # tiles.
    @pl.when((l == 0) & (p == 0))
    def _patches_init():
        patches = _patches(x_ref[0, 0], kernel, stride, rows, wo)
        if kpad:
            patches = jnp.pad(patches, ((0, 0), (0, kpad)))
        patch_ref[...] = patches

    @pl.when(p == 0)
    def _acc_init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p < counts_ref[l])
    def _work():
        part = jax.lax.dot_general(
            patch_ref[...], unpack_plane(wp_ref[0]),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)       # int8 x {0,1} MXU pass
        acc_ref[...] += part * pw_ref[0]

    @pl.when(p == w_bits - 1)
    def _done():
        out_ref[0, 0] = acc_ref[...].reshape(rows, wo, -1)


@functools.partial(jax.jit, static_argnames=("kernel", "stride", "w_bits",
                                             "w_group", "bn",
                                             "rows_per_band", "interpret",
                                             "name"))
def bitserial_conv_wgroup(x: jax.Array, w_packed: jax.Array,
                          counts: jax.Array, *, kernel: int, stride: int = 1,
                          w_bits: int, w_group: int = 16, bn: int = 128,
                          rows_per_band: int | None = None,
                          interpret: bool,
                          name: str | None = None) -> jax.Array:
    """Fused bit-serial conv with STATIC per-filter-group plane skipping.

    x: int8 [B, H, W, C]; w_packed: uint8 [Pw, ceil(k*k*C/8), N]; counts:
    int32 [ceil(N/w_group)] — the pack-time OR-tree effective weight
    precision of each group of ``w_group`` output filters
    (``LayerPlan.w_group_counts``). Filter group g executes only
    counts[g] of the ``w_bits`` serial weight planes; ``bn`` is the
    N-tile, which may hold several groups (on the TPU it must be a
    multiple of 128 or all of N). Returns int32 [B, Ho, Wo, N] ("same"
    geometry), bit-identical to :func:`bitserial_conv` whenever every
    group's weights fit in its count (the OR-tree guarantee); for
    arbitrary counts it matches the truncating oracle
    ``ref.bitserial_conv_wgroup_ref``. ``rows_per_band`` bands the grid
    over output rows exactly as in the static kernel, and ``name``
    names the kernel's HLO instruction as there (None keeps
    ``bitserial_conv_wgroup``).
    """
    assert kernel % 2 == 1, f"odd kernels only, got {kernel}"
    b, h, w, c = x.shape
    pw, k8, n = w_packed.shape
    kkc = kernel * kernel * c
    assert pw == w_bits and k8 == -(-kkc // 8), (w_packed.shape, kkc, w_bits)
    bn = min(bn, n)
    assert n % bn == 0, (n, bn)
    assert counts.shape == (-(-n // w_group),), (counts.shape, n, w_group)
    tiles = tile_plane_counts(counts, w_group, n, bn)
    weights = column_plane_weights(counts, w_group, n, w_bits)

    pad = kernel // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    wp_ = w + 2 * pad
    ho = -(-h // stride)
    wo = -(-w // stride)
    rpb, nb, band_rows = band_geometry(ho, wo, rows_per_band, kernel, stride)
    xb = _banded(xp, np.arange(nb) * rpb * stride, band_rows)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nb, n // bn, w_bits),
        in_specs=[
            pl.BlockSpec((1, 1, band_rows, wp_, c),
                         lambda i, j, l, p, counts: (i, j, 0, 0, 0)),
            pl.BlockSpec((1, k8, bn), lambda i, j, l, p, counts:
                         (live_plane(p, counts[l]), 0, l)),
            pl.BlockSpec((1, 1, bn), lambda i, j, l, p, counts: (p, 0, l)),
        ],
        out_specs=pl.BlockSpec((1, 1, rpb, wo, bn),
                               lambda i, j, l, p, counts: (i, j, 0, 0, l)),
        scratch_shapes=[pltpu.VMEM((rpb * wo, k8 * 8), jnp.int8),
                        pltpu.VMEM((rpb * wo, bn), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_wg, kernel=kernel, stride=stride,
                          w_bits=w_bits, rows=rpb, wo=wo,
                          kpad=k8 * 8 - kkc),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((b, nb, rpb, wo, n), jnp.int32),
        interpret=interpret,
        name=name,
    )(tiles, xb, w_packed, weights)
    return out.reshape(b, nb * rpb, wo, n)[:, :ho]


def _kernel_dyn(counts_ref, x_ref, w_ref, out_ref, band_ref, rows_ref,
                acc_ref, *, kernel: int, stride: int, a_bits: int, rows: int,
                wo: int, gsz: int, kpad: int):
    """Grid = (B, G, Pa): the serial ACTIVATION-plane axis innermost.

    The dynamic-precision transpose of the static kernel: weights ride as
    one dense int8 operand and the activations are decomposed plane-
    serially, so the runtime per-window-group effective precision
    (counts_ref, scalar prefetch — the per-group metadata of Lascorz et
    al.) gates the plane axis: plane grid steps with p >= count are
    skipped entirely via pl.when, and the (count-1)-th plane is negated
    (2's complement at the effective width). The group's patch rows are
    assembled ONCE, at plane 0 (which always executes — counts have a
    1-bit floor), from the group's OWN input row band: the band covers
    the ``rows`` output rows group g's windows span, so the prologue
    builds rows*Wo >= gsz patch rows and slices the group's gsz at its
    in-band column offset — band-local work, independent of Ho*Wo. Both
    patch scratches are int32: Mosaic reads a dynamic row offset from a
    32-bit ref only."""
    b = pl.program_id(0)
    g = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        patches = _patches(x_ref[0, 0], kernel, stride, rows, wo)
        if kpad:
            patches = jnp.pad(patches, ((0, 0), (0, kpad)))
        band_ref[...] = patches.astype(jnp.int32)
        w0 = g * gsz                        # first window of the group
        off = w0 - (w0 // wo) * wo          # its column offset in the band
        rows_ref[...] = band_ref[pl.ds(off, gsz), :]
        acc_ref[...] = jnp.zeros_like(acc_ref)

    count = counts_ref[b, g]

    @pl.when(p < count)
    def _work():
        bit = ((rows_ref[...] >> p) & 1).astype(jnp.int8)
        part = jax.lax.dot_general(
            bit, w_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)       # {0,1} x int8 MXU pass
        sign = jnp.where(p == count - 1, -1, 1)     # MSB at effective width
        acc_ref[...] += part * (sign * (jnp.int32(1) << p))

    @pl.when(p == a_bits - 1)
    def _done():
        out_ref[0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("kernel", "stride", "a_bits",
                                             "group_size", "interpret",
                                             "name"))
def bitserial_conv_dynamic(x: jax.Array, wq: jax.Array, counts: jax.Array, *,
                           kernel: int, stride: int = 1, a_bits: int,
                           group_size: int = 256,
                           interpret: bool,
                           name: str | None = None) -> jax.Array:
    """Fused "same"-padded conv with runtime activation-plane trimming.

    x: int8 [B, H, W, C]; wq: int8 [K8, N] — the UNPACKED weights (or one
    int8-safe subplane of a Pw>8 weight, summed by the caller), zero-padded
    to the packed layout's K8 = ceil(k*k*C/8)*8 rows; counts: int32
    [B, ceil(Ho*Wo/group_size)] per-window-group effective activation
    precisions (core.dynamic.conv_window_group_counts). Group g of image b
    executes only counts[b, g] of the ``a_bits`` serial activation planes.
    Window groups are band-aligned: grid step (b, g, p) stages only the
    input row band group g's windows read, so patch assembly is band-local
    (per-group work ~ group_size + Wo, NOT Ho*Wo). Returns int32
    [B, Ho, Wo, N], bit-identical to the static conv whenever every
    group's values fit in its count (2's-complement truncation at the
    effective width is value-preserving). ``name`` names the kernel's
    HLO instruction, as in :func:`bitserial_conv` (None keeps
    ``bitserial_conv_dynamic``).
    """
    assert kernel % 2 == 1, f"odd kernels only, got {kernel}"
    b, h, w, c = x.shape
    k8, n = wq.shape
    kkc = kernel * kernel * c
    assert k8 == -(-kkc // 8) * 8, (wq.shape, kkc)
    assert 1 <= a_bits <= 8, a_bits

    pad = kernel // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    wp_ = w + 2 * pad
    ho = -(-h // stride)
    wo = -(-w // stride)
    nwin = ho * wo
    gsz = group_size
    ng = -(-nwin // gsz)
    assert counts.shape == (b, ng), (counts.shape, b, ng)

    rows_pg, band_rows = dyn_band_geometry(wo, gsz, kernel, stride)
    starts = (np.arange(ng) * gsz // wo) * stride   # group g's first out row
    xb = _banded(xp, starts, band_rows)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, ng, a_bits),
        in_specs=[
            pl.BlockSpec((1, 1, band_rows, wp_, c),
                         lambda i, j, p, counts: (i, j, 0, 0, 0)),
            pl.BlockSpec((k8, n), lambda i, j, p, counts: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, gsz, n), lambda i, j, p, counts: (i, j, 0)),
        scratch_shapes=[pltpu.VMEM((rows_pg * wo, k8), jnp.int32),  # band
                        pltpu.VMEM((gsz, k8), jnp.int32),   # group patch rows
                        pltpu.VMEM((gsz, n), jnp.int32)],   # accumulator
    )
    out = pl.pallas_call(
        functools.partial(_kernel_dyn, kernel=kernel, stride=stride,
                          a_bits=a_bits, rows=rows_pg, wo=wo, gsz=gsz,
                          kpad=k8 - kkc),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((b, ng * gsz, n), jnp.int32),
        interpret=interpret,
        name=name,
    )(counts, xb, wq)
    return out[:, :nwin].reshape(b, ho, wo, n)
