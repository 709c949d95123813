"""Pallas TPU kernel: bit-serial (plane-serial) matmul over packed weights.

This is the SIP array adapted to the TPU memory hierarchy:

  * Weights live in HBM **bit-packed**: uint8 [Pw, K/8, N] — plane-major,
    8 reduction positions per byte (repro.core.bitpack layout). HBM traffic
    is Pw/16 of the bf16 baseline — the paper's bandwidth law.
  * Each grid step stages one (bk x bn) tile of ONE plane into VMEM,
    unpacks it to {0,1} int8 in-register, and feeds the MXU with an
    int8 x int8 -> int32 matmul against the activation tile: the TPU
    equivalent of a SIP column's AND + adder-tree, at MXU rate.
  * The serial plane loop is the innermost grid dimension; partial products
    are shifted by 2^p and accumulated in the output tile, with the MSB
    plane negated (2's complement — the paper's negation block).
  * Dynamic precision reduction: scalar-prefetch plane counts let the
    kernel skip planes above the runtime effective precision (Lascorz et
    al.) — blocks with plane >= the tile's count are masked via pl.when
    and re-name the last fetched plane, so neither MXU work nor an HBM
    fetch happens for them. A per-column plane-weight row carries each
    column group's own count inside the tile. The SAME kernel doubles as
    the STATIC per-filter-group weight trimming path (paper Sec 4.6):
    when the packed operand is the weights, the backend feeds the
    pack-time OR-tree counts from ``LayerPlan.w_group_counts`` over
    groups of 16 filters on 128-wide N-tiles — per-group weight
    precisions are known at pack time, so the counts are plan constants.

Activations are int8 (Pa <= 8 after quantization). This realizes the
paper's FCL law (work, bytes ∝ Pw) and, combined with 4-bit activation
packing upstream, the CVL law at plane granularity. Block shapes default to
MXU-aligned (multiples of 128 on M/N, 8*128 on packed K).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import weightgroups as wg


def unpack_plane(packed_tile: jax.Array) -> jax.Array:
    """uint8 [bk8, bn] -> {0,1} int8 [bk8*8, bn] (little-endian in byte).

    Row 8j+i of the plane is bit i of packed row j. The unpack runs in
    int32, whose (8, 128) register tile makes the [bk8, 8, bn] ->
    [bk8*8, bn] merge free: each packed row is broadcast across one
    tile's 8 sublanes and shifted right by its sublane index. Mosaic
    lowers neither 8-bit shifts nor that merge for 8-bit vectors."""
    bk8, bn = packed_tile.shape
    rows = jnp.broadcast_to(packed_tile.astype(jnp.int32)[:, None, :],
                            (bk8, 8, bn))
    shift = jax.lax.broadcasted_iota(jnp.int32, (bk8, 8, bn), 1)
    return ((rows >> shift) & 1).reshape(bk8 * 8, bn).astype(jnp.int8)


def _kernel(x_ref, wp_ref, out_ref, acc_ref, *, w_bits: int, nk: int):
    """Grid = (M/bm, N/bn, K/bk, Pw). Serial plane axis innermost."""
    k = pl.program_id(2)
    p = pl.program_id(3)

    @pl.when((k == 0) & (p == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    plane = unpack_plane(wp_ref[0])                      # [bk, bn] {0,1}
    part = jax.lax.dot_general(
        x_ref[...], plane,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                # MXU int8 pass
    sign = jnp.where(p == w_bits - 1, -1, 1)             # MSB negation
    acc_ref[...] += part * (sign * (1 << p))

    @pl.when((k == nk - 1) & (p == w_bits - 1))
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("w_bits", "bm", "bn", "bk",
                                             "interpret", "name"))
def bitserial_matmul(x: jax.Array, w_packed: jax.Array, *, w_bits: int,
                     bm: int = 128, bn: int = 128, bk: int = 512,
                     interpret: bool, name: str | None = None) -> jax.Array:
    """x: int8 [M, K]; w_packed: uint8 [Pw, K//8, N] -> int32 [M, N].

    Integer-exact: result == x.astype(i32) @ unpack(w_packed).astype(i32).
    ``interpret=True`` runs the kernel on CPU (validation); Mosaic
    compiles it for the TPU with ``interpret=False``. ``name`` names the
    kernel's HLO instruction (a profiler trace lists it as
    ``<name>.<n>``); None keeps ``bitserial_matmul``.
    """
    m, k = x.shape
    pw, k8, n = w_packed.shape
    assert pw == w_bits and k8 * 8 == k, (w_packed.shape, x.shape, w_bits)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % 8 == 0
    nk = k // bk

    grid = (m // bm, n // bn, nk, w_bits)
    return pl.pallas_call(
        functools.partial(_kernel, w_bits=w_bits, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, p: (i, kk)),
            pl.BlockSpec((1, bk // 8, bn), lambda i, j, kk, p: (p, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, p: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name=name,
    )(x, w_packed)


def _kernel_dyn(counts_ref, x_ref, wp_ref, pw_ref, out_ref, acc_ref, *,
                w_bits: int, nk: int):
    """Counted variant: counts_ref (scalar prefetch) holds the largest
    plane count of each N-tile, and planes at or above it are skipped
    entirely. pw_ref holds this plane's signed weight per column
    (``weightgroups.group_plane_weights`` spread over the columns): +2^p
    below a column group's count, -2^(count-1) at it, 0 above, so groups
    of any width share one 128-aligned tile."""
    j = pl.program_id(1)
    kk = pl.program_id(2)
    p = pl.program_id(3)

    @pl.when((kk == 0) & (p == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p < counts_ref[j])
    def _work():
        plane = unpack_plane(wp_ref[0])
        part = jax.lax.dot_general(
            x_ref[...], plane,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc_ref[...] += part * pw_ref[0]

    @pl.when((kk == nk - 1) & (p == w_bits - 1))
    def _done():
        out_ref[...] = acc_ref[...]


def column_plane_weights(counts: jax.Array, group: int, n: int,
                         bits: int) -> jax.Array:
    """int32 [bits, 1, n]: the signed weight of each plane per column,
    for per-group plane ``counts`` over groups of ``group`` columns."""
    table = wg.group_plane_weights(counts, bits)         # [groups, bits]
    return jnp.repeat(table.T, group, axis=1)[:, None, :n]


def tile_plane_counts(counts: jax.Array, group: int, n: int,
                      bn: int) -> jax.Array:
    """int32 [n/bn]: the largest plane count among each N-tile's columns
    (a wider two's-complement width keeps every narrower group's value,
    and the per-column weights zero the planes a group does not use)."""
    cols = jnp.repeat(jnp.asarray(counts, jnp.int32), group)[:n]
    return cols.reshape(n // bn, bn).max(axis=1)


def live_plane(p, count):
    """Plane block index for grid step ``p`` of a tile that executes
    ``count`` planes: skipped planes re-name the last executed one, so
    the pipeline fetches nothing new from HBM for them."""
    return jnp.maximum(jnp.minimum(p, count - 1), 0)


@functools.partial(jax.jit, static_argnames=("w_bits", "group", "bm", "bn",
                                             "bk", "interpret", "name"))
def bitserial_matmul_dynamic(x: jax.Array, w_packed: jax.Array,
                             plane_counts: jax.Array, *, w_bits: int,
                             group: int | None = None,
                             bm: int = 128, bn: int = 128, bk: int = 512,
                             interpret: bool,
                             name: str | None = None) -> jax.Array:
    """Like bitserial_matmul but column group g executes only
    plane_counts[g] planes, the (count-1)-th negated (two's complement
    at the effective width). A group is ``group`` columns (default: one
    N-tile of ``bn``); N-tiles hold whole or partial groups alike, so
    ``bn`` stays 128-aligned however narrow the groups are. Values that
    fit their group's count give the untrimmed result; for arbitrary
    counts this is the truncating oracle
    ``ref.bitserial_matmul_wgroup_ref``. ``name`` names the kernel's HLO
    instruction, as in :func:`bitserial_matmul` (None keeps
    ``bitserial_matmul_dynamic``)."""
    m, k = x.shape
    pw, k8, n = w_packed.shape
    assert pw == w_bits and k8 * 8 == k
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % 8 == 0
    group = bn if group is None else group
    assert plane_counts.shape == (-(-n // group),), (plane_counts.shape, n)
    nk = k // bk
    tiles = tile_plane_counts(plane_counts, group, n, bn)
    weights = column_plane_weights(plane_counts, group, n, w_bits)

    grid = (m // bm, n // bn, nk, w_bits)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, p, counts: (i, kk)),
            pl.BlockSpec((1, bk // 8, bn), lambda i, j, kk, p, counts:
                         (live_plane(p, counts[j]), kk, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, kk, p, counts: (p, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, p, counts: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel_dyn, w_bits=w_bits, nk=nk),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name=name,
    )(tiles, x, w_packed, weights)
