"""Static per-filter-group weight precision — Loom's sub-layer weight lever.

The paper's third lever (Sec 4.6, and the DPRed / Tartan line of work):
weight precision varies *within* a layer, so Loom keeps per-group metadata
for groups of 16 filters and executes only each group's effective number
of weight bit planes. Unlike activation trimming this is knowable at PACK
time — the OR-tree + leading-one detection runs once over the quantized
weights, and the resulting per-group plane counts are frozen into the
execution plan (``LayerPlan.w_group_counts``), never recomputed in the
hot path.

Semantics are the one group-mask idiom shared with the dynamic activation
routes: executing a group's first ``count`` planes with the (count-1)-th
plane negated equals 2's-complement truncation at width ``count`` —
value-preserving whenever the group's values fit (which the OR-tree
guarantees), the truncating-oracle semantics for arbitrary counts.

A group is ``group_size`` consecutive OUTPUT columns of the 2-D
[K, N] weight matrix — output filters for convs (the packed row order
folds k*k*C into K), output features for FC layers. The ragged last
group covers only its real columns; an all-zero group reports the 1-bit
floor (one plane of zeros still executes — counts never reach 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quantize as q


def weight_group_counts(wq: jax.Array, bits: int,
                        group_size: int) -> jax.Array:
    """Effective weight plane count per group of output columns.

    wq: int [K, N] quantized weights (signed, ``bits`` precision).
    Returns int32 [ceil(N/group_size)]: the OR-tree + leading-one
    minimum sufficient signed precision of each group of ``group_size``
    columns, clamped to [1, bits]. Pure jax (usable under eval_shape);
    callers that freeze counts into a plan do so eagerly.
    """
    k, n = wq.shape
    pad = (-n) % group_size
    if pad:
        wq = jnp.pad(wq, ((0, 0), (0, pad)))  # zeros never raise the OR
    g = wq.reshape(k, (n + pad) // group_size, group_size)
    eff = q.effective_bits(g, axis=(0, 2))
    return jnp.minimum(eff, bits).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def packed_group_counts(w_packed: jax.Array, bits: int,
                        group_size: int) -> jax.Array:
    """:func:`weight_group_counts` of packed planes uint8 [bits, K/8, N],
    as one program: unpacked op by op, a 2048 x 151936 head would stage
    10 GB of int32 plane temporaries."""
    from repro.core import bitpack
    return weight_group_counts(bitpack.unpack_weights(w_packed, bits), bits,
                               group_size)


def truncate_signed(v: jax.Array, counts: jax.Array) -> jax.Array:
    """2's-complement truncation of ``v`` at per-element width ``counts``:
    keep the low ``counts`` bits, reinterpret signed at that width. The
    ONE group-mask idiom every trimming route realizes — value-preserving
    whenever v fits in counts bits, the truncating-oracle semantics
    otherwise."""
    low = v & ((1 << counts) - 1)
    return low - (((low >> (counts - 1)) & 1) << counts)


def truncate_columns_grouped(wq: jax.Array, counts,
                             group_size: int) -> jax.Array:
    """Truncate each column group of ``wq`` [K, N] at its effective width.

    Group g keeps the low counts[g] bits of its columns, reinterpreted
    signed at that width (:func:`truncate_signed`) — the spec of what
    per-filter-group plane skipping computes: value-preserving when the
    group fits (the OR-tree guarantee), truncating otherwise. Tolerates
    a ragged last group (repeat + trim). The public column-group form of
    the mask idiom shared by the serving routes and the oracles.
    """
    n = wq.shape[-1]
    ccol = jnp.repeat(jnp.asarray(counts, jnp.int32), group_size)[:n]
    return truncate_signed(wq, ccol[None, :])


def group_plane_weights(counts, bits: int) -> jax.Array:
    """Per-group shift/negate metadata: the signed weight of each plane.

    Returns int32 [n_groups, bits]: plane p of group g contributes
    ``out[g, p] * plane_p`` — +2^p below the group's MSB, -2^(count-1) at
    it (the SIP negation block moved to the effective width), 0 for the
    skipped planes. The kernels and oracles realize this table
    implicitly (pl.when + a sign mux / :func:`truncate_signed`); it is
    materialized here as the inspectable spec of that decomposition —
    the per-group metadata a SIP-style accelerator would ship next to
    the packed planes.
    """
    c = jnp.asarray(counts, jnp.int32).reshape(-1, 1)
    p = jnp.arange(bits, dtype=jnp.int32).reshape(1, -1)
    w = jnp.where(p == c - 1, -(1 << p), 1 << p)
    return jnp.where(p < c, w, 0).astype(jnp.int32)


def grouped_packed_nbytes(shape_kn: tuple[int, int], counts,
                          group_size: int) -> int:
    """Bytes of the per-group packed store: each group keeps only its
    ``count`` planes (the paper's footprint claim at sub-layer
    granularity). Ragged tail groups are charged only their real columns;
    K%8 zero-padding is charged as in :func:`repro.core.bitpack.packed_nbytes`."""
    k, n = shape_kn
    k8rows = -(-k // 8)
    total = 0
    for g, c in enumerate(list(counts)):
        cols = min(group_size, n - g * group_size)
        total += int(c) * k8rows * cols
    return total


def mean_group_bits(counts) -> float:
    """Mean effective weight precision over the groups — the quantity the
    cycle model's weight-serial pass count scales with."""
    vals = [float(c) for c in list(counts)]
    return sum(vals) / len(vals)
