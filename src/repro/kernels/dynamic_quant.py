"""Pallas TPU kernel: per-group dynamic quantization + precision detection.

Fuses Loom's runtime activation path: per group of G activations compute the
absmax (the OR-tree), derive scale and the effective precision (the
leading-one detector of Lascorz et al.), and emit int8 values. Runs once
per layer input on the serving path; its eff_bits output feeds the
bit-serial matmul's dynamic plane counts and the performance counters.

Tiling: grid over row blocks; each block stages [bm, K] f32 into VMEM,
reduces per group along the lane dimension, writes int8 values + per-group
scale/effective-bit metadata.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, xq_ref, scale_ref, eff_ref, *, group_size: int, bits: int):
    x = x_ref[...]                                  # [bm, K] f32
    bm, k = x.shape
    g = k // group_size
    xg = x.reshape(bm, g, group_size)
    absmax = jnp.maximum(jnp.max(jnp.abs(xg), axis=-1),
                         jnp.finfo(jnp.float32).tiny)      # [bm, g]
    qmax = (1 << (bits - 1)) - 1
    scale = absmax / qmax
    xq = jnp.clip(jnp.round(xg / scale[..., None]),
                  -(1 << (bits - 1)), qmax)
    mag = jnp.max(jnp.abs(xq), axis=-1)                    # [bm, g]
    eff = jnp.ceil(jnp.log2(mag + 1.0)).astype(jnp.int32) + 1
    xq_ref[...] = xq.reshape(bm, k).astype(jnp.int8)
    scale_ref[...] = scale
    eff_ref[...] = jnp.maximum(eff, 1)


@functools.partial(jax.jit, static_argnames=("group_size", "bits", "bm", "interpret"))
def dynamic_quant(x: jax.Array, *, group_size: int = 256, bits: int = 8,
                  bm: int = 256, interpret: bool):
    """x: f32 [M, K] -> (xq int8 [M,K], scale f32 [M,G], eff_bits i32 [M,G]).

    G = K // group_size. Matches ref.dynamic_quant_ref exactly.
    """
    m, k = x.shape
    assert k % group_size == 0, (k, group_size)
    g = k // group_size
    bm = min(bm, m)
    assert m % bm == 0

    return pl.pallas_call(
        functools.partial(_kernel, group_size=group_size, bits=bits),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, g), lambda i: (i, 0)),
            pl.BlockSpec((bm, g), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((m, g), jnp.float32),
            jax.ShapeDtypeStruct((m, g), jnp.int32),
        ),
        interpret=interpret,
    )(x)
