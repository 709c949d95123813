"""Pallas TPU kernel: chunked (flash) causal attention.

Used by the 32k-prefill path, where materializing [S, S] logits is
impossible. Online-softmax over KV blocks with VMEM-resident accumulators:

    grid = (B*H, S/bq); inner fori over S/bk KV blocks
    running (m, l, acc) updated per block; causal + optional sliding window
    masking at block granularity (fully-masked blocks are skipped by the
    trip-count bound, matching SWA's sub-quadratic cost).

Not a Loom contribution per se, but the perf-critical substrate kernel the
quantized serving path runs on; KV tensors may arrive Loom-packed (dequant
happens in the engine's KV-cache read path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, seq: int,
            scale: float, causal: bool, window: int | None):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # [bq, d]
    d = q.shape[-1]

    q_pos = iq * bq + jax.lax.iota(jnp.int32, bq)       # absolute q indices

    # Causal: only KV blocks with start <= last q index participate.
    n_kv = seq // bk
    if causal:
        hi = jnp.minimum(((iq + 1) * bq + bk - 1) // bk, n_kv)
    else:
        hi = n_kv
    if window is not None:
        lo = jnp.maximum((iq * bq - window + 1) // bk, 0)
    else:
        lo = 0

    def body(jk, carry):
        m_prev, l_prev, acc = carry
        k_blk = k_ref[0, pl.dslice(jk * bk, bk), :].astype(jnp.float32)  # [bk, d]
        v_blk = v_ref[0, pl.dslice(jk * bk, bk), :].astype(jnp.float32)
        s = q @ k_blk.T                                  # [bq, bk]
        k_pos = jk * bk + jax.lax.iota(jnp.int32, bk)
        mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + p @ v_blk
        return m_cur, l_cur, acc

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc := a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "scale", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool) -> jax.Array:
    """q,k,v: [B, H, S, D] (same head count — repeat KV upstream for GQA).

    Returns [B, H, S, D]. Sliding window = keys in (q - window, q].
    """
    b, h, s, d = q.shape
    assert k.shape == v.shape == (b, h, s, d)
    bq_, bk_ = min(bq, s), min(bk, s)
    assert s % bq_ == 0 and s % bk_ == 0
    if scale is None:
        scale = d ** -0.5

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq_, bk=bk_, seq=s, scale=scale,
                          causal=causal, window=window),
        grid=(b * h, s // bq_),
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, s, d), lambda bh, iq: (bh, 0, 0)),
            pl.BlockSpec((1, s, d), lambda bh, iq: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda bh, iq: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)
