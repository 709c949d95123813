"""Attention: GQA self-attention (full / sliding-window), cross-attention,
chunked-flash XLA path for long prefill, and decode over (ring) KV caches.

Sharding (logical axes): q heads over "tp"; KV projections are row-parallel
(input sharded over "tp", output replicated) whenever n_kv_heads doesn't
divide the tp axis — the standard KV-replication strategy for GQA with
tp > n_kv. Long-context decode shards the KV cache over "sp" on the
sequence dim (flash-decoding: GSPMD turns the softmax reductions into the
partial-max/partial-sum merges).

Loom integration: all four projections are LoomLinears; the KV cache may be
stored int8 with per-(head, position) scales — the paper's precision-
scaled memory applied to decode's dominant bandwidth consumer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.core import quantize as quant
from repro.dist.sharding import constraint
from repro.models import layers as L

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    rope_theta: float = 500000.0
    qk_norm: bool = False
    window: int | None = None          # sliding-window size (None = full)
    flash_vjp: bool = False            # memory-efficient custom backward
    kv_col_parallel: bool = False      # kv projections column-parallel
    decode_pin_seq: bool = False       # pin cache seq-sharding in decode
    mask_cache_update: bool = False    # where()-based shard-local cache write
    kv_replicated: bool = False        # kv projections replicated over tp
    attn_int8: bool = False            # integer QK/PV dots on the int8 cache
    block: int = 512                   # flash q/kv block size
    causal: bool = True
    cross: bool = False                # cross-attention (KV from encoder side)
    kv_cache_bits: int = 16            # 16 = bf16 cache; 8 = Loom int8 cache


def init(key, cfg: AttnConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    p, s = {}, {}
    p["wq"], s["wq"] = L.linear_init(ks[0], cfg.d_model, cfg.n_heads * cfg.d_head,
                                     "fsdp", "tp", dtype)
    # KV default: row-parallel (input sharded over tp, output replicated —
    # costs an activation all-reduce). kv_col_parallel instead shards the
    # (kv_head x d_head) output over tp; the later head-repeat reshard is a
    # small intra-group gather instead of a full all-reduce (see §Perf).
    kv_in, kv_out = ("fsdp", "tp") if cfg.kv_col_parallel else ("tp", "fsdp")
    if cfg.kv_replicated:
        # replicate the (small) kv projections over tp: redundant compute,
        # ZERO kv-projection collectives fwd AND bwd-dgrad (§Perf cell A)
        kv_in, kv_out = "fsdp", None
    p["wk"], s["wk"] = L.linear_init(ks[1], cfg.d_model, cfg.n_kv_heads * cfg.d_head,
                                     kv_in, kv_out, dtype)
    p["wv"], s["wv"] = L.linear_init(ks[2], cfg.d_model, cfg.n_kv_heads * cfg.d_head,
                                     kv_in, kv_out, dtype)
    p["wo"], s["wo"] = L.linear_init(ks[3], cfg.n_heads * cfg.d_head, cfg.d_model,
                                     "tp", "fsdp", dtype)
    if cfg.qk_norm:
        p["qnorm"], s["qnorm"] = L.norm_init(cfg.d_head, dtype)
        p["knorm"], s["knorm"] = L.norm_init(cfg.d_head, dtype)
    return p, s


def _project_qkv(p, cfg: AttnConfig, x, kv_x, positions, exec_cfg):
    b = x.shape[0]
    q = L.linear_apply(p["wq"], x, exec_cfg, "attn_q")
    q = q.reshape(*x.shape[:-1], cfg.n_heads, cfg.d_head)
    k = L.linear_apply(p["wk"], kv_x, exec_cfg, "attn_k")
    k = k.reshape(*kv_x.shape[:-1], cfg.n_kv_heads, cfg.d_head)
    v = L.linear_apply(p["wv"], kv_x, exec_cfg, "attn_v")
    v = v.reshape(*kv_x.shape[:-1], cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["qnorm"]["g"])
        k = L.rms_norm(k, p["knorm"]["g"])
    if not cfg.cross:
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    q = constraint(q, PS("dp", None, "tp", None))
    return q, k, v


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def chunked_attention(q, k, v, *, causal=True, window=None, bq=512, bk=512,
                      q_offset=0, return_stats=False):
    """Pure-XLA flash attention (scan over q and kv blocks, online softmax).

    q: [B, S, H, D]; k/v: [B, Sk, H, D] (same head count). For sliding-
    window layers each q block attends only its (window + bq)-wide KV span
    (dynamic_slice) — true sub-quadratic compute, matching SWA's cost.
    q_offset: absolute position of q[0] (prefill continuation).
    return_stats: also return the logsumexp rows [B, H, S] (flash-VJP).
    """
    b, s, h, d = q.shape
    sk = k.shape[1]
    scale = d ** -0.5
    bq = min(bq, s)
    bk = min(bk, sk)
    assert s % bq == 0 and sk % bk == 0
    qb = q.reshape(b, s // bq, bq, h, d).transpose(1, 0, 3, 2, 4)  # [nq,B,H,bq,D]
    kt = k.transpose(0, 2, 1, 3)                                   # [B,H,Sk,D]
    vt = v.transpose(0, 2, 1, 3)

    k_pos_all = jnp.arange(sk)

    def q_block(carry, inp):
        iq, qblk = inp
        qblk = qblk.astype(jnp.float32) * scale
        q_pos = q_offset + iq * bq + jnp.arange(bq)

        if window is not None and sk > window + bq:
            span = window + bq
            # round span up to a multiple of bk for uniform inner blocks
            span = -(-span // bk) * bk
            start = jnp.clip(q_offset + iq * bq - window + 1, 0, sk - span)
            k_sp = jax.lax.dynamic_slice_in_dim(kt, start, span, axis=2)
            v_sp = jax.lax.dynamic_slice_in_dim(vt, start, span, axis=2)
            k_pos = start + jnp.arange(span)
        else:
            k_sp, v_sp, k_pos = kt, vt, k_pos_all
        nkb = k_sp.shape[2] // bk

        def kv_block(acc, jk):
            m_prev, l_prev, o_prev = acc
            ks_ = jax.lax.dynamic_slice_in_dim(k_sp, jk * bk, bk, axis=2)
            vs_ = jax.lax.dynamic_slice_in_dim(v_sp, jk * bk, bk, axis=2)
            kp = jax.lax.dynamic_slice_in_dim(k_pos, jk * bk, bk, axis=0)
            logits = jnp.einsum("bhqd,bhkd->bhqk", qblk,
                                ks_.astype(jnp.float32))
            mask = jnp.ones((bq, bk), dtype=bool)
            if causal:
                mask &= kp[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= kp[None, :] > q_pos[:, None] - window
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            m_cur = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
            p_ = jnp.exp(logits - m_cur[..., None])
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = l_prev * alpha + jnp.sum(p_, axis=-1)
            o_cur = o_prev * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p_, vs_.astype(jnp.float32))
            return (m_cur, l_cur, o_cur), None

        m0 = jnp.full((b, h, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, bq), jnp.float32)
        o0 = jnp.zeros((b, h, bq, d), jnp.float32)
        (m, l, o), _ = jax.lax.scan(kv_block, (m0, l0, o0), jnp.arange(nkb))
        out = o / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return carry, (out.astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_block, None, (jnp.arange(s // bq), qb))
    # outs: [nq, B, H, bq, D] -> [B, S, H, D]
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, s, h, d)
    if return_stats:
        # lses: [nq, B, H, bq] -> [B, H, S]
        return out, lses.transpose(1, 2, 0, 3).reshape(b, h, s)
    return out


# ---------------------------------------------------------------------------
# Flash VJP: memory-efficient backward (recompute p blockwise from saved
# logsumexp rows instead of letting autodiff save every [bq, bk] f32
# probability/mask block into scan carries — the O(S^2) bwd buffers are
# the dominant HBM term of the baseline train cells).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_xla(q, k, v, causal=True, window=None, bq=512, bk=512):
    return chunked_attention(q, k, v, causal=causal, window=window,
                             bq=bq, bk=bk)


def _flash_fwd(q, k, v, causal, window, bq, bk):
    out, lse = chunked_attention(q, k, v, causal=causal, window=window,
                                 bq=bq, bk=bk, return_stats=True)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, bq, bk, res, dout):
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    sk = k.shape[1]
    scale = d ** -0.5
    bq_ = min(bq, s)
    bk_ = min(bk, sk)
    nq = s // bq_

    # Sliding-window layers: a q block's gradient only touches its
    # (window + bq)-wide KV span — loop that span, not all of sk. Without
    # this the bwd is O(S^2) even for SWA and regresses the gemma3/mixtral
    # train cells below their no-flash baseline.
    span = -(-min((window or sk) + bq_, sk) // bk_) * bk_
    use_span = window is not None and sk > span
    if not use_span:
        span = sk
    nkb = span // bk_

    # [B, H, S, D] layouts
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    dot_ = dout.transpose(0, 2, 1, 3).astype(jnp.float32)
    ot = out.transpose(0, 2, 1, 3).astype(jnp.float32)
    delta = jnp.sum(dot_ * ot, axis=-1)                    # [B, H, S]

    def q_block(carry, iq):
        dk_acc, dv_acc = carry
        qi = jax.lax.dynamic_slice_in_dim(qt, iq * bq_, bq_, 2) * scale
        doi = jax.lax.dynamic_slice_in_dim(dot_, iq * bq_, bq_, 2)
        lsei = jax.lax.dynamic_slice_in_dim(lse, iq * bq_, bq_, 2)
        di = jax.lax.dynamic_slice_in_dim(delta, iq * bq_, bq_, 2)
        q_pos = iq * bq_ + jnp.arange(bq_)
        if use_span:
            start = jnp.clip(iq * bq_ - window + 1, 0, sk - span)
            kt_sp = jax.lax.dynamic_slice_in_dim(kt, start, span, 2)
            vt_sp = jax.lax.dynamic_slice_in_dim(vt, start, span, 2)
        else:
            start = 0
            kt_sp, vt_sp = kt, vt

        def kv_block(inner, jk):
            dq_i, dk_a, dv_a = inner
            kj = jax.lax.dynamic_slice_in_dim(kt_sp, jk * bk_, bk_, 2)
            vj = jax.lax.dynamic_slice_in_dim(vt_sp, jk * bk_, bk_, 2)
            k_pos = start + jk * bk_ + jnp.arange(bk_)
            logits = jnp.einsum("bhqd,bhkd->bhqk", qi, kj)
            p = jnp.exp(logits - lsei[..., None])          # [B,H,bq,bk]
            mask = jnp.ones((bq_, bk_), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            p = jnp.where(mask[None, None], p, 0.0)
            dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, doi)
            dp = jnp.einsum("bhqd,bhkd->bhqk", doi, vj)
            ds = p * (dp - di[..., None])
            dq_i = dq_i + jnp.einsum("bhqk,bhkd->bhqd", ds, kj) * scale
            dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qi)   # qi already scaled
            dk_a = jax.lax.dynamic_update_slice_in_dim(
                dk_a, jax.lax.dynamic_slice_in_dim(dk_a, jk * bk_, bk_, 2)
                + dk_j, jk * bk_, 2)
            dv_a = jax.lax.dynamic_update_slice_in_dim(
                dv_a, jax.lax.dynamic_slice_in_dim(dv_a, jk * bk_, bk_, 2)
                + dv_j, jk * bk_, 2)
            return (dq_i, dk_a, dv_a), None

        dq0 = jnp.zeros((b, h, bq_, d), jnp.float32)
        if use_span:
            dkl0 = jnp.zeros((b, h, span, d), jnp.float32)
            dvl0 = jnp.zeros((b, h, span, d), jnp.float32)
            (dq_i, dk_l, dv_l), _ = jax.lax.scan(
                kv_block, (dq0, dkl0, dvl0), jnp.arange(nkb))
            dk_acc = jax.lax.dynamic_update_slice_in_dim(
                dk_acc,
                jax.lax.dynamic_slice_in_dim(dk_acc, start, span, 2) + dk_l,
                start, 2)
            dv_acc = jax.lax.dynamic_update_slice_in_dim(
                dv_acc,
                jax.lax.dynamic_slice_in_dim(dv_acc, start, span, 2) + dv_l,
                start, 2)
        else:
            (dq_i, dk_acc, dv_acc), _ = jax.lax.scan(
                kv_block, (dq0, dk_acc, dv_acc), jnp.arange(nkb))
        return (dk_acc, dv_acc), dq_i

    dk0 = jnp.zeros((b, h, sk, d), jnp.float32)
    dv0 = jnp.zeros((b, h, sk, d), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(q_block, (dk0, dv0), jnp.arange(nq))
    # dqs: [nq, B, H, bq, D] -> [B, S, H, D]
    dq = dqs.transpose(1, 0, 3, 2, 4).reshape(b, s, h, d)
    return (dq.astype(q.dtype),
            dk.transpose(0, 2, 1, 3).astype(k.dtype),
            dv.transpose(0, 2, 1, 3).astype(v.dtype))


flash_attention_xla.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# KV cache (decode). Stored [B, S_cache, H_kv, D]; ring buffer when the
# layer is sliding-window (S_cache = window). Optional Loom int8 storage
# with per-(position, head) scales — halves decode's dominant HBM traffic.
# ---------------------------------------------------------------------------

def init_cache(cfg: AttnConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    s_cache = min(cfg.window or max_seq, max_seq)
    kv_dtype = jnp.int8 if cfg.kv_cache_bits == 8 else dtype
    shape = (batch, s_cache, cfg.n_kv_heads, cfg.d_head)
    cache = {
        "k": jnp.zeros(shape, kv_dtype),
        "v": jnp.zeros(shape, kv_dtype),
        # per-ROW slot positions: batched serving decodes rows at different
        # absolute positions, so the causal mask must be per-slot
        "slot_pos": jnp.full((batch, s_cache), -1, jnp.int32),
    }
    if cfg.kv_cache_bits == 8:
        cache["k_scale"] = jnp.zeros((batch, s_cache, cfg.n_kv_heads), jnp.float32)
        cache["v_scale"] = jnp.zeros((batch, s_cache, cfg.n_kv_heads), jnp.float32)
    return cache


def cache_specs(cfg: AttnConfig):
    """Sequence-sharded ("sp") KV cache — flash-decoding layout."""
    sp = {"k": PS("dp", "sp", None, None), "v": PS("dp", "sp", None, None),
          "slot_pos": PS("dp", "sp")}
    if cfg.kv_cache_bits == 8:
        sp["k_scale"] = PS("dp", "sp", None)
        sp["v_scale"] = PS("dp", "sp", None)
    return sp


def _quant_kv(x):  # [B, 1, H, D] -> int8 + per-head scale
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-20)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -128, 127)
    return xq.astype(jnp.int8), s


def _mask_update(buf, new, slot):
    """Elementwise one-slot write: buf [B,S,...], new [B,1,...], slot scalar.

    dynamic-update-slice on a seq-SHARDED cache cannot be partitioned —
    GSPMD falls back to replicate-update-reshard, which reads/writes the
    FULL cache on every device every step (the dominant decode cost in the
    baseline). A where() against the slot index is elementwise in the
    sharded dim, so every shard touches only its local slice."""
    s_cache = buf.shape[1]
    hit = (jnp.arange(s_cache) == slot).reshape(
        (1, s_cache) + (1,) * (buf.ndim - 2))
    return jnp.where(hit, new.astype(buf.dtype), buf)


def _row_update(buf, new, slot):
    """Per-ROW one-slot write: buf [B,S,...], new [B,1,...], slot [B].

    The where() counterpart of the in-place scatter for seq-sharded
    caches: elementwise against a [B,S] hit mask, shard-local like
    _mask_update."""
    s_cache = buf.shape[1]
    hit = (jnp.arange(s_cache)[None, :] == slot[:, None]).reshape(
        buf.shape[:2] + (1,) * (buf.ndim - 2))
    return jnp.where(hit, new.astype(buf.dtype), buf)


def _write_slot_pos(sp, pos, slot):
    """Record ``pos`` at ``slot`` in the slot_pos map (1-D or [B,S])."""
    s_cache = sp.shape[-1]
    if jnp.ndim(slot) == 1:                    # per-row slots, sp is [B,S]
        hit = jnp.arange(s_cache)[None, :] == slot[:, None]
        return jnp.where(hit, pos[:, None].astype(jnp.int32), sp)
    hit = jnp.arange(s_cache) == slot
    if sp.ndim == 2:
        hit = hit[None, :]
    return jnp.where(hit, jnp.asarray(pos, jnp.int32), sp)


def layer_cache(cache: dict, layer) -> dict:
    """Layer ``layer`` of a stacked cache (leaves [L, B, ...]). Each leaf
    is a dynamic slice, which XLA fuses into the op that reads it."""
    return {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for n, a in cache.items()}


def cache_update(cache: dict, cfg: AttnConfig, k_new, v_new, pos, layer):
    """Insert one token's K/V at absolute position ``pos`` (ring for SWA)
    into layer ``layer`` of the stacked cache (leaves [L, B, S_cache, ...]).

    ``pos`` is a scalar (whole batch at one position) or a [B] vector
    (continuous batching: each row at its own position). Each row's K, V,
    int8 scales and position go in at (layer, row, pos % S_cache) with
    one scatter per leaf, which XLA applies in place to the decode loop's
    carried buffer. ``cfg.mask_cache_update`` keeps the where() write
    over the layer instead: a scatter or dynamic_update_slice on a
    seq-sharded cache cannot be partitioned and replicates it."""
    pos = jnp.asarray(pos, jnp.int32)
    slot = pos % cache["k"].shape[2]
    new = {"k": k_new, "v": v_new}                     # [B, 1, ...]
    if cfg.kv_cache_bits == 8:
        new["k"], new["k_scale"] = _quant_kv(k_new)
        new["v"], new["v_scale"] = _quant_kv(v_new)
    cache = dict(cache)
    if cfg.mask_cache_update:
        upd = _row_update if pos.ndim == 1 else _mask_update
        old = layer_cache(cache, layer)
        written = {n: upd(old[n], a, slot) for n, a in new.items()}
        written["slot_pos"] = _write_slot_pos(old["slot_pos"], pos, slot)
        for n, a in written.items():
            cache[n] = jax.lax.dynamic_update_index_in_dim(cache[n], a,
                                                           layer, 0)
        return cache
    b = k_new.shape[0]
    rows = jnp.arange(b)
    slots = jnp.broadcast_to(slot, (b,))
    new = {n: a[:, 0] for n, a in new.items()}
    new["slot_pos"] = jnp.broadcast_to(pos, (b,))
    for n, a in new.items():
        cache[n] = cache[n].at[layer, rows, slots].set(
            a.astype(cache[n].dtype), indices_are_sorted=True,
            unique_indices=True)
    return cache


def _valid_slots(cache: dict, cfg: AttnConfig, pos):
    """Causal validity mask over cache slots, shape [B-or-1, S].

    Accepts scalar or [B] ``pos`` and 1-D (legacy) or [B,S] slot_pos —
    each row masks against ITS OWN decode position."""
    sp = cache["slot_pos"]
    if sp.ndim == 1:
        sp = sp[None, :]
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = pos[:, None] if pos.ndim == 1 else pos
    valid = (sp >= 0) & (sp <= pos_b)
    if cfg.window is not None:
        valid &= sp > pos_b - cfg.window
    return valid


def decode_attend(q, cache: dict, cfg: AttnConfig, pos):
    """q: [B, 1, Hq, D] against one layer's cache; returns [B, 1, Hq, D].

    The queries are grouped [B, G, R, D] over the G stored KV heads
    (R = Hq / G), so K and V are read as stored ([B, S, G, D]): no head
    repeat, no f32 or transposed copy of the cache. Products, sums,
    softmax and PV are f32 (a bf16 cache upcasts exactly); both
    contractions are multiply-and-sum, so XLA reduces them in fusions
    that read the cache and never rounds q to bf16 for the MXU. The
    softmax reductions run over the (possibly "sp"-sharded) cache seq
    axis; GSPMD lowers them to partial reductions + all-reduce — the
    flash-decoding merge.
    """
    if cfg.attn_int8 and cfg.kv_cache_bits == 8:
        return _decode_attend_gqa_int8(q, cache, cfg, pos)
    b, _, hq, d = q.shape
    g = cfg.n_kv_heads
    r = hq // g
    k, v = cache["k"], cache["v"]
    if cfg.kv_cache_bits == 8:
        k = k.astype(jnp.float32) * cache["k_scale"][..., None]
        v = v.astype(jnp.float32) * cache["v_scale"][..., None]
    if cfg.decode_pin_seq:
        # Flash-decoding sharding: without the pin GSPMD may prefer a
        # head-sharded read and replicate the whole seq-sharded cache
        # every step. Pinned, the contractions stay seq-local; only the
        # partial-softmax stats cross devices.
        k = constraint(k, PS("dp", "sp", None, None))
        v = constraint(v, PS("dp", "sp", None, None))
    qg = q.reshape(b, 1, g, r, d).astype(jnp.float32) * d ** -0.5
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    # One multiply-and-sum per query head of a group: each reads K as
    # stored and upcasts it inside its reduction. One sum over K
    # broadcast across the R heads makes XLA write the f32 K out first.
    # One variadic reduce of all R reads K once, but on a TPU v5e it
    # compiled ~10x slower and the qwen3-1.7b chat step took 112 ms
    # against 58 ms with these sums.
    logits = jnp.stack([jnp.sum(qg[:, :, :, i] * kf, axis=-1)
                        for i in range(r)], axis=-1)   # [B, S, G, R]
    if cfg.decode_pin_seq:
        logits = constraint(logits, PS("dp", "sp", None, None))
    valid = _valid_slots(cache, cfg, pos)
    logits = jnp.where(valid[:, :, None, None], logits, NEG_INF)
    p_ = jax.nn.softmax(logits, axis=1)
    out = jnp.stack([jnp.sum(p_[:, :, :, i, None] * vf, axis=1)
                     for i in range(r)], axis=2)       # [B, G, R, D]
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def _decode_attend_gqa_int8(q, cache, cfg: AttnConfig, pos):
    """Loom applied to attention compute: QK and PV as int8 x int8 -> int32
    MXU dots straight on the stored cache — the f32 dequantized cache copy
    (2-4x the cache bytes) never materializes. Scales fold into the logits
    (per-position k_scale) and the output (per-position v_scale via the
    weighted sum). Requires kv_cache_bits == 8."""
    b, _, hq, d = q.shape
    g = cfg.n_kv_heads
    r = hq // g
    kq = cache["k"].transpose(0, 2, 1, 3)              # [B,G,S,D] int8
    vq = cache["v"].transpose(0, 2, 1, 3)
    k_scale = cache["k_scale"].transpose(0, 2, 1)      # [B,G,S]
    v_scale = cache["v_scale"].transpose(0, 2, 1)
    if cfg.decode_pin_seq:
        kq = constraint(kq, PS("dp", None, "sp", None))
        vq = constraint(vq, PS("dp", None, "sp", None))
    # quantize q per (batch, head): int8 grid
    qf = q.reshape(b, g, r, d).astype(jnp.float32) * d ** -0.5
    q_scale = jnp.max(jnp.abs(qf), axis=-1, keepdims=True) / 127.0
    q_scale = jnp.maximum(q_scale, 1e-20)
    qi = jnp.clip(jnp.round(qf / q_scale), -127, 127).astype(jnp.int8)
    logits_i = jax.lax.dot_general(
        qi, kq, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32)              # [B,G,R,S]
    logits = logits_i.astype(jnp.float32) * q_scale         * k_scale[:, :, None, :]
    if cfg.decode_pin_seq:
        logits = constraint(logits, PS("dp", None, None, "sp"))
    valid = _valid_slots(cache, cfg, pos)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p_ = jax.nn.softmax(logits, axis=-1)
    # fold v_scale into p, then integer PV: p is [0,1] -> uint-ish int8 grid
    pv = p_ * v_scale[:, :, None, :]                   # [B,G,R,S]
    p_scale = jnp.max(pv, axis=-1, keepdims=True) / 127.0
    p_scale = jnp.maximum(p_scale, 1e-20)
    pi = jnp.clip(jnp.round(pv / p_scale), 0, 127).astype(jnp.int8)
    out_i = jax.lax.dot_general(
        pi, vq, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32)              # [B,G,R,D]
    out = out_i.astype(jnp.float32) * p_scale
    return out.reshape(b, 1, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Layer-level entry points
# ---------------------------------------------------------------------------

def apply_train(p, cfg: AttnConfig, x, positions, exec_cfg,
                kv_x=None) -> jax.Array:
    """Full-sequence forward (training / prefill). kv_x for cross-attn."""
    kv_src = kv_x if cfg.cross else x
    q, k, v = _project_qkv(p, cfg, x, kv_src, positions, exec_cfg)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if cfg.kv_col_parallel:
        k = constraint(k, PS("dp", None, "tp", None))
        v = constraint(v, PS("dp", None, "tp", None))
    causal = cfg.causal and not cfg.cross
    win = cfg.window if not cfg.cross else None
    # flash VJP pays when backward would otherwise save O(S^2) blocks —
    # i.e. full attention (or SWA with window >= seq). For short-window
    # layers the autodiff backward already only saves span-sized blocks,
    # and flash's span-accumulator merges cost MORE (measured: gemma3
    # local layers regress ~2x; see EXPERIMENTS §Perf fleet notes).
    use_flash = cfg.flash_vjp and (win is None or win >= x.shape[1])
    if use_flash:
        out = flash_attention_xla(q, k, v, causal, win, cfg.block, cfg.block)
    else:
        out = chunked_attention(q, k, v, causal=causal, window=win,
                                bq=cfg.block, bk=cfg.block)
    out = out.reshape(*x.shape[:-1], cfg.n_heads * cfg.d_head)
    return L.linear_apply(p["wo"], out, exec_cfg, "attn_o")


def apply_prefill(p, cfg: AttnConfig, x, positions, exec_cfg, cache):
    """Prefill: full forward + populate the cache with the last S_cache kv."""
    q, k, v = _project_qkv(p, cfg, x, x, positions, exec_cfg)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    out = chunked_attention(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                            causal=cfg.causal, window=cfg.window)
    out = out.reshape(*x.shape[:-1], cfg.n_heads * cfg.d_head)
    s = x.shape[1]
    s_cache = cache["k"].shape[1]
    take = min(s, s_cache)
    k_tail = k[:, s - take:, :, :]
    v_tail = v[:, s - take:, :, :]
    pos_tail = positions[s - take:] if positions.ndim == 1 else positions[0, s - take:]
    slots = pos_tail % s_cache
    cache = dict(cache)
    if cfg.kv_cache_bits == 8:
        kq, ks = _quant_kv(k_tail)
        vq, vs = _quant_kv(v_tail)
        cache["k"] = cache["k"].at[:, slots].set(kq)
        cache["v"] = cache["v"].at[:, slots].set(vq)
        cache["k_scale"] = cache["k_scale"].at[:, slots].set(ks)
        cache["v_scale"] = cache["v_scale"].at[:, slots].set(vs)
    else:
        cache["k"] = cache["k"].at[:, slots].set(k_tail.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[:, slots].set(v_tail.astype(cache["v"].dtype))
    cache["slot_pos"] = cache["slot_pos"].at[:, slots].set(
        pos_tail.astype(jnp.int32))
    return L.linear_apply(p["wo"], out, exec_cfg, "attn_o"), cache


def apply_decode(p, cfg: AttnConfig, x, pos, exec_cfg, cache, layer):
    """One-token decode. x: [B, 1, d]. Returns (out [B,1,d], cache).

    ``cache`` is the stacked cache of every layer at this pattern
    position (leaves [L, B, ...]); this layer is ``layer``. ``pos`` is a
    scalar (whole batch at one position) or a [B] vector (continuous
    batching: per-row positions for rope, cache write, and causal
    masking)."""
    pos = jnp.asarray(pos, jnp.int32)
    # [1] or [B,1]: both broadcast per-row in rope
    positions = pos[None] if pos.ndim == 0 else pos[:, None]
    q = L.linear_apply(p["wq"], x, exec_cfg, "attn_q")
    q = q.reshape(x.shape[0], 1, cfg.n_heads, cfg.d_head)
    if cfg.cross:
        # cross KV precomputed at prefill and held in the cache
        if cfg.qk_norm:
            q = L.rms_norm(q, p["qnorm"]["g"])
        out = decode_attend(q, layer_cache(cache, layer), cfg, pos)
        out = out.reshape(*x.shape[:-1], cfg.n_heads * cfg.d_head)
        return L.linear_apply(p["wo"], out, exec_cfg, "attn_o"), cache
    k = L.linear_apply(p["wk"], x, exec_cfg, "attn_k")
    k = k.reshape(x.shape[0], 1, cfg.n_kv_heads, cfg.d_head)
    v = L.linear_apply(p["wv"], x, exec_cfg, "attn_v")
    v = v.reshape(x.shape[0], 1, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["qnorm"]["g"])
        k = L.rms_norm(k, p["knorm"]["g"])
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    cache = cache_update(cache, cfg, k, v, pos, layer)
    out = decode_attend(q, layer_cache(cache, layer), cfg, pos)
    out = out.reshape(*x.shape[:-1], cfg.n_heads * cfg.d_head)
    return L.linear_apply(p["wo"], out, exec_cfg, "attn_o"), cache


def init_cross_cache(p, cfg: AttnConfig, enc: jax.Array, exec_cfg):
    """Precompute cross-attention KV from encoder/image embeddings."""
    b, n, _ = enc.shape
    k = L.linear_apply(p["wk"], enc, exec_cfg, "attn_k").reshape(
        b, n, cfg.n_kv_heads, cfg.d_head)
    v = L.linear_apply(p["wv"], enc, exec_cfg, "attn_v").reshape(
        b, n, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        k = L.rms_norm(k, p["knorm"]["g"])
    cache = {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16),
             "slot_pos": jnp.zeros((b, n), jnp.int32)}
    return cache
