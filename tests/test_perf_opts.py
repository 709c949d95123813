"""Correctness of the §Perf optimization paths: every hillclimb toggle must
be numerically equivalent (or within quantization tolerance) to the
baseline it replaces — speedups that break the model don't count."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as loom
from repro import configs
from repro.models import attention as A, model as M

jax.config.update("jax_platform_name", "cpu")


def _ref_attn(q, k, v, causal=True, window=None):
    b, s, h, d = q.shape
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32) * d ** -0.5
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt)
    qp, kp = jnp.arange(s), jnp.arange(k.shape[1])
    mask = jnp.ones((s, k.shape[1]), bool)
    if causal:
        mask &= kp[None, :] <= qp[:, None]
    if window is not None:
        mask &= kp[None, :] > qp[:, None] - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vt).transpose(0, 2, 1, 3) \
        .astype(q.dtype)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_flash_vjp_matches_autodiff(causal, window):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 128, 4, 16)), jnp.float32)
               for _ in range(3))

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(_ref_attn(q, k, v, causal, window)))

    def f_flash(q, k, v):
        return jnp.sum(jnp.sin(
            A.flash_attention_xla(q, k, v, causal, window, 32, 32)))

    o_ref = _ref_attn(q, k, v, causal, window)
    o_fl = A.flash_attention_xla(q, k, v, causal, window, 32, 32)
    np.testing.assert_allclose(np.asarray(o_fl), np.asarray(o_ref),
                               atol=2e-5)
    g_ref = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    g_fl = jax.grad(f_flash, (0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=3e-5)


def _mk_attn_cfg(n_kv_heads=2, **kw):
    return A.AttnConfig(d_model=64, n_heads=8, n_kv_heads=n_kv_heads,
                        d_head=16, **kw)


def _stacked(cache, n_layers=1):
    """A one-layer cache stacked as decode carries it, [L, B, ...]."""
    return jax.tree.map(lambda a: jnp.stack([a] * n_layers), cache)


def _repeat_decode_ref(q, cache, cfg, pos):
    """Decode attention as it was before the grouped read: K and V
    repeated to every query head, upcast to f32, transposed [B, H, S, D];
    the mask written out from slot_pos."""
    b, _, hq, d = q.shape
    n_rep = hq // cfg.n_kv_heads
    kh = jnp.repeat(cache["k"], n_rep, axis=2).transpose(0, 2, 1, 3)
    vh = jnp.repeat(cache["v"], n_rep, axis=2).transpose(0, 2, 1, 3)
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32) * d ** -0.5
    hi = jax.lax.Precision.HIGHEST
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kh.astype(jnp.float32),
                        precision=hi)
    sp = jnp.broadcast_to(cache["slot_pos"], (b, cache["k"].shape[1]))
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]
    valid = (sp >= 0) & (sp <= pos_b)
    if cfg.window is not None:
        valid &= sp > pos_b - cfg.window
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    p_ = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p_, vh.astype(jnp.float32),
                     precision=hi)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _filled_cache(cfg, pos, s_cache, seed):
    """Random bf16 K/V; row b holds positions 0..pos[b] in ring slots."""
    rng = np.random.default_rng(seed)
    b = len(pos)
    cache = A.init_cache(cfg, b, s_cache)
    sp = np.full((b, cache["k"].shape[1]), -1, np.int32)
    for r, p in enumerate(pos):
        for t in range(max(0, p + 1 - sp.shape[1]), p + 1):
            sp[r, t % sp.shape[1]] = t
    return {"k": jnp.asarray(rng.normal(size=cache["k"].shape), jnp.bfloat16),
            "v": jnp.asarray(rng.normal(size=cache["v"].shape), jnp.bfloat16),
            "slot_pos": jnp.asarray(sp)}


@pytest.mark.parametrize("window", [None, 16], ids=["full", "ring"])
@pytest.mark.parametrize("per_row", [True, False], ids=["per_row", "scalar"])
@pytest.mark.parametrize("n_kv_heads", [8, 4], ids=["R1", "R2"])
def test_grouped_decode_equals_repeat_decode(window, per_row, n_kv_heads):
    """The grouped read of the stored cache == the repeat-and-f32 math,
    per-row and scalar pos, full and sliding-window ring caches, R = 1
    (plain MHA) and 2. On the sliding-window ring the rows past 16
    positions have wrapped, so their valid slots are out of order."""
    cfg = _mk_attn_cfg(n_kv_heads=n_kv_heads, window=window)
    pos = [39, 5, 63, 20] if per_row else [39] * 4
    cache = _filled_cache(cfg, pos, 64, seed=sum(pos) + n_kv_heads)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(4, 1, 8, 16)), jnp.bfloat16)
    p = jnp.asarray(pos if per_row else pos[0], jnp.int32)
    got = jax.jit(A.decode_attend, static_argnums=2)(q, cache, cfg, p)
    want = _repeat_decode_ref(q, cache, cfg, p)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    # the same f32 math before the final bf16 rounding
    got32 = A.decode_attend(q.astype(jnp.float32), cache, cfg, p)
    want32 = _repeat_decode_ref(q.astype(jnp.float32), cache, cfg, p)
    np.testing.assert_allclose(np.asarray(got32), np.asarray(want32),
                               rtol=1e-5, atol=1e-6)


def _row_write_ref(cache, k_new, v_new, pos):
    """The where() write over a whole one-layer cache, as decode did it:
    each row's token at its own slot pos % S."""
    b, s_cache = cache["slot_pos"].shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    hit = jnp.arange(s_cache)[None, :] == (pos % s_cache)[:, None]
    out = dict(cache)
    for n, new in (("k", k_new), ("v", v_new)):
        out[n] = jnp.where(hit[:, :, None, None], new.astype(out[n].dtype),
                           out[n])
    out["slot_pos"] = jnp.where(hit, pos[:, None], out["slot_pos"])
    return out


@pytest.mark.parametrize("window", [None, 8], ids=["full", "ring"])
@pytest.mark.parametrize("per_row", [True, False], ids=["per_row", "scalar"])
def test_in_place_write_equals_row_write(window, per_row):
    """The scatter into one layer of the stacked cache writes exactly the
    old where() write's bytes there and leaves the other layers alone,
    through a ring that wraps."""
    cfg = _mk_attn_cfg(window=window)
    rng = np.random.default_rng(6)
    one = A.init_cache(cfg, 3, 32)
    stack = _stacked(one, 3)
    for step in range(13):
        kn = jnp.asarray(rng.normal(size=(3, 1, 2, 16)), jnp.bfloat16)
        vn = jnp.asarray(rng.normal(size=(3, 1, 2, 16)), jnp.bfloat16)
        pos = (jnp.asarray([step, step + 5, 2 * step], jnp.int32) if per_row
               else jnp.asarray(step, jnp.int32))
        one = _row_write_ref(one, kn, vn, pos)
        stack = A.cache_update(stack, cfg, kn, vn, pos, 1)
    for key in one:
        np.testing.assert_array_equal(np.asarray(stack[key][1]),
                                      np.asarray(one[key]), err_msg=key)
        for other in (0, 2):
            np.testing.assert_array_equal(
                np.asarray(stack[key][other]),
                np.asarray(A.init_cache(cfg, 3, 32)[key]), err_msg=key)


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_mask_cache_update_equals_dus(kv_bits):
    """where()-based cache writes == the in-place scatter writes."""
    base = _mk_attn_cfg(kv_cache_bits=kv_bits)
    masked = _mk_attn_cfg(kv_cache_bits=kv_bits, mask_cache_update=True)
    rng = np.random.default_rng(3)
    c1 = _stacked(A.init_cache(base, 2, 32), 2)
    c2 = jax.tree.map(lambda x: x, c1)
    for step in range(5):
        kn = jnp.asarray(rng.normal(size=(2, 1, 2, 16)), jnp.bfloat16)
        vn = jnp.asarray(rng.normal(size=(2, 1, 2, 16)), jnp.bfloat16)
        pos = jnp.asarray(step, jnp.int32)
        c1 = A.cache_update(c1, base, kn, vn, pos, 1)
        c2 = A.cache_update(c2, masked, kn, vn, pos, 1)
    for key in c1:
        np.testing.assert_allclose(
            np.asarray(c1[key], np.float32), np.asarray(c2[key], np.float32),
            atol=0, rtol=0, err_msg=key)


def test_ring_cache_mask_update_wraps():
    """SWA ring cache: mask update wraps at window size like the scatter."""
    base = _mk_attn_cfg(window=8)
    masked = _mk_attn_cfg(window=8, mask_cache_update=True)
    rng = np.random.default_rng(4)
    c1 = _stacked(A.init_cache(base, 1, 64))
    c2 = jax.tree.map(lambda x: x, c1)
    assert c1["k"].shape[2] == 8   # ring sized to the window
    for step in range(13):         # wraps past the ring boundary
        kn = jnp.asarray(rng.normal(size=(1, 1, 2, 16)), jnp.bfloat16)
        vn = jnp.asarray(rng.normal(size=(1, 1, 2, 16)), jnp.bfloat16)
        c1 = A.cache_update(c1, base, kn, vn, jnp.asarray(step, jnp.int32), 0)
        c2 = A.cache_update(c2, masked, kn, vn, jnp.asarray(step, jnp.int32),
                            0)
    np.testing.assert_array_equal(np.asarray(c1["slot_pos"]),
                                  np.asarray(c2["slot_pos"]))
    np.testing.assert_allclose(np.asarray(c1["k"], np.float32),
                               np.asarray(c2["k"], np.float32))


def test_flash_vjp_full_model_grads_close():
    """End-to-end: qwen3 smoke with flash_vjp grads ~= baseline grads."""
    import dataclasses as dc
    cfg = configs.get("qwen3-1.7b", smoke=True)
    cfg_f = dc.replace(cfg, flash_vjp=True)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)),
                                   jnp.int32)}
    ec = loom.build_plan(cfg, mode="dense")

    g1 = jax.grad(lambda p: M.loss_fn(p, cfg, batch, ec)[0])(params)
    g2 = jax.grad(lambda p: M.loss_fn(p, cfg_f, batch, ec)[0])(params)
    l1, l2 = jax.tree.leaves(g1), jax.tree.leaves(g2)
    for a, b in zip(l1, l2):
        na = np.asarray(a, np.float32)
        nb = np.asarray(b, np.float32)
        denom = max(np.abs(na).max(), 1e-6)
        assert np.abs(na - nb).max() / denom < 0.05


def test_kv_col_parallel_same_math():
    """kv_col_parallel only changes sharding specs, not values."""
    import dataclasses as dc
    cfg = configs.get("qwen3-1.7b", smoke=True)
    cfg_k = dc.replace(cfg, kv_col_parallel=True)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    ec = loom.build_plan(cfg, mode="dense")
    o1, _ = M.forward_train(params, cfg, toks, ec)
    o2, _ = M.forward_train(params, cfg_k, toks, ec)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=1e-3)
