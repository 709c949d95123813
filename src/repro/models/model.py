"""Model: init / train forward / prefill / decode over the period-scan.

Param layout: {"embed": ..., "blocks": {"p<i>": stacked-leaf pytrees with a
leading [n_groups] axis}, "final_norm": ..., "head": ...}. The same
structure holds the PartitionSpec tree (logical axes) and the KV/SSM cache
tree for decoding.

``convert_params_for_serving`` performs the paper's offline weight packing
(dense bf16 -> int8 or bit-packed planes) as a pure pytree transform usable
under jax.eval_shape (the dry-run builds packed ShapeDtypeStructs with it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.api.plan import PARAM_CLASS_NAMES as _CLASS_NAMES
from repro.core import bitpack, quantize as quant
from repro.dist.sharding import constraint
from repro.models import attention, layers as L, transformer as T


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def _stack_specs(spec):
    return jax.tree.map(lambda s: PS(None, *s), spec,
                        is_leaf=lambda x: isinstance(x, PS))


def init_params(key, cfg: T.ModelConfig, dtype=jnp.bfloat16):
    """Returns (params, specs). Usable under jax.eval_shape."""
    kemb, khead, kblocks = jax.random.split(key, 3)
    params, specs = {}, {}
    params["embed"], specs["embed"] = L.embed_init(kemb, cfg.vocab, cfg.d_model, dtype)
    params["final_norm"], specs["final_norm"] = L.norm_init(cfg.d_model, dtype)
    params["head"], specs["head"] = L.linear_init(khead, cfg.d_model, cfg.vocab,
                                                  "fsdp", "tp", dtype)
    blocks, bspecs = {}, {}
    for i, spec in enumerate(cfg.pattern):
        kp = jax.random.fold_in(kblocks, i)
        ps, ss = [], None
        for g in range(cfg.n_groups):
            p, ss = T.block_init(jax.random.fold_in(kp, g), cfg, spec, dtype)
            ps.append(p)
        blocks[f"p{i}"] = _stack_trees(ps)
        bspecs[f"p{i}"] = _stack_specs(ss)
    params["blocks"] = blocks
    specs["blocks"] = bspecs
    return params, specs


def _remat_policy(cfg: T.ModelConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def forward_train(params, cfg: T.ModelConfig, tokens, exec_cfg,
                  img_embeds=None):
    """tokens: [B, S] -> (logits [B, S, V], aux_loss scalar)."""
    b, s = tokens.shape
    x = L.embed_apply(params["embed"], tokens).astype(jnp.bfloat16)
    x = constraint(x, PS("dp", None, None))
    positions = jnp.arange(s, dtype=jnp.int32)

    def group_body(carry, group_params):
        x, aux = carry
        for i, spec in enumerate(cfg.pattern):
            x, a = T.block_apply_train(group_params[f"p{i}"], cfg, spec, x,
                                       positions, exec_cfg, img_embeds)
            aux = aux + a
        return (x, aux), None

    policy = _remat_policy(cfg)
    if policy is not None:
        group_body = jax.checkpoint(group_body, policy=policy,
                                    prevent_cse=False)
    (x, aux), _ = jax.lax.scan(group_body,
                               (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = L.rms_norm(x, params["final_norm"]["g"])
    logits = L.linear_apply(params["head"], x, exec_cfg, "lm_head")
    logits = constraint(logits, PS("dp", None, "tp"))
    return logits, aux


def loss_fn(params, cfg: T.ModelConfig, batch, exec_cfg):
    logits, aux = forward_train(params, cfg, batch["tokens"], exec_cfg,
                                batch.get("img_embeds"))
    labels = batch["labels"]
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - gold)
    return nll + aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: cache init + prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: T.ModelConfig, batch: int, max_seq: int):
    caches = {}
    for i, spec in enumerate(cfg.pattern):
        per = [T.block_cache_init(cfg, spec, batch, max_seq)
               for _ in range(cfg.n_groups)]
        caches[f"p{i}"] = _stack_trees(per)
    return caches


def cache_spec_tree(cfg: T.ModelConfig):
    out = {}
    for i, spec in enumerate(cfg.pattern):
        out[f"p{i}"] = _stack_specs(T.block_cache_specs(cfg, spec))
    return out


def prefill(params, cfg: T.ModelConfig, tokens, cache, exec_cfg,
            img_embeds=None):
    """Populate caches from a full prompt. Returns (last_logits, cache)."""
    b, s = tokens.shape
    x = L.embed_apply(params["embed"], tokens).astype(jnp.bfloat16)
    x = constraint(x, PS("dp", None, None))
    positions = jnp.arange(s, dtype=jnp.int32)

    def group_body(x, xs):
        group_params, group_cache = xs
        new_caches = {}
        for i, spec in enumerate(cfg.pattern):
            p, c = group_params[f"p{i}"], group_cache[f"p{i}"]
            if spec.kind == "mamba":
                h = L.rms_norm(x, p["ln1"]["g"])
                from repro.models import ssm as ssm_mod
                xi, c_new = ssm_mod.apply_prefill(p["mix"], cfg.ssm, h,
                                                  exec_cfg, c)
                x = x + xi
            elif spec.kind == "cross":
                h = L.rms_norm(x, p["ln1"]["g"])
                c_new = attention.init_cross_cache(p["mix"], cfg.attn_cfg(spec),
                                                   img_embeds, exec_cfg)
                mix = attention.apply_train(p["mix"], cfg.attn_cfg(spec), h,
                                            positions, exec_cfg, kv_x=img_embeds)
                x = x + mix
            else:
                h = L.rms_norm(x, p["ln1"]["g"])
                mix, c_new = attention.apply_prefill(
                    p["mix"], cfg.attn_cfg(spec), h, positions, exec_cfg, c)
                x = x + mix
            if spec.ffn != "none":
                h = L.rms_norm(x, p["ln2"]["g"])
                if spec.ffn == "moe":
                    from repro.models import moe as moe_mod
                    f, _ = moe_mod.apply(p["ffn"], cfg.moe, h, exec_cfg)
                else:
                    f = T.ffn_apply(p["ffn"], h, cfg.activation, exec_cfg)
                x = x + f
            x = constraint(x, PS("dp", None, None))
            new_caches[f"p{i}"] = c_new
        return x, new_caches

    policy = _remat_policy(cfg)
    if policy is not None:
        group_body = jax.checkpoint(group_body, policy=policy, prevent_cse=False)
    x, caches = jax.lax.scan(group_body, x, (params["blocks"], cache))
    x = L.rms_norm(x[:, -1:], params["final_norm"]["g"])
    logits = L.linear_apply(params["head"], x, exec_cfg, "lm_head")
    return logits, caches


def decode_step(params, cfg: T.ModelConfig, token, pos, cache, exec_cfg):
    """One decode step. token: [B] int32; pos: scalar int32 absolute pos,
    or a [B] int32 vector of per-row positions (continuous batching —
    each row ropes, writes its cache slot, and masks at its own pos).

    The stacked cache is the layer loop's carry, not a scanned input
    rebuilt as an output stack: each layer writes its new token into it
    and reads its K/V from it in place, so a donated cache buffer is
    updated where it lies. Only the parameters are scanned.

    Returns (logits [B, V], new_cache)."""
    x = L.embed_apply(params["embed"], token[:, None]).astype(jnp.bfloat16)
    x = constraint(x, PS("dp", None, None))

    def group_body(carry, xs):
        x, cache = carry
        group_params, g = xs
        cache = dict(cache)
        for i, spec in enumerate(cfg.pattern):
            x, cache[f"p{i}"] = T.block_apply_decode(
                group_params[f"p{i}"], cfg, spec, x, pos, exec_cfg,
                cache[f"p{i}"], g)
        return (x, cache), None

    (x, cache), _ = jax.lax.scan(
        group_body, (x, cache),
        (params["blocks"], jnp.arange(cfg.n_groups, dtype=jnp.int32)))
    x = L.rms_norm(x, params["final_norm"]["g"])
    logits = L.linear_apply(params["head"], x[:, 0], exec_cfg, "lm_head")
    logits = constraint(logits, PS("dp", "tp"))
    return logits, cache


# ---------------------------------------------------------------------------
# Offline weight packing (the paper's bit-interleaved storage step)
# ---------------------------------------------------------------------------

_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


_SKIP_LINEARS = ("router", "conv")  # tiny/accuracy-critical or depthwise conv

# _CLASS_NAMES (param-tree key -> apply-time layer-class name used by
# PrecisionPolicy) is imported from repro.api.plan — the canonical table
# lives next to the plan builder.


def _policy_key(path) -> str:
    if path and path[-1] in _CLASS_NAMES:
        return _CLASS_NAMES[path[-1]]
    return "/".join(path)


def _convert_tree(params, specs, policy, mode: str, root=()):
    """Walk an UNSTACKED tree converting every 2-D linear + 3-D expert."""
    def walk(p, s, path):
        if isinstance(p, dict):
            if ("w" in p and getattr(p["w"], "ndim", 0) == 2
                    and (not path or path[-1] not in _SKIP_LINEARS)):
                prec = policy.lookup(_policy_key(path))
                return L.convert_linear_for_serving(p, s, prec, mode)
            newp, news = {}, {}
            for k in p:
                if k in _EXPERT_KEYS and getattr(p[k], "ndim", 0) == 3:
                    prec = policy.lookup("/".join(path + (k,)))
                    newp[k], news[k] = _convert_expert(p[k], s[k], prec, mode)
                else:
                    newp[k], news[k] = walk(p[k], s[k], path + (k,))
            return newp, news
        return p, s

    return walk(params, specs, tuple(root))


def convert_params_for_serving(params, specs, policy, mode: str):
    """Pytree transform: every linear's w -> quantized/packed representation.

    mode: "serve_int8" (LM_8b) or "serve_packed" (bit-serial planes).
    Embeddings and norms stay bf16 (lookup tables / tiny). Expert tensors
    [E, d, f] are packed per-expert. Stacked block params (leading
    [n_groups] scan axis) are unstacked, converted with the same 2-D
    logic, and restacked. Pure jax -> works under eval_shape.
    """
    out_p, out_s = {}, {}
    for k in params:
        if k == "blocks":
            bp, bs = {}, {}
            for pk, stacked in params[k].items():
                n_groups = jax.tree.leaves(stacked)[0].shape[0]
                per_p, per_s = [], None
                for g in range(n_groups):
                    slice_g = jax.tree.map(lambda a: a[g], stacked)
                    # strip the leading stack axis from the spec tree
                    spec_g = jax.tree.map(lambda sp: PS(*sp[1:]), specs[k][pk],
                                          is_leaf=lambda x: isinstance(x, PS))
                    cp, cs = _convert_tree(slice_g, spec_g, policy, mode)
                    per_p.append(cp)
                    per_s = cs
                bp[pk] = _stack_trees(per_p)
                bs[pk] = _stack_specs(per_s)
            out_p[k], out_s[k] = bp, bs
        else:
            out_p[k], out_s[k] = _convert_tree(params[k], specs[k], policy,
                                               mode, root=(k,))
    return out_p, out_s


def convert_specs_for_serving(param_structs, specs, mode: str):
    """Spec-tree counterpart of convert_params_for_serving: same routing
    (driven by the struct tree's ndim/keys), no array math — usable with
    ShapeDtypeStruct trees for the dry-run's in_shardings."""
    def walk(p, s, path):
        if isinstance(p, dict):
            if ("w" in p and getattr(p["w"], "ndim", 0) == 2
                    and (not path or path[-1] not in _SKIP_LINEARS)):
                return L.convert_linear_specs(s, mode)
            news = {}
            for k in p:
                if k in _EXPERT_KEYS and getattr(p[k], "ndim", 0) == 3:
                    news[k] = _EXPERT_SPEC_CONVERTERS[mode](
                        s[k][0], s[k][1], s[k][2])
                else:
                    news[k] = walk(p[k], s[k], path + (k,))
            return news
        return s

    out = {}
    for k in param_structs:
        if k == "blocks":
            bs = {}
            for pk, stacked in param_structs[k].items():
                slice_g = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stacked)
                spec_g = jax.tree.map(lambda sp: PS(*sp[1:]), specs[k][pk],
                                      is_leaf=lambda x: isinstance(x, PS))
                bs[pk] = _stack_specs(walk(slice_g, spec_g, ()))
            out[k] = bs
        else:
            out[k] = walk(param_structs[k], specs[k], ())
    return out


def convert_structs_for_serving(param_structs, specs, policy, mode: str):
    """(struct tree, spec tree) of the packed representation, allocation-free:
    params via eval_shape over the real conversion, specs via the parallel
    spec walker. The dry-run's serving cells are built from this."""
    new_p = jax.eval_shape(
        lambda p: convert_params_for_serving(p, specs, policy, mode)[0],
        param_structs)
    new_s = convert_specs_for_serving(param_structs, specs, mode)
    return new_p, new_s


def _convert_expert_int8(wf, prec):
    scale = quant.compute_scale(wf, 8, axis=(1, 2))
    wq = jnp.clip(jnp.round(wf / scale), -128, 127).astype(jnp.int8)
    return {"wq": wq, "scale": scale.reshape(-1)}


def _convert_expert_packed(wf, prec):
    bits = prec.w_bits
    scale = quant.compute_scale(wf, bits, axis=(1, 2))
    wq = jnp.clip(jnp.round(wf / scale), quant.qmin(bits),
                  quant.qmax(bits)).astype(jnp.int32)
    packed = jax.vmap(lambda m: bitpack.pack_weights(m, bits))(wq)
    return {"w_packed": packed, "scale": scale.reshape(-1)}


_EXPERT_CONVERTERS = {"serve_int8": _convert_expert_int8,
                      "serve_packed": _convert_expert_packed}

# Single source of truth for the per-expert packed PartitionSpecs — the
# param conversion and the spec-only walk both read this table.
_EXPERT_SPEC_CONVERTERS = {
    "serve_int8": lambda e_ax, in_ax, out_ax: {
        "wq": PS(e_ax, in_ax, out_ax), "scale": PS(e_ax)},
    "serve_packed": lambda e_ax, in_ax, out_ax: {
        "w_packed": PS(e_ax, None, in_ax, out_ax), "scale": PS(e_ax)},
}


def _convert_expert(w, spec, prec, mode):
    """w: [E, din, dout] -> per-expert quantized/packed."""
    try:
        converter = _EXPERT_CONVERTERS[mode]
    except KeyError:
        raise ValueError(mode) from None
    return (converter(w.astype(jnp.float32), prec),
            _EXPERT_SPEC_CONVERTERS[mode](spec[0], spec[1], spec[2]))
