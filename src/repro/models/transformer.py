"""Decoder blocks + period-grouped scan composition.

Heterogeneous layer stacks (jamba's 1:7 mamba:attn interleave, gemma3's
5:1 local:global windows, llama-vision's cross-attn insertions) are
expressed as a repeating ``pattern`` of LayerSpecs with period p; params
are stacked [n_layers/p, ...] per pattern position and the model scans
over groups (HLO stays O(pattern), activations stay O(1) in depth).

Every block: pre-norm -> mixer (attention | mamba | cross-attn) ->
pre-norm -> FFN (dense | MoE), residual adds, all linears via LoomLinear.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.dist.sharding import constraint
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str = "attn"           # "attn" | "mamba" | "cross"
    ffn: str = "dense"           # "dense" | "moe" | "none"
    window: Optional[int] = None  # sliding window for this position


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0
    d_ff: int = 0
    activation: str = "silu"
    qk_norm: bool = False
    rope_theta: float = 500000.0
    ffn_gated: bool = True       # False: h = act(W_up x) (nemotron relu^2 MLP)
    pattern: tuple = (LayerSpec(),)
    moe: Optional[moe_mod.MoEConfig] = None
    ssm: Optional[ssm_mod.SSMConfig] = None
    max_seq: int = 8192
    n_img_tokens: int = 0        # VLM: image-embedding stub length
    kv_cache_bits: int = 16
    flash_vjp: bool = False      # memory-efficient attention backward
    kv_col_parallel: bool = False  # kv projections column-parallel (§Perf)
    decode_pin_seq: bool = False   # pin decode cache seq-sharding (§Perf)
    mask_cache_update: bool = False  # shard-local where() cache writes
    kv_replicated: bool = False    # kv projections replicated over tp
    attn_int8: bool = False        # integer decode attention on int8 cache
    attn_block: int = 512          # flash attention block size
    remat: str = "full"          # "full" | "dots" | "none"
    sub_quadratic: bool = False  # eligible for long_500k
    # families: dense | moe | ssm | hybrid | audio | vlm
    family: str = "dense"

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def n_groups(self) -> int:
        assert self.n_layers % self.period == 0, (self.n_layers, self.period)
        return self.n_layers // self.period

    def attn_cfg(self, spec: LayerSpec) -> attn.AttnConfig:
        return attn.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_head=self.d_head,
            rope_theta=self.rope_theta, qk_norm=self.qk_norm,
            window=spec.window, cross=(spec.kind == "cross"),
            kv_cache_bits=self.kv_cache_bits, flash_vjp=self.flash_vjp,
            kv_col_parallel=self.kv_col_parallel,
            decode_pin_seq=self.decode_pin_seq,
            mask_cache_update=self.mask_cache_update,
            kv_replicated=self.kv_replicated, attn_int8=self.attn_int8,
            block=self.attn_block)


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------

def ffn_init(key, d: int, f: int, dtype=jnp.bfloat16, gated: bool = True):
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    if gated:
        p["w_gate"], s["w_gate"] = L.linear_init(ks[0], d, f, "fsdp", "tp", dtype)
    p["w_up"], s["w_up"] = L.linear_init(ks[1], d, f, "fsdp", "tp", dtype)
    p["w_down"], s["w_down"] = L.linear_init(ks[2], f, d, "tp", "fsdp", dtype)
    return p, s


def ffn_apply(p, x, activation: str, exec_cfg) -> jax.Array:
    u = L.linear_apply(p["w_up"], x, exec_cfg, "ffn_up")
    if "w_gate" in p:
        g = L.linear_apply(p["w_gate"], x, exec_cfg, "ffn_gate")
        h = L.activation_fn(activation)(g) * u
    else:
        h = L.activation_fn(activation)(u)
    h = constraint(h, PS("dp", None, "tp"))
    return L.linear_apply(p["w_down"], h, exec_cfg, "ffn_down")


# ---------------------------------------------------------------------------
# Block init/apply
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, spec: LayerSpec, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.norm_init(cfg.d_model, dtype)
    if spec.kind == "mamba":
        p["mix"], s["mix"] = ssm_mod.init(ks[0], cfg.ssm, dtype)
    else:
        p["mix"], s["mix"] = attn.init(ks[0], cfg.attn_cfg(spec), dtype)
    if spec.ffn != "none":
        p["ln2"], s["ln2"] = L.norm_init(cfg.d_model, dtype)
        if spec.ffn == "moe":
            p["ffn"], s["ffn"] = moe_mod.init(ks[1], cfg.moe, dtype)
        else:
            p["ffn"], s["ffn"] = ffn_init(ks[1], cfg.d_model, cfg.d_ff, dtype,
                                          gated=cfg.ffn_gated)
    return p, s


def block_apply_train(p, cfg: ModelConfig, spec: LayerSpec, x, positions,
                      exec_cfg, img_embeds=None):
    h = L.rms_norm(x, p["ln1"]["g"])
    if spec.kind == "mamba":
        mix = ssm_mod.apply_train(p["mix"], cfg.ssm, h, exec_cfg)
    elif spec.kind == "cross":
        mix = attn.apply_train(p["mix"], cfg.attn_cfg(spec), h, positions,
                               exec_cfg, kv_x=img_embeds)
    else:
        mix = attn.apply_train(p["mix"], cfg.attn_cfg(spec), h, positions,
                               exec_cfg)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn != "none":
        h = L.rms_norm(x, p["ln2"]["g"])
        if spec.ffn == "moe":
            f, aux = moe_mod.apply(p["ffn"], cfg.moe, h, exec_cfg)
        else:
            f = ffn_apply(p["ffn"], h, cfg.activation, exec_cfg)
        x = x + f
    x = constraint(x, PS("dp", None, None))
    return x, aux


def block_apply_decode(p, cfg: ModelConfig, spec: LayerSpec, x, pos,
                       exec_cfg, cache, layer):
    """``cache``: this pattern position's stacked cache (leaves [L, ...]),
    of which the block reads and writes layer ``layer``."""
    h = L.rms_norm(x, p["ln1"]["g"])
    if spec.kind == "mamba":
        mix, c = ssm_mod.apply_decode(p["mix"], cfg.ssm, h, exec_cfg,
                                      attn.layer_cache(cache, layer))
        cache = jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0),
            cache, c)
    else:
        mix, cache = attn.apply_decode(p["mix"], cfg.attn_cfg(spec), h, pos,
                                       exec_cfg, cache, layer)
    x = x + mix
    if spec.ffn != "none":
        h = L.rms_norm(x, p["ln2"]["g"])
        if spec.ffn == "moe":
            f, _ = moe_mod.apply(p["ffn"], cfg.moe, h, exec_cfg)
        else:
            f = ffn_apply(p["ffn"], h, cfg.activation, exec_cfg)
        x = x + f
    return x, cache


def block_cache_init(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_seq: int):
    if spec.kind == "mamba":
        return ssm_mod.init_cache(cfg.ssm, batch)
    if spec.kind == "cross":
        a = cfg.attn_cfg(spec)
        n = cfg.n_img_tokens
        return {"k": jnp.zeros((batch, n, a.n_kv_heads, a.d_head), jnp.bfloat16),
                "v": jnp.zeros((batch, n, a.n_kv_heads, a.d_head), jnp.bfloat16),
                "slot_pos": jnp.zeros((batch, n), jnp.int32)}
    return attn.init_cache(cfg.attn_cfg(spec), batch, max_seq)


def block_cache_specs(cfg: ModelConfig, spec: LayerSpec):
    if spec.kind == "mamba":
        return ssm_mod.cache_specs(cfg.ssm)
    if spec.kind == "cross":
        return {"k": PS("dp", "sp", None, None), "v": PS("dp", "sp", None, None),
                "slot_pos": PS("dp", "sp")}
    return attn.cache_specs(cfg.attn_cfg(spec))
