"""Qwen3 dense decoder: seeded weights and a plain float32 reference.

The weights are the benchmark's own: one jitted call makes every leaf
from the seed on the device, in bfloat16 (the dense type the program
packs from). ``program_tree`` hands the same arrays to the program in
its dense parameter layout; nothing the program computes flows back.

The reference follows the Qwen3 description (hf:Qwen/Qwen3-1.7B,
modeling_qwen3): pre-norm RMSNorm blocks, grouped-query attention with
RMSNorm on each query and key head before a half-split rotary
embedding, a SiLU-gated MLP, a final RMSNorm and an untied output head.
The norm gains are stored as offsets from 1 (``x * (1 + g)``), which is
the same function as Qwen3's ``x * w`` with ``w = 1 + g``. Everything is
float32 at ``highest`` matmul precision, one sequence at a time, the
layers scanned so that only one layer is upcast at a time.

Optional fake quantization (``a_bits``/``w_bits``) turns the reference
into the lower-precision control: symmetric per-tensor weights and
per-row activations at the given widths, on every linear.

It also states what the harness reads of this architecture, by the
contract in ``manifest.py``: the program config's fields that the file
fixes (``program_fields``) and the decode work (``decode_linears``,
``decode_attention``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
LINEARS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def sizes(cfg: dict) -> dict:
    """The sizes the reference needs, by their Hugging Face names."""
    return {k: cfg[k] for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "rope_theta", "rms_norm_eps")}


# -- what the harness reads of this architecture (manifest.py) ---------------

def program_fields(c: dict) -> dict:
    """Each ``ModelConfig`` attribute that the configuration file fixes,
    with its value."""
    return {"n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "vocab": c["vocab_size"], "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"], "d_head": c["head_dim"],
            "d_ff": c["intermediate_size"], "rope_theta": c["rope_theta"],
            "qk_norm": True, "activation": "silu"}


def decode_linears(c: dict) -> list[tuple[str, int, int, int]]:
    """(name, K, N, count) of every linear of one token's forward pass."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    n = c["num_hidden_layers"]
    return [("attn_q", d, h * dh, n), ("attn_k", d, kv * dh, n),
            ("attn_v", d, kv * dh, n), ("attn_o", h * dh, d, n),
            ("ffn_gate", d, f, n), ("ffn_up", d, f, n),
            ("ffn_down", f, d, n), ("lm_head", d, c["vocab_size"], 1)]


def decode_attention(c: dict, ctx: int, kv_bytes: int = 2):
    """(ops, bytes) of one row's decode attention over ``ctx`` valid
    positions, all layers."""
    h, kv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    n = c["num_hidden_layers"]
    return n * 4 * ctx * h * dh, n * 2 * ctx * kv * dh * kv_bytes


# -- seeded weights ------------------------------------------------------------

def _shapes(c: dict) -> dict:
    d, f = c["hidden_size"], c["intermediate_size"]
    n, h, kv, dh = (c["num_hidden_layers"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    v = c["vocab_size"]
    return {
        "embed": (v, d), "head": (d, v), "final_norm": (d,),
        "ln1": (n, d), "ln2": (n, d), "qnorm": (n, dh), "knorm": (n, dh),
        "wq": (n, d, h * dh), "wk": (n, d, kv * dh), "wv": (n, d, kv * dh),
        "wo": (n, h * dh, d), "w_gate": (n, d, f), "w_up": (n, d, f),
        "w_down": (n, f, d),
    }


def _std(name: str, shape) -> float:
    if name == "embed":
        return 0.02
    if name in ("final_norm", "ln1", "ln2", "qnorm", "knorm"):
        return 0.1                      # gains 1 + g, g ~ N(0, 0.1^2)
    return shape[-2] ** -0.5            # fan-in scaling of a linear


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, items):
    out = {}
    for i, (name, shape) in enumerate(items):
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (w * _std(name, shape)).astype(jnp.bfloat16)
    return out


def make_weights(seed: int, c: dict) -> dict:
    """Every weight from ``seed``, on the default device, in bfloat16."""
    items = tuple(sorted(_shapes(c).items()))
    return _make(jax.random.PRNGKey(seed), items)


def program_tree(w: dict) -> tuple[dict, dict]:
    """(params, specs) in the program's dense layout (``models/model.py``):
    blocks stacked on a leading layer axis under ``blocks/p0``."""
    lin = lambda a: {"w": a}
    gain = lambda a: {"g": a}
    params = {
        "embed": {"emb": w["embed"]},
        "final_norm": gain(w["final_norm"]),
        "head": lin(w["head"]),
        "blocks": {"p0": {
            "ln1": gain(w["ln1"]), "ln2": gain(w["ln2"]),
            "mix": {"wq": lin(w["wq"]), "wk": lin(w["wk"]),
                    "wv": lin(w["wv"]), "wo": lin(w["wo"]),
                    "qnorm": gain(w["qnorm"]), "knorm": gain(w["knorm"])},
            "ffn": {"w_gate": lin(w["w_gate"]), "w_up": lin(w["w_up"]),
                    "w_down": lin(w["w_down"])}}},
    }
    from jax.sharding import PartitionSpec as PS
    specs = jax.tree.map(lambda a: PS(*([None] * a.ndim)), params)
    return params, specs


# -- the reference forward ----------------------------------------------------

def _fq(x, bits: int, axis):
    """Symmetric fake quantization to ``bits`` (0 = off)."""
    if not bits:
        return x
    q = (1 << (bits - 1)) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    jnp.finfo(jnp.float32).tiny) / q
    return jnp.clip(jnp.round(x / s), -q - 1, q) * s


def _lin(x, w, a_bits, w_bits):
    w = _fq(w.astype(jnp.float32), w_bits, None)
    return _fq(x, a_bits, -1) @ w


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    """x: [S, H, D], positions 0..S-1, half-split rotation."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _hidden(w, tokens, c, a_bits, w_bits):
    """Final-normed hidden states [S, d] of one sequence."""
    h, kv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    s = tokens.shape[0]
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))
    layers = {k: w[k] for k in LINEARS + ("ln1", "ln2", "qnorm", "knorm")}

    def layer(x, p):
        lin = lambda a, k: _lin(a, p[k], a_bits, w_bits)
        y = _rms(x, p["ln1"], eps)
        q = _rms(lin(y, "wq").reshape(s, h, dh), p["qnorm"], eps)
        k = _rms(lin(y, "wk").reshape(s, kv, dh), p["knorm"], eps)
        v = lin(y, "wv").reshape(s, kv, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        att = jnp.einsum("qhd,khd->hqk", q, k) * dh ** -0.5
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, h * dh)
        x = x + lin(o, "wo")
        y = _rms(x, p["ln2"], eps)
        x = x + lin(jax.nn.silu(lin(y, "w_gate")) * lin(y, "w_up"), "w_down")
        return x, None

    x, _ = jax.lax.scan(layer, x, layers)
    return _rms(x, w["final_norm"], eps)


def _logits_blocks(w, hid, a_bits, w_bits, rows: int):
    """lm_head over blocks of ``rows`` positions: yields [rows, V]."""
    s = hid.shape[0]
    for i in range(0, s, rows):
        yield i, _lin(hid[i:i + rows], w["head"], a_bits, w_bits)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _stats(w, tokens, targets, c_items, a_bits=0, w_bits=0):
    """Per position: (max logit, std over the vocabulary, logit of each
    target row) of the (optionally fake-quantized) reference."""
    c = dict(c_items)
    with jax.default_matmul_precision("highest"):
        hid = _hidden(w, tokens, c, a_bits, w_bits)
        mx, sd, at = [], [], []
        for i, lg in _logits_blocks(w, hid, a_bits, w_bits, 256):
            mx.append(jnp.max(lg, -1))
            sd.append(jnp.std(lg, -1))
            at.append(jnp.take_along_axis(lg[None], targets[:, i:i + 256,
                                                             None], -1)[..., 0])
        return (jnp.concatenate(mx), jnp.concatenate(sd),
                jnp.concatenate(at, axis=1))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _argmax(w, tokens, c_items, a_bits, w_bits):
    c = dict(c_items)
    with jax.default_matmul_precision("highest"):
        hid = _hidden(w, tokens, c, a_bits, w_bits)
        return jnp.concatenate([jnp.argmax(lg, -1).astype(jnp.int32)
                                for _, lg in _logits_blocks(
                                    w, hid, a_bits, w_bits, 256)])


def served_gaps(w, c: dict, prompt, served, pad_to: int,
                control: tuple[int, int] | None = None) -> np.ndarray:
    """Normalized gap of every served token of one request.

    ``prompt`` and ``served`` are int arrays; the reference reads
    ``prompt + served[:-1]`` (padded to ``pad_to`` so that one program
    serves every request) and, at the position that predicted each
    served token, returns (best logit - logit of the served token) / the
    standard deviation of that position's logits. With ``control =
    (a_bits, w_bits)`` the served tokens are replaced by the argmax of
    the fake-quantized reference at each position.
    """
    p, n = len(prompt), len(served)
    seq = np.zeros(pad_to, np.int32)
    seq[:p] = prompt
    seq[p:p + n - 1] = served[:-1]
    items = tuple(sorted(sizes(c).items()))
    tokens = jnp.asarray(seq)
    if control is None:
        tgt = np.zeros(pad_to, np.int32)
        tgt[p - 1:p + n - 1] = served
        tgt = jnp.asarray(tgt)
    else:
        tgt = _argmax(w, tokens, items, *control)
    mx, sd, at = _stats(w, tokens, tgt[None], items)
    mx, sd, at = (np.asarray(a, np.float64) for a in (mx, sd, at[0]))
    sl = slice(p - 1, p + n - 1)
    return (mx[sl] - at[sl]) / sd[sl]
