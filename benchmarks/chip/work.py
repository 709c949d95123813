"""Operations and bytes that each layer's algorithm needs, from shapes.

Work is the logical operation, whatever implements it:

- operations: 2*M*K*N dense-equivalent multiply-adds counted twice, with
  M the ACTIVE rows (padding rows and empty batch slots count nothing);
- bytes: the weights at Pw bits, the activations at Pa bits, and the
  outputs in the model's bfloat16;
- attention during decode: the bytes of the VALID cache positions only,
  and the operations of scores and weighted sum over them.

No LM architecture is counted here: an LM configuration's linears and
its decode attention come from its own reference module
(``decode_linears``, ``decode_attention``; see manifest.py), where each
architecture states its shapes.

A route change (bit-serial planes, int8, bf16) leaves these counts
unchanged, and a change that skips padded rows or dead cache positions
is credited. ``least_time`` turns a count into the least seconds the
chip could take (the larger of its compute and memory bounds), and
``peaks`` reads the chip's peaks from ``peaks.json`` by device kind.
"""
from __future__ import annotations

import json
import os

import manifest

OUT_BYTES = 2          # bfloat16 outputs


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def linear(m: int, k: int, n: int, a_bits: int = 8, w_bits: int = 8):
    """(ops, bytes) of y[M, N] = x[M, K] @ w[K, N]."""
    ops = 2 * m * k * n
    nbytes = k * n * w_bits / 8 + m * k * a_bits / 8 + m * n * OUT_BYTES
    return ops, nbytes


def conv(b: int, h: int, w: int, c_in: int, c_out: int, kernel: int,
         stride: int = 1, a_bits: int = 8, w_bits: int = 8):
    """(ops, bytes) of a 'same'-padded conv over a [B, H, W, C_in] map."""
    ho, wo = -(-h // stride), -(-w // stride)
    ops = 2 * b * ho * wo * kernel * kernel * c_in * c_out
    nbytes = (kernel * kernel * c_in * c_out * w_bits / 8
              + b * h * w * c_in * a_bits / 8 + b * ho * wo * c_out * OUT_BYTES)
    return ops, nbytes


def least_time(ops: float, nbytes: float, pk: dict) -> float:
    """Least seconds on the chip: int8 compute bound vs HBM bound."""
    return max(ops / pk["int8_ops"], nbytes / pk["hbm_bytes_per_s"])


# -- a language model, by its reference module ------------------------------

def lm_decode(c: dict):
    """The reference module of LM configuration ``c`` where it states its
    decode work (``decode_linears`` and ``decode_attention``; the contract
    is in manifest.py), else None."""
    ref = manifest.load_module("refs", c["reference"])
    if hasattr(ref, "decode_linears") and hasattr(ref, "decode_attention"):
        return ref
    return None


def lm_linear_work(c: dict, rows: int, a_bits: int = 8, w_bits: int = 8):
    """(ops, bytes) of every linear of one step over ``rows`` active rows;
    None where the reference states no decode work."""
    ref = lm_decode(c)
    if ref is None:
        return None
    ops = nbytes = 0.0
    for _, k, n, count in ref.decode_linears(c):
        o, b = linear(rows, k, n, a_bits, w_bits)
        ops += o * count
        nbytes += b * count
    return ops, nbytes


def decode_step(c: dict, contexts: list[int], a_bits: int = 8,
                w_bits: int = 8):
    """(ops, bytes) of one batched decode step; ``contexts`` holds the
    valid cache length of each active row (its own token included).
    None where the reference states no decode work."""
    ref = lm_decode(c)
    if ref is None:
        return None
    ops, nbytes = lm_linear_work(c, len(contexts), a_bits, w_bits)
    for ctx in contexts:
        o, b = ref.decode_attention(c, ctx)
        ops += o
        nbytes += b
    return ops, nbytes


# -- a plain CNN (models/cnn.CNNConfig fields) --------------------------------

def cnn_layers(cfg: dict) -> list[tuple]:
    """('conv', H, W, C_in, C_out, k, stride) and ('fc', K, N) per layer,
    for one image."""
    out = []
    side, ch = cfg["image_size"], cfg["in_channels"]
    for conv_ in cfg["convs"]:
        out.append(("conv", side, side, ch, conv_["out"], conv_["kernel"],
                    conv_.get("stride", 1)))
        ch = conv_["out"]
        side = side // conv_.get("stride", 1) // conv_.get("pool", 1)
    k = side * side * ch
    for n in cfg["fcs"]:
        out.append(("fc", k, n))
        k = n
    return out


def classify(cfg: dict, batch: int, a_bits: int = 8, w_bits: int = 8):
    """(ops, bytes) of one classify call, summed over its layers."""
    ops = nbytes = 0.0
    for layer in cnn_layers(cfg):
        fn = conv if layer[0] == "conv" else linear
        o, b = fn(batch, *layer[1:], a_bits=a_bits, w_bits=w_bits)
        ops += o
        nbytes += b
    return ops, nbytes
