"""A plain CNN of 3x3 convs, max pools and fully connected layers: seeded
weights, seeded images and a float32 reference.

The configuration file gives the layers (``convs`` with ``out``,
``kernel``, ``pool``; ``fcs``), as VGG's Table 1 does. Each conv weight
is kept as the program keeps it, a [k*k*C_in, C_out] matrix in (row,
column, channel) order, and He-scaled so that the signal neither grows
nor dies through ReLUs. There are no biases (the program's layers have
none). The reference convolves in NHWC at ``highest`` precision, applies
ReLU after every conv and every FC but the last, and max-pools where a
conv asks for it. Optional fake quantization (``a_bits``/``w_bits``,
symmetric per tensor, as the program quantizes a conv's input) turns it
into the lower-precision control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _shapes(cfg: dict) -> list[tuple[str, tuple]]:
    out, ch, side = [], cfg["in_channels"], cfg["image_size"]
    for c in cfg["convs"]:
        out.append((c["name"], (c["kernel"] * c["kernel"] * ch, c["out"])))
        ch, side = c["out"], side // c.get("pool", 1)
    k = side * side * ch
    for i, n in enumerate(cfg["fcs"]):
        out.append((f"fc{i}", (k, n)))
        k = n
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, items):
    return {name: jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32) * (2.0 / shape[0]) ** 0.5
            for i, (name, shape) in enumerate(items)}


def make_weights(seed: int, cfg: dict) -> dict:
    """Every weight from ``seed``, on the device, in float32 (the dense
    type the program packs from)."""
    return _make(jax.random.PRNGKey(seed), tuple(_shapes(cfg)))


def program_tree(w: dict) -> tuple[dict, dict]:
    """(params, specs) in the program's layout (``models/cnn.py``)."""
    from jax.sharding import PartitionSpec as PS
    params = {name: {"w": a} for name, a in w.items()}
    return params, jax.tree.map(lambda a: PS(None, None), params)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _images(key, n: int, batch: int, shape: tuple):
    return jax.random.normal(key, (n, batch) + shape, jnp.float32)


def make_images(seed: int, cfg: dict, n: int, batch: int):
    """``n`` distinct batches [batch, H, W, C] of N(0, 1) pixels."""
    side = cfg["image_size"]
    return _images(jax.random.fold_in(jax.random.PRNGKey(seed), 1 << 20),
                   n, batch, (side, side, cfg["in_channels"]))


def _fq(x, bits: int):
    if not bits:
        return x
    q = (1 << (bits - 1)) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x)), jnp.finfo(jnp.float32).tiny) / q
    return jnp.clip(jnp.round(x / s), -q - 1, q) * s


def layers(cfg: dict) -> tuple:
    """The layer walk as a hashable tuple: ("conv", name, kernel, pool)
    and ("fc", name, relu)."""
    n = len(cfg["fcs"])
    return (tuple(("conv", c["name"], c["kernel"], c.get("pool", 1))
                  for c in cfg["convs"])
            + tuple(("fc", f"fc{i}", i < n - 1) for i in range(n)))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def logits(w: dict, x, walk: tuple, a_bits: int = 0, w_bits: int = 0):
    """Reference logits [B, classes] of images x [B, H, W, C]; ``walk``
    is ``layers(cfg)``."""
    with jax.default_matmul_precision("highest"):
        for layer in walk:
            if layer[0] == "conv":
                _, name, k, p = layer
                wc = _fq(w[name], w_bits).reshape(k, k, x.shape[-1], -1)
                x = jax.lax.conv_general_dilated(
                    _fq(x, a_bits), wc, (1, 1), [(k // 2, k // 2)] * 2,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                x = jax.nn.relu(x)
                if p > 1:
                    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                              (1, p, p, 1), (1, p, p, 1),
                                              "VALID")
            else:
                _, name, relu = layer
                x = _fq(x.reshape(x.shape[0], -1), a_bits) @ _fq(w[name],
                                                                 w_bits)
                if relu:
                    x = jax.nn.relu(x)
        return x


def logit_error(got, want) -> float:
    """Widest |got - want| of any image, in units of that image's spread
    of reference logits."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want).max(-1) / want.std(-1)))
