"""Ahead-of-time Mosaic compiles of the serving kernels for one TPU v5e.

Each test lowers one Pallas kernel with ``interpret=False`` at a real
serving shape against a described (not attached) ``v5e:2x2`` topology
and compiles it with the TPU compiler, which refuses what the chip
would refuse: unsupported vector layouts, misaligned block shapes, VMEM
overruns. Nothing runs, so these tests say nothing about results or
time; the interpret-mode tests and the chip smoke run cover those.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api.backend import _pallas_blocks
from repro.kernels.bitserial_conv import (bitserial_conv,
                                          bitserial_conv_dynamic,
                                          bitserial_conv_wgroup)
from repro.kernels.bitserial_matmul import (bitserial_matmul,
                                            bitserial_matmul_dynamic)
from repro.kernels.dynamic_quant import dynamic_quant

# qwen3-1.7b serving widths and the paper CNN's middle conv at 32 px.
D_MODEL, D_FF, VOCAB, DECODE_M = 2048, 6144, 151936, 8
CNN_B, CNN_HW, CNN_C, CNN_N = 64, 16, 32, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n", [D_FF, VOCAB], ids=["ffn_up", "lm_head"])
def test_bitserial_matmul_decode(one_chip, n):
    bm, bn, bk = _pallas_blocks(DECODE_M, n, D_MODEL)
    fn = functools.partial(bitserial_matmul, w_bits=8, bm=bm, bn=bn, bk=bk,
                           interpret=False)
    _compile(fn, one_chip, ((DECODE_M, D_MODEL), jnp.int8),
             ((8, D_MODEL // 8, n), jnp.uint8))


def test_bitserial_matmul_weight_groups(one_chip):
    """The lm_head weight-group route: one count per 16 filters, 128-wide
    N-tiles (the backend's static trimming call)."""
    bm, bn, bk = _pallas_blocks(DECODE_M, VOCAB, D_MODEL)
    fn = functools.partial(bitserial_matmul_dynamic, w_bits=8, group=16,
                           bm=bm, bn=bn, bk=bk, interpret=False)
    _compile(fn, one_chip, ((DECODE_M, D_MODEL), jnp.int8),
             ((8, D_MODEL // 8, VOCAB), jnp.uint8),
             ((VOCAB // 16,), jnp.int32))


def _conv_shapes():
    k8 = -(-9 * CNN_C // 8)
    return (((CNN_B, CNN_HW, CNN_HW, CNN_C), jnp.int8),
            ((8, k8, CNN_N), jnp.uint8))


@pytest.mark.parametrize("rows_per_band", [None, 4])
def test_bitserial_conv(one_chip, rows_per_band):
    fn = functools.partial(bitserial_conv, kernel=3, stride=1, w_bits=8,
                           rows_per_band=rows_per_band, interpret=False)
    _compile(fn, one_chip, *_conv_shapes())


def test_bitserial_conv_wgroup(one_chip):
    fn = functools.partial(bitserial_conv_wgroup, kernel=3, stride=1,
                           w_bits=8, w_group=16, bn=CNN_N, interpret=False)
    _compile(fn, one_chip, *_conv_shapes(), ((CNN_N // 16,), jnp.int32))


def test_bitserial_conv_dynamic(one_chip):
    k8 = -(-9 * CNN_C // 8) * 8
    groups = -(-CNN_HW * CNN_HW // 64)
    fn = functools.partial(bitserial_conv_dynamic, kernel=3, stride=1,
                           a_bits=8, group_size=64, interpret=False)
    _compile(fn, one_chip, ((CNN_B, CNN_HW, CNN_HW, CNN_C), jnp.int8),
             ((k8, CNN_N), jnp.int8), ((CNN_B, groups), jnp.int32))


def test_dynamic_quant(one_chip):
    fn = functools.partial(dynamic_quant, group_size=256, bits=8,
                           interpret=False)
    _compile(fn, one_chip, ((256, D_MODEL), jnp.float32))


# -- kernel names: each packed kernel is named after its layer ----------------

LM_CLASSES = ("attn_q", "attn_k", "attn_v", "attn_o", "ffn_gate", "ffn_up",
              "ffn_down", "lm_head")


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sharding),
                        tree)


def _custom_calls(text: str, kernel: str) -> list:
    """Names of the ``kernel*`` custom calls of a compiled program."""
    return re.findall(rf"%({kernel}[\w]*?)(?:\.\d+)? = [^\n]*custom-call\(",
                      text)


def _lm_policy(name):
    from repro.core.policy import uniform_policy
    return {"static": uniform_policy(8, 8),
            "dynamic_a": uniform_policy(8, 8, dynamic_a=True),
            "w_group": uniform_policy(8, 8, w_group=16)}[name]


@pytest.mark.parametrize("policy", ["static", "dynamic_a", "w_group"])
def test_decode_step_names_kernels_by_layer(one_chip, policy):
    """One packed decode step of the smoke qwen3 on pallas_tpu: every
    linear class compiles to one custom call named
    ``bitserial_matmul_<class>``, on each route (static planes, runtime
    activation trimming, and the lm_head's weight-group route)."""
    from repro import configs
    from repro.api.plan import build_plan
    from repro.api.session import _jit_lm
    from repro.launch import shapes
    from repro.models import model as M
    cfg = configs.get("qwen3-1.7b", smoke=True)
    pol = _lm_policy(policy)
    plan = build_plan(cfg, pol, "serve_packed", "pallas_tpu")
    if policy == "w_group":
        # pack-time counts with one trimmable group: the lm_head takes the
        # weight-group kernel, as a trimmed checkpoint's would
        plan.layer("lm_head")
        plan.set_weight_counts("lm_head", "linear",
                               (7,) + (8,) * (cfg.vocab // 16 - 1))
    params, _ = shapes.param_structs(cfg, serving_mode="serve_packed",
                                     policy=pol)
    cache = jax.eval_shape(lambda: M.init_cache(cfg, DECODE_M, cfg.max_seq))
    tok = jax.ShapeDtypeStruct((DECODE_M,), jnp.int32, sharding=one_chip)
    decode = _jit_lm(cfg, plan, None, None, None)[1]
    text = decode.lower(_on(one_chip, params), tok, tok,
                        _on(one_chip, cache)).compile().as_text()
    names = _custom_calls(text, "bitserial_matmul")
    assert sorted(names) == sorted(f"bitserial_matmul_{c}"
                                   for c in LM_CLASSES), names


def test_classify_names_convs_by_layer(one_chip):
    """A small CNN's packed classify on pallas_tpu: each fused conv is
    ``bitserial_conv_<layer>`` and the FC ``bitserial_matmul_fc0``."""
    from repro.api.plan import build_plan
    from repro.core.policy import uniform_policy
    from repro.launch import shapes
    from repro.models import cnn
    from repro.models import model as M
    cfg = cnn.CNNConfig(img=16, convs=(cnn.ConvSpec("conv1", 32, 3, pool=2),
                                       cnn.ConvSpec("conv2", 64, 3, pool=2)),
                        fcs=(10,))
    pol = uniform_policy(8, 8)
    plan = build_plan(cfg, pol, "serve_packed", "pallas_tpu")
    params, specs = shapes._eval_shape_with_specs(
        lambda: cnn.init_params(jax.random.PRNGKey(0), cfg))
    params, _ = M.convert_structs_for_serving(params, specs, pol,
                                              "serve_packed")
    x = jax.ShapeDtypeStruct((CNN_B, 16, 16, 3), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(lambda p, x: cnn.forward(p, cfg, x, plan)).lower(
        _on(one_chip, params), x).compile().as_text()
    assert sorted(_custom_calls(text, "bitserial_conv")) == [
        "bitserial_conv_conv1", "bitserial_conv_conv2"]
    assert _custom_calls(text, "bitserial_matmul") == ["bitserial_matmul_fc0"]


# -- the decode step keeps the KV cache in place ------------------------------

def _hlo_computations(text: str) -> dict:
    """Instruction lines of each computation of a compiled HLO module."""
    comps, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$", line)
        if head:
            lines = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            lines = None
        elif lines is not None:
            lines.append(line)
    return comps


def _shapes_of(hlo_type: str):
    for dtype, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", hlo_type):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        yield dtype, n


def test_decode_step_updates_cache_in_place(one_chip):
    """The full-width qwen3-1.7b decode step of the chat cell (12 slots x
    3072, per-row pos, packed 8/8 on pallas_tpu): outside the parameters
    and the layer loop, the only instructions whose output is as large as
    one layer's K are the in-place K and V writes into the carried cache,
    and nothing that large is f32 — no repeated, upcast, transposed or
    restacked copy of the cache, in the loop or after it."""
    from repro import configs
    from repro.api.plan import build_plan
    from repro.api.session import _jit_lm
    from repro.core.policy import uniform_policy
    from repro.launch import shapes
    from repro.models import model as M
    slots, seq = 12, 3072
    cfg = configs.get("qwen3-1.7b")
    pol = uniform_policy(8, 8)
    plan = build_plan(cfg, pol, "serve_packed", "pallas_tpu")
    params, _ = shapes.param_structs(cfg, serving_mode="serve_packed",
                                     policy=pol)
    cache = jax.eval_shape(lambda: M.init_cache(cfg, slots, seq))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    decode = _jit_lm(cfg, plan, None, None, None)[1]
    text = decode.lower(_on(one_chip, params), rows, rows,
                        _on(one_chip, cache)).compile().as_text()

    layer_k = slots * seq * cfg.n_kv_heads * cfg.d_head
    comps = _hlo_computations(text)
    called = {c for lines in comps.values() for line in lines
              for c in re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", line)}
    writes, big = [], []
    for name, lines in comps.items():
        if name in called:
            continue      # a fusion's body: its values never reach HBM
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\(", line)
            if m is None:
                continue
            inst, hlo_type, op = m.groups()
            sizes = [(dt, n) for dt, n in _shapes_of(hlo_type) if n >= layer_k]
            if not sizes:
                continue
            assert not [dt for dt, _ in sizes if dt == "f32"], line[:300]
            if op in ("parameter", "get-tuple-element", "tuple", "bitcast",
                      "while"):
                continue
            in_place = ('"aliasing_operands":{"lists":[{' in line
                        and "scatter" in line) or op == "scatter"
            (writes if in_place else big).append(inst)
    assert not big, big
    assert len(writes) == 2, writes       # K and V, each once per layer
