"""Property tests on the distribution layer's invariants: logical-axis
resolution, override composition, and the serving conversion's byte law."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as PS

from repro import configs
from repro.dist import sharding
from repro.launch import shapes
from repro.launch.mesh import make_mesh

jax.config.update("jax_platform_name", "cpu")

LOGICAL = [None, "dp", "fsdp", "tp", "sp"]


def _rules(multi=False):
    if multi:
        return {"fsdp": ("pod", "data"), "dp": ("pod", "data"),
                "tp": "model", "sp": "model"}
    return {"fsdp": "data", "dp": "data", "tp": "model", "sp": "model"}


@given(st.lists(st.sampled_from(LOGICAL), min_size=1, max_size=4),
       st.booleans())
@settings(max_examples=40, deadline=None)
def test_resolve_spec_never_leaks_logical_names(entries, multi):
    spec = PS(*entries)
    out = sharding.resolve_spec(spec, _rules(multi))
    flat = []
    for e in out:
        if isinstance(e, tuple):
            flat.extend(e)
        elif e is not None:
            flat.append(e)
    assert all(a in ("pod", "data", "model") for a in flat), out
    assert len(out) == len(spec)


@given(st.lists(st.sampled_from(LOGICAL), min_size=1, max_size=4))
@settings(max_examples=30, deadline=None)
def test_resolve_spec_idempotent_on_resolved(entries):
    rules = _rules()
    once = sharding.resolve_spec(PS(*entries), rules)
    twice = sharding.resolve_spec(once, rules)
    assert once == twice


def _mesh_rules():
    mesh = make_mesh((1, 1), ("data", "model"))
    return sharding.rules_for_mesh(mesh)


@given(st.sampled_from(["dp", "sp", "fsdp", "tp"]))
@settings(max_examples=10, deadline=None)
def test_override_drop_axis(axis):
    try:
        sharding.set_rule_overrides({axis: ()})
        out = sharding.resolve_spec(PS(axis, "tp"), _mesh_rules())
        if axis != "tp":
            assert out[0] is None
    finally:
        sharding.set_rule_overrides({})


def test_override_alias_to_other_logical():
    try:
        sharding.set_rule_overrides({"sp": ("data", "model")})
        out = sharding.resolve_spec(PS("dp", "sp"), _mesh_rules())
        assert out == PS("data", ("data", "model"))
    finally:
        sharding.set_rule_overrides({})


# ---------------------------------------------------------------------------
# Serving-conversion invariants across every architecture
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", list(configs.LM_ARCHS))
def test_packed_structs_byte_law_every_arch(arch):
    """For every arch: serve_int8 shrinks every 2-D linear to ~half the
    bf16 bytes and the struct tree stays shard-spec-complete."""
    cfg = configs.get(arch)
    p_dense, s_dense = shapes.param_structs(cfg)
    p_int8, s_int8 = shapes.param_structs(cfg, serving_mode="serve_int8")
    bytes_d = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(p_dense))
    bytes_q = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(p_int8))
    assert bytes_q < 0.75 * bytes_d, (arch, bytes_q / bytes_d)
    assert (jax.tree_util.tree_structure(p_int8)
            == jax.tree_util.tree_structure(
                jax.tree.map(lambda x: x, s_int8,
                             is_leaf=lambda x: isinstance(x, PS))))


@given(st.integers(2, 16))
@settings(max_examples=15, deadline=None)
def test_packed_weight_bytes_exactly_pw_over_16(w_bits):
    """The paper's storage law as a property: packed bytes == Pw/16 x bf16
    for any weight precision."""
    from repro.core import bitpack
    k, n = 64, 32
    assert bitpack.packed_nbytes((k, n), w_bits) \
        == int(bitpack.baseline_nbytes((k, n)) * w_bits / 16)
