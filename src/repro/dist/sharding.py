"""Logical-axis sharding: resolve model-side PartitionSpecs to mesh axes.

Model code annotates params and activations with LOGICAL axis names
("dp", "fsdp", "tp", "sp"); this module resolves them against the ambient
mesh's PHYSICAL axes ("pod", "data", "model") via a rules dict, with a
process-global override table for launch-time experiments (e.g. dropping
sequence parallelism for a decode cell).

Resolution is idempotent: physical names and ``None`` pass through, so a
resolved spec can be resolved again (the dryrun driver does this when it
re-enters with a different mesh kind).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

# --------------------------------------------------------------------------
# Rules and overrides
# --------------------------------------------------------------------------

_PHYSICAL = ("pod", "data", "model")
_OVERRIDES: dict = {}


def set_rule_overrides(overrides: dict) -> None:
    """Install launch-time overrides: logical name -> physical axis spec.

    ``()`` drops the axis (resolves to None); a str or tuple of physical
    axes aliases it. Pass ``{}`` to clear.
    """
    _OVERRIDES.clear()
    _OVERRIDES.update(overrides)


def rules_for_mesh(mesh: Mesh) -> dict:
    """Default logical->physical rules for a mesh's axis names.

    Batch-like logical axes (dp/fsdp) map to the data axes — ("pod",
    "data") on a multi-pod mesh — and model-like axes (tp/sp) to "model".
    """
    names = tuple(mesh.axis_names)
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    data = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    model = "model" if "model" in names else None
    rules = {}
    for ax in ("dp", "fsdp"):
        if data is not None:
            rules[ax] = data
    for ax in ("tp", "sp"):
        if model is not None:
            rules[ax] = model
    return rules


def _resolve_entry(entry, rules):
    if entry is None:
        return None
    if isinstance(entry, str) and entry in _OVERRIDES:
        o = _OVERRIDES[entry]
        if o == () or o is None:
            return None
        return tuple(o) if isinstance(o, (tuple, list)) else o
    if isinstance(entry, str) and entry in rules:
        r = rules[entry]
        return tuple(r) if isinstance(r, (tuple, list)) else r
    # Already physical (str or tuple of physical axes): pass through.
    return tuple(entry) if isinstance(entry, (tuple, list)) else entry


def resolve_spec(spec: PS, rules: dict) -> PS:
    """Map every logical entry of ``spec`` through overrides then rules."""
    return PS(*(_resolve_entry(e, rules) for e in spec))


def _dedup_axes(spec: PS) -> PS:
    """Drop mesh axes already claimed by an earlier entry (jax requires
    each mesh axis to appear at most once in a spec)."""
    used: set = set()
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a not in used)
            used.update(kept)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(None if e in used else e)
            used.add(e)
    return PS(*out)


def _drop_missing(spec: PS, mesh: Mesh) -> PS:
    names = set(mesh.axis_names)
    out = []
    for e in spec:
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a in names)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(e if e in names else None)
    return PS(*out)


def resolve_tree(specs, mesh: Mesh):
    """Tree-map logical PartitionSpecs to NamedShardings on ``mesh``."""
    rules = rules_for_mesh(mesh)

    def one(spec):
        resolved = _drop_missing(_dedup_axes(resolve_spec(spec, rules)), mesh)
        return NamedSharding(mesh, resolved)

    return jax.tree.map(one, specs, is_leaf=lambda x: isinstance(x, PS))


def constraint(x: jax.Array, spec: PS) -> jax.Array:
    """Sharding-constrain ``x`` under the ambient mesh; no-op without one."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return x
    rules = rules_for_mesh(mesh)
    resolved = _drop_missing(_dedup_axes(resolve_spec(spec, rules)), mesh)
    # Trim trailing entries beyond the array rank (callers annotate with
    # the widest layout; decode-time tensors can be lower-rank).
    entries = tuple(resolved)[:x.ndim]
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PS(*entries)))
    except (ValueError, TypeError):  # abstract-mesh-only contexts
        return x
