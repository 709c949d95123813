"""Execution plans: per-layer dispatch decisions resolved once, not per call.

The seed repo dispatched every linear/conv through string-mode ``if/elif``
chains (``mode == "serve_packed"`` ...) plus a ``policy.lookup(layer_name)``
string match *inside every apply call*. A :class:`LayerPlan` hoists all of
that to conversion/compile time: the layer's kind, its resolved
(Pa, Pw), the packed-weight route, the conv geometry, and the dynamic-trim
group config are frozen into one record, and apply-time code branches on
``plan.route`` — a closed enum resolved exactly once per layer.

``build_plan(cfg, policy, mode, backend)`` produces the model-wide
:class:`ExecutionPlan`: a pytree-of-records keyed by layer name (LM layer
classes such as ``attn_q``/``ffn_up``, or CNN layer names such as
``conv1``/``fc0``), with lazy resolution for names that only appear at
apply time. The plan also owns the :class:`~repro.api.backend.Backend`,
subsuming the ``use_pallas``/``interpret`` flag pairs.
"""
from __future__ import annotations

import dataclasses

from repro.api.backend import Backend, resolve_backend
from repro.core.policy import LayerPrecision, PrecisionPolicy

# Routes: the closed set of execution strategies a layer can resolve to.
DENSE = "dense"              # bf16 matmul (DPNN-equivalent baseline)
FAKE_QUANT = "fake_quant"    # QAT STE fake-quant forward
INT8 = "int8"                # LM_8b: dynamic act quant + int8 weights
PACKED = "packed"            # paper-faithful bit-serial packed planes

# Execution-mode names (the public/serving vocabulary) -> routes.
MODE_ROUTES = {
    "dense": DENSE,
    "fake_quant": FAKE_QUANT,
    "serve_int8": INT8,
    "serve_packed": PACKED,
}

# Param-tree key -> apply-time layer-class name used by PrecisionPolicy.
# (Shared with models.model's serving conversion walk.)
PARAM_CLASS_NAMES = {"wq": "attn_q", "wk": "attn_k", "wv": "attn_v",
                     "wo": "attn_o", "w_gate": "ffn_gate", "w_up": "ffn_up",
                     "w_down": "ffn_down", "head": "lm_head",
                     "in_x": "ssm_x", "in_z": "ssm_z", "in_B": "ssm_B",
                     "in_C": "ssm_C", "in_dt": "ssm_dt", "out": "ssm_out"}

# Every linear layer class an LM architecture can route through.
LM_LINEAR_CLASSES = tuple(sorted(set(PARAM_CLASS_NAMES.values()))) + (
    "moe_expert", "moe_shared_gate", "moe_shared_up", "moe_shared_down")


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """Everything apply-time dispatch needs for ONE layer, resolved once.

    ``route`` is one of the module-level route constants. ``dynamic_a``
    enables runtime per-group activation-plane trimming on the PACKED
    route (the Lascorz OR-tree path): groups of ``group_size``
    concurrently-processed rows for linears, groups of ``group_size``
    output windows for convs. ``kernel``/``stride`` are conv geometry;
    ``conv_route`` picks the fused implicit-im2col lowering vs the legacy
    HBM-materializing one (A/B benchmarks only). ``conv_tile`` is the
    resolved output-rows-per-band of the banded conv kernel — filled in
    by :meth:`ExecutionPlan.conv_tile` from the layer's activation
    geometry (recorded in ``conv_tile_geom``; re-resolved if the
    geometry ever changes) and the backend's VMEM budget, never a
    hot-path kwarg.

    ``w_group`` / ``w_group_counts`` are the static per-filter-group
    weight-plane trimming metadata (the paper's Sec 4.6 groups of 16
    filters): the OR-tree effective plane count per group of ``w_group``
    output columns, computed ONCE at pack time
    (:meth:`ExecutionPlan.record_weight_groups`) and frozen here as a
    tuple of Python ints — static, so the XLA routes can partition
    columns by count at trace time and the Pallas kernels take them as
    scalar-prefetch constants. ``None`` = untrimmed (no pack-time
    counts recorded).
    """

    name: str
    kind: str                      # "linear" | "conv"
    route: str                     # DENSE | FAKE_QUANT | INT8 | PACKED
    precision: LayerPrecision = LayerPrecision()
    dynamic_a: bool = False
    group_size: int = 256
    kernel: int | None = None
    stride: int | None = None
    conv_route: str = "fused"      # "fused" | "im2col"
    conv_tile: int | None = None   # rows per band; None = not yet resolved
    conv_tile_geom: tuple | None = None   # (h, w, c, n, w_bits) it fits
    w_group: int = 16              # filter-group size for weight trimming
    w_group_counts: tuple | None = None   # per-group plane counts (ints)

    @property
    def a_bits(self) -> int:
        return self.precision.a_bits

    @property
    def w_bits(self) -> int:
        return self.precision.w_bits


@dataclasses.dataclass
class ExecutionPlan:
    """Model-wide execution plan: resolved LayerPlans + the backend.

    ``layers`` maps ``(name, kind)`` to a resolved :class:`LayerPlan`;
    names not pre-resolved by :func:`build_plan` (e.g. ad-hoc layer names
    in examples) resolve lazily on first use and are memoized, so policy
    string matching happens at most once per layer, never per call.

    ``mode`` and ``policy`` stay readable attributes (the serving
    conversion walk keys off ``mode``); apply-time code should only touch
    ``layer()``, ``conv_tile()`` and ``backend``.
    """

    mode: str
    policy: PrecisionPolicy
    backend: Backend
    conv_route: str = "fused"
    layers: dict = dataclasses.field(default_factory=dict)

    def layer(self, name: str = "", kind: str = "linear",
              kernel: int | None = None, stride: int | None = None
              ) -> LayerPlan:
        key = (name, kind)
        lp = self.layers.get(key)
        if lp is None:
            lp = self._resolve(name, kind, kernel, stride)
            self.layers[key] = lp
        elif kernel is not None:
            if lp.kernel is None:
                # Resolved before the geometry was known (e.g. via
                # introspection on a lazy plan): fill it in, once.
                lp = dataclasses.replace(lp, kernel=kernel, stride=stride)
                self.layers[key] = lp
            elif (lp.kernel, lp.stride) != (kernel, stride):
                raise ValueError(
                    f"layer {name!r} resolved with conv geometry "
                    f"{(lp.kernel, lp.stride)} but called with "
                    f"{(kernel, stride)}")
        return lp

    def conv_tile(self, lp: LayerPlan, h: int, w: int, c: int, n: int,
                  w_bits: int) -> int:
        """Rows-per-band of the banded conv kernel for layer ``lp``.

        Resolved from the layer's activation geometry and the backend's
        VMEM budget (:func:`conv_rows_per_band`), then frozen into the
        stored LayerPlan keyed to that geometry — apply-time calls with
        the same shapes (the steady state: a layer's geometry is fixed
        per model) just read it back. A DIFFERENT geometry re-runs the
        budget check: a tile sized for a small map is numerically fine on
        a big one (banding never changes results) but could bust the
        VMEM budget, which is the one guarantee this resolver owns.
        """
        geom = (h, w, c, n, w_bits)
        if lp.conv_tile is not None and lp.conv_tile_geom == geom:
            return lp.conv_tile
        rpb = conv_rows_per_band(h, w, c, n, kernel=lp.kernel,
                                 stride=lp.stride, w_bits=w_bits,
                                 budget=self.backend.vmem_budget)
        self.layers[(lp.name, lp.kind)] = dataclasses.replace(
            lp, conv_tile=rpb, conv_tile_geom=geom)
        return rpb

    def fallback_report(self) -> dict:
        """Which ops degraded off the primary backend, and to where.

        The plan owns the backend, so backend fallbacks ARE plan state:
        a :class:`~repro.api.backend.GuardedBackend` records every sticky
        per-op fallback in ``fallbacks_by_op`` and this accessor exposes
        it (``{}`` for unguarded backends / the fault-free path). A layer
        whose op fell back stays fallen back for the plan's lifetime.
        """
        return dict(getattr(self.backend, "fallbacks_by_op", {}))

    def record_weight_groups(self, named_params: dict) -> None:
        """Freeze pack-time per-filter-group weight plane counts into plans.

        ``named_params`` maps layer names to their PACKED param dicts
        (``{"w_packed": uint8 [Pw, K/8, N], ...}``). For every resolved
        layer with a matching packed tensor the OR-tree counts
        (``core.weightgroups.weight_group_counts``) are computed ONCE,
        eagerly, and stored as a tuple of Python ints on the LayerPlan —
        the only place hot-path dispatch reads them from. Must be called
        with concrete arrays (after real conversion, outside jit /
        eval_shape); a no-op when ``policy.w_group`` is 0.
        """
        import numpy as np

        from repro.core import weightgroups
        if not getattr(self.policy, "w_group", 0):
            return
        memo = {}   # (name, w_group) -> counts: conv layers also carry a
        #             legacy im2col "linear" twin over the SAME tensor
        for (name, kind), lp in list(self.layers.items()):
            p = named_params.get(name)
            if not isinstance(p, dict):
                continue
            wp = p.get("w_packed")
            if wp is None or getattr(wp, "ndim", 0) != 3:
                continue
            counts = memo.get((name, lp.w_group))
            if counts is None:
                counts = tuple(int(v) for v in np.asarray(
                    weightgroups.packed_group_counts(wp, wp.shape[0],
                                                     lp.w_group)))
                memo[(name, lp.w_group)] = counts
            self.set_weight_counts(name, kind, counts)

    def set_weight_counts(self, name: str, kind: str, counts,
                          w_group: int | None = None) -> LayerPlan:
        """Attach per-filter-group plane counts to one resolved layer."""
        lp = self.layers[(name, kind)]
        lp = dataclasses.replace(
            lp, w_group_counts=tuple(int(c) for c in counts),
            w_group=lp.w_group if w_group is None else w_group)
        self.layers[(name, kind)] = lp
        return lp

    def _resolve(self, name, kind, kernel=None, stride=None) -> LayerPlan:
        try:
            route = MODE_ROUTES[self.mode]
        except KeyError:
            raise ValueError(f"unknown execution mode {self.mode!r}; "
                             f"expected one of {sorted(MODE_ROUTES)}") from None
        return LayerPlan(
            name=name, kind=kind, route=route,
            precision=self.policy.lookup(name),
            dynamic_a=self.policy.dynamic_a,
            group_size=self.policy.group_size,
            w_group=getattr(self.policy, "w_group", 16) or 16,
            kernel=kernel, stride=stride, conv_route=self.conv_route)


def conv_rows_per_band(h: int, w: int, c: int, n: int, *, kernel: int,
                       stride: int, w_bits: int,
                       budget: int | None) -> int:
    """VMEM-budget heuristic for the banded conv kernel's band size.

    Starts from one band covering the whole map and halves the band until
    the modeled per-grid-step footprint
    (:func:`repro.kernels.bitserial_conv.conv_vmem_bytes`) fits
    ``budget``. ``budget=None`` (backends with no VMEM, e.g. XLA) keeps
    the single band. Deterministic and monotone in the budget; floors at
    one output row per band (best effort when even that exceeds the
    budget — e.g. an enormous width).
    """
    from repro.kernels.bitserial_conv import conv_vmem_bytes
    ho = -(-h // stride)
    rpb = ho
    if budget is None:
        return rpb
    while rpb > 1 and conv_vmem_bytes(h, w, c, n, kernel=kernel,
                                      stride=stride, w_bits=w_bits,
                                      rows_per_band=rpb) > budget:
        rpb = -(-rpb // 2)
    return rpb


def build_plan(cfg, policy: PrecisionPolicy | None = None,
               mode: str = "dense", backend="xla",
               conv_route: str = "fused") -> ExecutionPlan:
    """Compile the per-layer plans for a model config.

    ``cfg`` may be a ``models.transformer.ModelConfig`` (pre-resolves the
    LM linear classes), a ``models.cnn.CNNConfig`` (pre-resolves each conv
    with its kernel/stride plus the FC head), or None (everything lazy).
    ``backend`` is a Backend object or registered name.
    """
    policy = policy if policy is not None else PrecisionPolicy()
    plan = ExecutionPlan(mode=mode, policy=policy,
                         backend=resolve_backend(backend),
                         conv_route=conv_route)
    if cfg is None:
        return plan
    if hasattr(cfg, "convs"):            # CNNConfig
        for c in cfg.convs:
            plan.layer(c.name, kind="conv", kernel=c.kernel, stride=c.stride)
            plan.layer(c.name, kind="linear")   # legacy im2col A/B route
        for i in range(len(cfg.fcs)):
            plan.layer(f"fc{i}", kind="linear")
    elif hasattr(cfg, "pattern"):        # ModelConfig
        for cls in LM_LINEAR_CLASSES:
            plan.layer(cls, kind="linear")
    return plan


def as_plan(obj) -> ExecutionPlan:
    """Validate that ``obj`` is an :class:`ExecutionPlan`.

    The deprecated string-mode shim this used to coerce was retired;
    build plans with :func:`build_plan` (or ``loom.compile`` for serving).
    """
    if isinstance(obj, ExecutionPlan):
        return obj
    raise TypeError(f"expected ExecutionPlan, got {obj!r} — the legacy "
                    f"config shim was removed; use repro.api.build_plan")
