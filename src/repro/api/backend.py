"""Backend registry: one object per kernel substrate, one uniform op surface.

A :class:`Backend` owns the *lowering* decision that used to be threaded
through every signature in ``kernels/ops.py`` and ``models/layers.py`` as
``use_pallas``/``interpret`` boolean pairs. Model code never chooses a
kernel again — it asks its :class:`~repro.api.plan.LayerPlan` for the
backend and calls one of five ops:

    matmul_planes          static bit-serial matmul over packed planes
    matmul_planes_dynamic  plane-count-gated variant (runtime trimming)
    conv_planes            fused bit-serial convolution
    conv_planes_dynamic    conv with runtime per-window-group activation
                           plane trimming (counts from the OR-tree)
    dynamic_quant          per-group activation quantization + OR-tree bits
    attention              full-sequence attention

Built-ins:

    xla              pure-XLA oracle paths (CPU dry-run / fallback)
    pallas_interpret Pallas kernels under interpret=True (CPU validation)
    pallas_tpu       Pallas kernels compiled by Mosaic (real TPU)

The four plane ops take ``name``: the kernel's name in the compiled
program (``bitserial_matmul_attn_q``), so a profiler trace attributes
each kernel to its layer. The XLA oracle has no kernel and ignores it;
None keeps the kernel's own name.

``register_backend`` admits out-of-tree substrates (a future Triton or
CUDA port) without touching model code: implement the five ops, register
under a name, pass ``backend="yourname"`` to ``loom.compile``.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import guards
from repro.core.weightgroups import (truncate_columns_grouped,
                                     truncate_signed as _truncate_signed)
from repro.kernels import ref
from repro.kernels.bitserial_conv import (bitserial_conv,
                                          bitserial_conv_dynamic,
                                          bitserial_conv_wgroup)
from repro.kernels.bitserial_matmul import (bitserial_matmul,
                                            bitserial_matmul_dynamic)
from repro.kernels.dynamic_quant import dynamic_quant
from repro.kernels.flash_attention import flash_attention


def _pallas_blocks(m: int, n: int, k: int) -> tuple[int, int, int]:
    """MXU-default block shape, shrunk to divisors for small/odd operands.

    The kernels assert dim % block == 0; the 128/128/512 defaults only fit
    MXU-aligned shapes, so fall back to the full dim when it doesn't divide
    (interpret-mode correctness never depends on the block shape)."""
    bm = 128 if m % 128 == 0 else m
    bn = 128 if n % 128 == 0 else n
    bk = 512 if k % 512 == 0 else k
    return bm, bn, bk


# _truncate_signed (imported above): 2's-complement truncation at a
# per-element width — the ONE group-mask idiom every trimming route
# (dynamic linear column groups, dynamic conv window groups, static
# weight filter groups) realizes; canonical home: core.weightgroups.


def _wgroup_partitions(w_counts, w_group: int, n: int):
    """Trace-time partition of the N output columns by plane count.

    ``w_counts`` are pack-time Python ints (``LayerPlan.w_group_counts``),
    so this runs at trace time: returns ``[(count, cols)]`` with the
    column indices of every group sharing that count (ragged last group
    covers only its real columns), plus the inverse permutation that
    restores column order after the per-partition results are
    concatenated. This is what turns static sub-layer weight precision
    into DELETED work on the XLA backend — each partition executes only
    its count's worth of planes/precision — instead of a runtime mask.
    """
    assert len(w_counts) == -(-n // w_group), (len(w_counts), n, w_group)
    by_count: dict[int, list] = {}
    for g, c in enumerate(w_counts):
        by_count.setdefault(int(c), []).extend(
            range(g * w_group, min((g + 1) * w_group, n)))
    parts = [(c, np.asarray(cols, np.int64))
             for c, cols in sorted(by_count.items())]
    order = np.concatenate([cols for _, cols in parts])
    inv = np.empty(n, np.int64)
    inv[order] = np.arange(n)
    return parts, inv


class Backend:
    """XLA oracle backend — also the base class of the Pallas backends."""

    name = "xla"
    use_pallas = False      # legacy introspection (backend resolution)
    interpret = True
    # Per-grid-step VMEM budget (bytes) the banded conv kernel's tile
    # heuristic (repro.api.plan.conv_rows_per_band) targets. None = no
    # VMEM constraint (XLA lowers through HBM-resident convs).
    vmem_budget: int | None = None

    def matmul_planes(self, xq: jax.Array, w_packed: jax.Array, *,
                      w_bits: int, a_bits: int = 8, w_counts=None,
                      w_group: int = 16,
                      name: str | None = None) -> jax.Array:
        """int8 [M, K] @ packed uint8 [Pw, K//8, N] -> exact int32 [M, N].

        ``w_counts`` (pack-time per-filter-group plane counts, Python
        ints from ``LayerPlan.w_group_counts``; ``w_group`` columns per
        group) enables STATIC weight-plane trimming: the N columns are
        partitioned by count at trace time and each partition unpacks
        and multiplies only its ``count`` planes (2's-complement
        truncation at that width — value-preserving for OR-tree counts).
        Low-count partitions additionally qualify for the exact-f32 GEMM
        fast path (every partial sum fits a float32 mantissa once the
        weight width shrinks), which is where the measured XLA wall-clock
        win comes from — work is deleted at trace time, not masked.
        """
        if w_counts is None or all(c >= w_bits for c in w_counts):
            return ref.bitserial_matmul_ref(xq, w_packed, w_bits)
        from repro.core import bitpack
        from repro.kernels.ops import conv_accum_fits_f32
        k8 = w_packed.shape[1] * 8
        parts, inv = _wgroup_partitions(w_counts, w_group,
                                        w_packed.shape[-1])
        outs = []
        for c, cols in parts:
            wq_c = bitpack.unpack_weights(w_packed[:c][:, :, cols], c)
            if conv_accum_fits_f32(k8, a_bits, c):
                outs.append(jnp.matmul(
                    xq.astype(jnp.float32),
                    wq_c.astype(jnp.float32)).astype(jnp.int32))
            else:
                outs.append(jnp.matmul(xq.astype(jnp.int32), wq_c,
                                       preferred_element_type=jnp.int32))
        return jnp.take(jnp.concatenate(outs, axis=-1), inv, axis=-1)

    def matmul_planes_dynamic(self, xq: jax.Array, w_packed: jax.Array,
                              plane_counts: jax.Array, *, w_bits: int,
                              bn: int, name: str | None = None) -> jax.Array:
        """Like matmul_planes but N-tile j executes only plane_counts[j]
        planes of the packed operand (2's complement at the effective
        width). ``bn`` is the N-tile width one count covers.

        Production XLA route (the linear twin of the conv group mask):
        instead of materializing all w_bits plane tensors and the
        truncating per-plane sum (the oracle,
        ref.bitserial_matmul_dynamic_ref, does that), the unpacked
        operand is truncated per COLUMN GROUP with one arithmetic mask —
        keep the low ``count`` bits, reinterpret signed at that width —
        then a single int32 matmul runs. In the dynamic serving linear
        the packed operand is the runtime-packed ACTIVATIONS of the
        transposed matmul, so this is the CPU/GPU fallback that trims
        without a Pa-plane stack.
        """
        from repro.core import bitpack
        wq = bitpack.unpack_weights(w_packed, w_bits)   # signed int32 [K, N]
        counts = jnp.repeat(plane_counts, bn)[None, :]  # [1, N] per-col width
        return jnp.matmul(xq.astype(jnp.int32), _truncate_signed(wq, counts),
                          preferred_element_type=jnp.int32)

    def conv_planes(self, xq: jax.Array, w_packed: jax.Array, *, kernel: int,
                    stride: int, w_bits: int, a_bits: int,
                    conv_tile: int | None = None, w_counts=None,
                    w_group: int = 16, name: str | None = None) -> jax.Array:
        """Fused bit-serial "same" conv: int [B,H,W,C] x packed planes ->
        exact int32 [B, Ho, Wo, N]. No im2col patch tensor in HBM.
        ``conv_tile`` (rows per band) only matters to VMEM-constrained
        backends; the XLA lowering ignores it.

        ``w_counts``/``w_group``: static per-filter-group weight-plane
        trimming — output filters are partitioned by their pack-time
        plane count at trace time and each partition runs its own
        shift-and-matmul window walk at that count's precision (the
        exact-f32 GEMM fast path engages per partition once the
        accumulator fits a float32 mantissa at the reduced weight
        width). Bit-identical to the untrimmed path for OR-tree counts.
        """
        from repro.core import bitpack
        from repro.kernels import ops
        c = xq.shape[-1]
        kkc = kernel * kernel * c
        if w_counts is None or all(cc >= w_bits for cc in w_counts):
            wq = bitpack.unpack_weights(w_packed, w_bits, k=kkc)
            return ops.int_conv_same(
                xq, wq.reshape(kernel, kernel, c, -1), stride,
                exact_f32=ops.conv_accum_fits_f32(kkc, a_bits, w_bits))
        parts, inv = _wgroup_partitions(w_counts, w_group,
                                        w_packed.shape[-1])
        outs = []
        for cnt, cols in parts:
            wq_c = bitpack.unpack_weights(w_packed[:cnt][:, :, cols], cnt,
                                          k=kkc)
            outs.append(ops.int_conv_same(
                xq, wq_c.reshape(kernel, kernel, c, -1), stride,
                exact_f32=ops.conv_accum_fits_f32(kkc, a_bits, cnt)))
        return jnp.take(jnp.concatenate(outs, axis=-1), inv, axis=-1)

    def conv_planes_dynamic(self, xq: jax.Array, w_packed: jax.Array,
                            counts: jax.Array, *, kernel: int, stride: int,
                            w_bits: int, a_bits: int, group_size: int,
                            w_counts=None, w_group: int = 16,
                            name: str | None = None) -> jax.Array:
        """Like conv_planes but each group of ``group_size`` output windows
        executes only counts[b, g] serial activation planes.

        Production XLA route: instead of materializing all Pa activation
        plane tensors (the truncating oracle, ref.bitserial_conv_dynamic_ref
        does that), every window's activations are truncated to the
        group's effective width with ONE arithmetic GROUP-LEVEL mask —
        keep the low ``count`` bits, reinterpret signed at that width —
        fused into the k*k shift-and-matmul window walk, so no Pa-plane
        stack and no im2col patch tensor exist on this path either.

        ``w_counts``/``w_group`` compose static weight-group trimming in:
        the weights are truncated per filter group at their pack-time
        effective width (the same mask idiom on the other operand) —
        value-preserving for OR-tree counts, so the composed result stays
        bit-identical to the static conv; the modeled pass count becomes
        mean_Pa_eff x mean_Pw_eff over the group intersections.
        """
        from repro.core import bitpack
        c = xq.shape[-1]
        kkc = kernel * kernel * c
        wq = bitpack.unpack_weights(w_packed, w_bits, k=kkc)
        if w_counts is not None:
            wq = truncate_columns_grouped(wq, w_counts, w_group)
        w2 = wq.reshape(kernel * kernel, c, -1)
        b, h, w_, _ = xq.shape
        pad = kernel // 2
        ho, wo = -(-h // stride), -(-w_ // stride)
        # Per-window effective width, [B, Ho, Wo, 1] (row-major groups).
        cmap = jnp.repeat(counts, group_size, axis=1)[:, :ho * wo]
        cmap = cmap.reshape(b, ho, wo, 1)
        xp = jnp.pad(xq.astype(jnp.int32),
                     ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        acc = jnp.zeros((b, ho, wo, w2.shape[-1]), jnp.int32)
        slices = ref.conv_window_slices(xp, kernel, stride, ho, wo)
        for sl, wslab in zip(slices, w2):
            acc = acc + jax.lax.dot_general(
                _truncate_signed(sl, cmap), wslab,
                dimension_numbers=(((3,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        return acc

    def dynamic_quant(self, x2: jax.Array, *, group_size: int,
                      bits: int) -> tuple:
        """f32 [M, K] -> (xq int8, per-group scale, per-group eff bits)."""
        return ref.dynamic_quant_ref(x2, group_size, bits)

    def attention(self, q_: jax.Array, k_: jax.Array, v_: jax.Array, *,
                  causal: bool = True, window: int | None = None) -> jax.Array:
        return ref.flash_attention_ref(q_, k_, v_, causal=causal,
                                       window=window)

    def __repr__(self):
        return f"<Backend {self.name}>"


# 16 MiB of physical VMEM per TensorCore, kept at 3/4 utilization so the
# pipelined grid can double-buffer the band + weight blocks.
_VMEM_BUDGET = 12 * 2 ** 20


class PallasBackend(Backend):
    """Mosaic kernels; ``interpret=True`` runs them on CPU for validation."""

    use_pallas = True

    def __init__(self, name: str, interpret: bool,
                 vmem_budget: int = _VMEM_BUDGET):
        self.name = name
        self.interpret = interpret
        self.vmem_budget = vmem_budget

    def matmul_planes(self, xq, w_packed, *, w_bits, a_bits=8, w_counts=None,
                      w_group=16, name=None):
        m, k = xq.shape
        n = w_packed.shape[-1]
        # All-full counts (nothing trimmable, e.g. random-init weights on
        # the per-tensor scale) keep the tuned static kernel — same
        # no-op guard as the XLA route, without which every default
        # serving session would pay the bn=w_group tile shrink for zero
        # skipped planes.
        if w_counts is None or all(c >= w_bits for c in w_counts):
            bm, bn, bk = _pallas_blocks(m, n, k)
            return bitserial_matmul(xq, w_packed, w_bits=w_bits, bm=bm,
                                    bn=bn, bk=bk, interpret=self.interpret,
                                    name=name)
        # Static weight-group trimming reuses the dynamic-precision kernel:
        # the packed operand here IS the weights and the counts are the
        # pack-time constants from the plan, one per group of w_group
        # filters on 128-wide N-tiles — pl.when skips whole
        # (plane x N-tile) grid steps above the tile's largest count, so
        # on TPU the dead planes' tiles are never fetched from HBM. Ragged
        # last group: pad N with zero columns (they fit any count), slice
        # the result back.
        npad = (-n) % w_group
        wp = jnp.pad(w_packed, ((0, 0), (0, 0), (0, npad))) if npad \
            else w_packed
        bm, bn, bk = _pallas_blocks(m, n + npad, k)
        y = bitserial_matmul_dynamic(
            xq, wp, jnp.asarray(w_counts, jnp.int32), w_bits=w_bits,
            group=w_group, bm=bm, bn=bn, bk=bk, interpret=self.interpret,
            name=name)
        return y[:, :n] if npad else y

    def matmul_planes_dynamic(self, xq, w_packed, plane_counts, *, w_bits,
                              bn, name=None):
        m, k = xq.shape
        n = w_packed.shape[-1]
        bm, _, bk = _pallas_blocks(m, n, k)
        return bitserial_matmul_dynamic(xq, w_packed, plane_counts,
                                        w_bits=w_bits, bm=bm, bn=bn, bk=bk,
                                        interpret=self.interpret, name=name)

    def conv_planes(self, xq, w_packed, *, kernel, stride, w_bits, a_bits,
                    conv_tile=None, w_counts=None, w_group=16, name=None):
        # Same all-full-counts no-op guard as matmul_planes: untrimmable
        # counts stay on the static kernel (one patch assembly per
        # band/N-tile at bn=128, plane loop unrolled in-body).
        if w_counts is None or all(c >= w_bits for c in w_counts):
            return bitserial_conv(xq.astype(jnp.int8), w_packed,
                                  kernel=kernel, stride=stride,
                                  w_bits=w_bits, rows_per_band=conv_tile,
                                  interpret=self.interpret, name=name)
        # Static weight-group trimming: the wgroup kernel's grid gains the
        # serial weight-plane axis, gated per N-tile by the pack-time
        # scalar-prefetch counts. Ragged last group: pad N with zero
        # columns (they fit any count), slice the result back.
        n = w_packed.shape[-1]
        npad = (-n) % w_group
        wp = jnp.pad(w_packed, ((0, 0), (0, 0), (0, npad))) if npad \
            else w_packed
        y = bitserial_conv_wgroup(
            xq.astype(jnp.int8), wp, jnp.asarray(w_counts, jnp.int32),
            kernel=kernel, stride=stride, w_bits=w_bits, w_group=w_group,
            bn=_pallas_blocks(1, n + npad, 1)[1], rows_per_band=conv_tile,
            interpret=self.interpret, name=name)
        return y[..., :n] if npad else y

    def conv_planes_dynamic(self, xq, w_packed, counts, *, kernel, stride,
                            w_bits, a_bits, group_size, w_counts=None,
                            w_group=16, name=None):
        # Activations are the plane-serial operand here; weights ride as
        # dense int8 MXU passes. Pw > 8 splits into 7-bit int8-safe
        # subplanes whose shifted partials accumulate exactly (the same
        # decomposition as the dynamic linear path in kernels/ops.py).
        # Composed static weight-group trimming truncates the dense
        # operand per filter group at its pack-time width before the
        # split — value-preserving for OR-tree counts (bit-identical
        # composition), truncating-oracle semantics otherwise.
        from repro.core import bitpack, quantize as q
        wq = bitpack.unpack_weights(w_packed, w_bits)       # [K8, N] int32
        if w_counts is not None:
            wq = truncate_columns_grouped(wq, w_counts, w_group)
        if w_bits <= 8:
            w_planes, shifts = wq[None], jnp.ones((1,), jnp.int32)
        else:
            w_planes, shifts = q.group_planes(wq, w_bits, 7)
        y = None
        for i in range(w_planes.shape[0]):
            part = bitserial_conv_dynamic(
                xq.astype(jnp.int8), w_planes[i].astype(jnp.int8), counts,
                kernel=kernel, stride=stride, a_bits=a_bits,
                group_size=group_size, interpret=self.interpret, name=name)
            part = part * shifts[i]
            y = part if y is None else y + part
        return y

    def dynamic_quant(self, x2, *, group_size, bits):
        return dynamic_quant(x2, group_size=group_size, bits=bits,
                             interpret=self.interpret)

    def attention(self, q_, k_, v_, *, causal=True, window=None):
        return flash_attention(q_, k_, v_, causal=causal, window=window,
                               interpret=self.interpret)


# -- Guarded dispatch -------------------------------------------------------

def default_fallback_chain() -> tuple[str, ...]:
    """Degradation order: fastest substrate first, the always-works XLA
    oracle last. A GuardedBackend's chain is the suffix of this order
    starting after its inner backend (an unknown/out-of-tree inner falls
    straight to the built-ins). On a TPU the Pallas interpreter is never
    a recovery — it runs the kernel on the host, orders of magnitude
    slower — so a kernel Mosaic refuses falls straight to XLA there."""
    if jax.default_backend() == "tpu":
        return ("pallas_tpu", "xla")
    return ("pallas_tpu", "pallas_interpret", "xla")

# The uniform op surface a Backend exposes (= what a GuardedBackend guards).
BACKEND_OPS = ("matmul_planes", "matmul_planes_dynamic", "conv_planes",
               "conv_planes_dynamic", "dynamic_quant", "attention")


def _silent_corrupt(out):
    """``backend.silent_corrupt`` fault effect: wrong-but-finite values.

    Reverses the last axis of the op's (primary) output — shape- and
    dtype-preserving, deterministic, and guaranteed to change downstream
    argmax decisions, but raising nothing and producing no NaN/Inf: the
    corruption every loud guard is blind to. Works on tracers, so a
    corruption injected before compile bakes into the jit cache exactly
    like a silently-miscompiled kernel would."""
    def flip(x):
        return jnp.flip(x, axis=-1)
    if isinstance(out, tuple):
        return (flip(out[0]),) + tuple(out[1:])
    return flip(out)


class GuardedBackend(Backend):
    """Fault-classifying wrapper: fallback chain + numeric-integrity guards.

    Wraps any registered backend. Every op dispatch:

    1. runs the *numeric-integrity prechecks* — operand-shape coherence
       against the packed layout and the accumulator-overflow bound
       recomputed from the ACTUAL (Pa, Pw, K) of the operands (typed
       :class:`repro.api.guards.AccumulatorOverflowError` /
       ``BackendShapeError``; these fail loudly rather than fall back,
       because every chain member shares the same int32 accumulator);
    2. fires the ``backend.op`` fault point (chaos testing);
    3. delegates to the innermost non-failed backend in the chain. A
       non-transient failure (compile / resource / shape / unknown, per
       :func:`repro.api.guards.classify_error`) degrades the op to the
       next chain member with a one-line warning, and the op STAYS
       fallen back (sticky per op — recorded in ``fallbacks_by_op``,
       readable through the owning plan's ``fallback_report()``).
       Transient failures re-raise unchanged: the serving supervisor owns
       the retry, and the substrate is not the problem.

    Bit-transparency contract: on the fault-free path every op returns
    the inner backend's result unchanged — guarded serving is
    byte-identical to unguarded serving (CI's serve-smoke invariant).
    """

    def __init__(self, inner, chain=None):
        inner = resolve_backend(inner)
        self.inner = inner
        self.name = f"guarded:{inner.name}"
        self.use_pallas = inner.use_pallas
        self.interpret = inner.interpret
        self.vmem_budget = inner.vmem_budget
        if chain is None:
            names = list(default_fallback_chain())
            if inner.name in names:
                names = names[names.index(inner.name) + 1:]
            chain = [get_backend(n) for n in names]
        else:
            chain = [resolve_backend(b) for b in chain]
        self.chain: list[Backend] = [inner] + [b for b in chain
                                               if b is not inner]
        self.fallbacks_by_op: dict[str, str] = {}   # op -> serving backend
        self._active_idx: dict[str, int] = {}

    def __repr__(self):
        return (f"<GuardedBackend {self.inner.name} "
                f"chain={[b.name for b in self.chain[1:]]} "
                f"fallbacks={self.fallbacks_by_op}>")

    def active_backend(self, op: str) -> Backend:
        """The chain member currently serving ``op``."""
        return self.chain[self._active_idx.get(op, 0)]

    def quarantine(self, reason: str = "") -> int:
        """Sticky-demote EVERY op one chain member past its current
        substrate (the shadow auditor's response to a silent divergence:
        the active backend returned wrong-but-finite values, so no single
        op can be trusted and no error classification exists to react
        to). Reuses the same per-op sticky state as fault-driven
        fallback — ``fallback_report()`` shows the quarantine. Returns
        the number of ops demoted (0 = chain already exhausted)."""
        n = 0
        for op in BACKEND_OPS:
            i = self._active_idx.get(op, 0)
            if i + 1 < len(self.chain):
                nxt = self.chain[i + 1]
                self._active_idx[op] = i + 1
                self.fallbacks_by_op[op] = nxt.name
                n += 1
        if n:
            warnings.warn(
                f"[guarded] QUARANTINE: {self.chain[0].name!r} demoted for "
                f"all ops ({reason or 'silent divergence'}) — serving "
                f"continues on the fallback chain (sticky)",
                RuntimeWarning, stacklevel=3)
        return n

    def _dispatch(self, op: str, *args, **kwargs):
        from repro.runtime import faults
        start = self._active_idx.get(op, 0)
        last_exc = None
        for i in range(start, len(self.chain)):
            b = self.chain[i]
            try:
                faults.fire("backend.op", detail=f"{op}:{b.name}")
                out = getattr(b, op)(*args, **kwargs)
                if faults.take("backend.silent_corrupt",
                               detail=f"{op}:{b.name}"):
                    out = _silent_corrupt(out)
                return out
            except Exception as exc:  # noqa: BLE001 — classified below
                kind = guards.classify_error(exc)
                if kind == guards.TRANSIENT:
                    raise   # substrate is fine; the supervisor retries
                last_exc = exc
                if i + 1 < len(self.chain):
                    nxt = self.chain[i + 1]
                    warnings.warn(
                        f"[guarded] {op}: backend {b.name!r} failed "
                        f"({kind}: {exc}) — falling back to {nxt.name!r} "
                        f"(sticky)", RuntimeWarning, stacklevel=3)
                    self._active_idx[op] = i + 1
                    self.fallbacks_by_op[op] = nxt.name
        raise guards.FallbackExhaustedError(
            f"{op}: every backend in the fallback chain "
            f"{[b.name for b in self.chain]} failed") from last_exc

    @staticmethod
    def _check_packed_k(k_logical: int, w_packed, op: str) -> int:
        """Packed-layout coherence: the packed K dim must be the logical
        reduction length rounded up to the 8-row pack quantum."""
        k8 = int(w_packed.shape[1]) * 8
        if not 0 <= k8 - k_logical < 8:
            raise guards.BackendShapeError(
                f"{op}: packed operand covers K={k8} but the logical "
                f"reduction length is {k_logical} (pad quantum is 8 rows) "
                f"— operands are incoherent")
        return k8

    @staticmethod
    def _check_w_counts(w_counts, w_group: int, n: int, w_bits: int,
                        op: str) -> None:
        """Pass-law precheck on the static weight-group counts: one count
        per group of ``w_group`` output columns (sum(Pw_counts) is the
        weight factor of Loom's pass law), every count in [1, w_bits].
        A violation means corrupt plan metadata — the dispatch would
        execute the wrong plane partitions, silently."""
        if w_counts is None:
            return
        want = -(-n // w_group)
        if len(w_counts) != want:
            raise guards.BackendShapeError(
                f"{op}: {len(w_counts)} weight-group counts for N={n} at "
                f"w_group={w_group} (pass law needs {want} groups) — "
                f"operands and plan metadata are incoherent")
        bad = sorted({int(c) for c in w_counts if not 1 <= int(c) <= w_bits})
        if bad:
            raise guards.WeightIntegrityError(
                f"{op}: weight-group plane counts {bad} outside "
                f"[1, {w_bits}] — corrupt pass-law metadata; refusing to "
                f"dispatch wrong plane partitions")

    @staticmethod
    def _check_plane_counts(counts, bits: int, op: str) -> None:
        """Bounds check on runtime (OR-tree) plane counts — concrete
        arrays only: inside a jit trace the check is a structural no-op,
        so guarded tracing stays bit-transparent."""
        if isinstance(counts, jax.core.Tracer):
            return
        arr = np.asarray(counts)
        if arr.size and (int(arr.min()) < 1 or int(arr.max()) > bits):
            raise guards.WeightIntegrityError(
                f"{op}: runtime plane counts span "
                f"[{int(arr.min())}, {int(arr.max())}] outside the legal "
                f"[1, {bits}] — the OR-tree output is corrupt")

    # -- guarded op surface -------------------------------------------------

    def matmul_planes(self, xq, w_packed, *, w_bits, a_bits=8, w_counts=None,
                      w_group=16, name=None):
        k8 = self._check_packed_k(int(xq.shape[-1]), w_packed,
                                  "matmul_planes")
        guards.check_accum_bound(k8, a_bits, w_bits, "matmul_planes")
        self._check_w_counts(w_counts, w_group, int(w_packed.shape[-1]),
                             w_bits, "matmul_planes")
        return self._dispatch("matmul_planes", xq, w_packed, w_bits=w_bits,
                              a_bits=a_bits, w_counts=w_counts,
                              w_group=w_group, name=name)

    def matmul_planes_dynamic(self, xq, w_packed, plane_counts, *, w_bits,
                              bn, name=None):
        # Dense operand rides int8 passes (<= 8 magnitude bits) on every
        # caller; the packed operand carries w_bits planes.
        k8 = self._check_packed_k(int(xq.shape[-1]), w_packed,
                                  "matmul_planes_dynamic")
        guards.check_accum_bound(k8, 8, w_bits, "matmul_planes_dynamic")
        self._check_plane_counts(plane_counts, w_bits,
                                 "matmul_planes_dynamic")
        return self._dispatch("matmul_planes_dynamic", xq, w_packed,
                              plane_counts, w_bits=w_bits, bn=bn, name=name)

    def conv_planes(self, xq, w_packed, *, kernel, stride, w_bits, a_bits,
                    conv_tile=None, w_counts=None, w_group=16, name=None):
        kkc = kernel * kernel * int(xq.shape[-1])
        self._check_packed_k(kkc, w_packed, "conv_planes")
        guards.check_accum_bound(kkc, a_bits, w_bits, "conv_planes")
        self._check_w_counts(w_counts, w_group, int(w_packed.shape[-1]),
                             w_bits, "conv_planes")
        return self._dispatch("conv_planes", xq, w_packed, kernel=kernel,
                              stride=stride, w_bits=w_bits, a_bits=a_bits,
                              conv_tile=conv_tile, w_counts=w_counts,
                              w_group=w_group, name=name)

    def conv_planes_dynamic(self, xq, w_packed, counts, *, kernel, stride,
                            w_bits, a_bits, group_size, w_counts=None,
                            w_group=16, name=None):
        kkc = kernel * kernel * int(xq.shape[-1])
        self._check_packed_k(kkc, w_packed, "conv_planes_dynamic")
        guards.check_accum_bound(kkc, a_bits, w_bits, "conv_planes_dynamic")
        self._check_w_counts(w_counts, w_group, int(w_packed.shape[-1]),
                             w_bits, "conv_planes_dynamic")
        self._check_plane_counts(counts, a_bits, "conv_planes_dynamic")
        return self._dispatch("conv_planes_dynamic", xq, w_packed, counts,
                              kernel=kernel, stride=stride, w_bits=w_bits,
                              a_bits=a_bits, group_size=group_size,
                              w_counts=w_counts, w_group=w_group, name=name)

    def dynamic_quant(self, x2, *, group_size, bits):
        # A NaN/Inf activation quantizes to garbage silently; reject it
        # here (concrete arrays only — inside jit the check is a no-op
        # and the value path is untouched either way).
        guards.check_finite(x2, "dynamic_quant input")
        return self._dispatch("dynamic_quant", x2, group_size=group_size,
                              bits=bits)

    def attention(self, q_, k_, v_, *, causal=True, window=None):
        return self._dispatch("attention", q_, k_, v_, causal=causal,
                              window=window)


def guard_backend(backend, chain=None) -> GuardedBackend:
    """Wrap ``backend`` (object or registered name) in a GuardedBackend.

    Idempotent: an already-guarded backend is returned unchanged."""
    if isinstance(backend, GuardedBackend):
        return backend
    return GuardedBackend(backend, chain=chain)


_REGISTRY: dict[str, Backend] = {}


def register_backend(name: str, backend: Backend) -> Backend:
    """Register (or replace) a backend under ``name``."""
    _REGISTRY[name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def list_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_backend(backend=None, use_pallas: bool | None = None,
                    interpret: bool | None = None) -> Backend:
    """Normalize any legacy spelling to a Backend object.

    ``backend`` may be a Backend, a registered name, or None — in which
    case the legacy ``use_pallas``/``interpret`` booleans pick among the
    built-ins (kept for ad-hoc tooling; plans carry a Backend object).
    Only an explicit ``interpret=True`` selects the interpreter.
    """
    if isinstance(backend, Backend):
        return backend
    if isinstance(backend, str):
        return get_backend(backend)
    if backend is not None:
        raise TypeError(f"backend must be a Backend or name, got {backend!r}")
    if use_pallas:
        return get_backend("pallas_interpret" if interpret else "pallas_tpu")
    return get_backend("xla")


register_backend("xla", Backend())
register_backend("pallas_interpret", PallasBackend("pallas_interpret", True))
register_backend("pallas_tpu", PallasBackend("pallas_tpu", False))
