"""``loom.compile``: one entry point from (config, policy) to serving.

A :class:`ServingSession` bundles everything ``launch/serve.py`` used to
wire by hand — param init, the offline serving conversion (weight
packing), cache init, jitted prefill/decode steps (with optional mesh
shardings), and CNN classification — behind one object::

    import repro.api as loom
    session = loom.compile(cfg, policy, mode="serve_packed",
                           backend="pallas_interpret")
    logits, cache = session.prefill(tokens)
    logits, cache = session.decode(token, pos, cache)
    gen = session.generate(tokens, gen_len=16)        # greedy decode loop

CNN configs compile to a classification session::

    session = loom.compile(cnn_cfg, policy, mode="serve_packed")
    logits = session.classify(images)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.api.plan import ExecutionPlan, build_plan
from repro.core import dynamic as dyn
from repro.core import quantize as quant
from repro.core.policy import PrecisionPolicy

_SERVING_MODES = ("serve_int8", "serve_packed")


@dataclasses.dataclass
class ServingSession:
    """A compiled model + plan, ready to serve. Built by :func:`compile`."""

    cfg: Any
    plan: ExecutionPlan
    params: Any
    specs: Any
    _prefill: Any = None
    _decode: Any = None
    _classify: Any = None
    # Content identity of the compiled weights (core.integrity), computed
    # once per compile/reload for serving modes; None = not fingerprinted.
    fingerprint: Any = None
    # The mesh the entry points were jitted against (rejit() needs it).
    _mesh: Any = None

    # -- LM entry points ----------------------------------------------------

    def init_cache(self, batch: int, max_seq: int | None = None):
        from repro.models import model as M
        if self._prefill is None:
            raise ValueError(f"{self.cfg.name}: not an LM session")
        return M.init_cache(self.cfg, batch, max_seq or self.cfg.max_seq)

    def prefill(self, tokens: jax.Array, cache=None, img_embeds=None):
        """Populate caches from a full prompt. Returns (last_logits, cache)."""
        if self._prefill is None:
            raise ValueError(f"{self.cfg.name}: not an LM session")
        if cache is None:
            cache = self.init_cache(tokens.shape[0])
        return self._prefill(self.params, tokens, cache, img_embeds)

    def decode(self, token: jax.Array, pos, cache):
        """One greedy-decode step. token: [B] int32; pos: absolute position,
        a scalar (whole batch at one position) or a [B] int32 vector of
        per-row positions (continuous batching — see runtime/batching)."""
        if self._decode is None:
            raise ValueError(f"{self.cfg.name}: not an LM session")
        return self._decode(self.params, token,
                            jnp.asarray(pos, jnp.int32), cache)

    def generate(self, tokens: jax.Array, gen_len: int):
        """Greedy generation: prefill + gen_len decode steps.

        Returns int32 [B, gen_len] (bit-compatible with the historical
        ``launch/serve.py`` driver loop for the same params/seed).
        Decoded tokens accumulate ON DEVICE; the single host transfer
        happens at the end instead of one round-trip per step."""
        import numpy as np
        b, s = tokens.shape
        logits, cache = self.prefill(tokens)
        tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        out = [tok]
        for i in range(gen_len - 1):
            logits, cache = self.decode(tok, s + i, cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        return np.asarray(jnp.stack(out, axis=1))

    # -- CNN entry point ----------------------------------------------------

    def classify(self, x: jax.Array) -> jax.Array:
        """x: [B, H, W, C] float -> logits [B, n_classes]."""
        if self._classify is None:
            raise ValueError(f"{self.cfg.name}: not a CNN session")
        return self._classify(self.params, x)

    # -- Integrity ----------------------------------------------------------

    def verify_integrity(self, where: str = "") -> int:
        """Re-verify the serving weights against the compile-time CRC32
        fingerprint and the plan's pass-law count metadata (a typed
        :class:`~repro.api.guards.WeightIntegrityError` on any mismatch).
        Returns the number of leaves verified; 0 when the session was
        compiled without a fingerprint (non-serving modes)."""
        if self.fingerprint is None:
            return 0
        from repro.core import integrity
        where = where or self.cfg.name
        n = integrity.verify_params(self.params, self.fingerprint, where)
        integrity.verify_plan_counts(self.plan, self.fingerprint, where)
        return n

    def refingerprint(self) -> None:
        """Recompute the fingerprint from the CURRENT params/plan — only
        legitimate after an intentional weight swap (engine reload)."""
        from repro.core import integrity
        self.fingerprint = integrity.fingerprint_session(self.params,
                                                         self.plan)

    def rejit(self) -> "ServingSession":
        """Fresh jit wrappers (and therefore fresh trace caches) for the
        same cfg/plan/params. Used after a backend quarantine: sticky
        fallback state lives in the GuardedBackend, but an already-traced
        entry point baked the old dispatch into its cache — re-jitting
        forces the next call to re-trace through the degraded chain."""
        if self._classify is not None:
            from repro.models import cnn
            cfg, plan = self.cfg, self.plan
            classify = jax.jit(lambda p, x: cnn.forward(p, cfg, x, plan),
                               **_JIT)
            return dataclasses.replace(self, _classify=classify)
        from repro.models import model as M
        cache_specs = M.cache_spec_tree(self.cfg) \
            if self._mesh is not None else None
        prefill_j, decode_j = _jit_lm(self.cfg, self.plan, self._mesh,
                                      self.specs, cache_specs)
        return dataclasses.replace(self, _prefill=prefill_j,
                                   _decode=decode_j)

    # -- Introspection ------------------------------------------------------

    def layer_plan(self, name: str = "", kind: str = "linear"):
        return self.plan.layer(name, kind=kind)

    def dynamic_stats(self, x: jax.Array, layer_name: str = "") -> dict:
        """Runtime trimming report for ``x`` entering ``layer_name``: what
        fraction of the static activation planes the OR-tree path executes
        (Loom's dynamic speedup contribution)."""
        lp = self.plan.layer(layer_name)
        bits = min(lp.a_bits, 8)
        xq, _ = quant.quantize(x.astype(jnp.float32).reshape(-1, x.shape[-1]),
                               bits)
        return dyn.dynamic_stats(xq, bits, lp.group_size)


# Serving programs round each value to the dtype the model states. By
# default XLA may keep f32 where the program rounds to bf16 ("excess
# precision"), and where it does so follows fusion, which differs between
# a Mosaic kernel and XLA's own dot. Per-row int8 activation quantization
# then turns a one-ulp difference into another int8 code: on a TPU v5e
# the pallas_tpu and xla streams of qwen3-1.7b parted within 10 tokens
# by default, and were bit-identical with this option. The byte compares
# against the xla route (shadow audit, guarded fallback) rely on it.
_JIT = {"compiler_options": {"xla_allow_excess_precision": False}}


def _jit_lm(cfg, plan, mesh, param_specs, cache_specs):
    """Jit the prefill/decode pair, with resolved shardings when a mesh is
    given. ``plan`` is an ExecutionPlan
    (launch/serve.jit_serve_steps delegates here)."""
    from repro.models import model as M

    def prefill_fn(params, tokens, cache, img_embeds=None):
        return M.prefill(params, cfg, tokens, cache, plan, img_embeds)

    def decode_fn(params, token, pos, cache):
        return M.decode_step(params, cfg, token, pos, cache, plan)

    if mesh is None:
        return (jax.jit(prefill_fn, **_JIT),
                jax.jit(decode_fn, donate_argnums=(3,), **_JIT))
    from jax.sharding import PartitionSpec as PS
    from repro.dist.sharding import resolve_tree
    psh = resolve_tree(param_specs, mesh)
    csh = resolve_tree(cache_specs, mesh)
    tok_sh = resolve_tree(PS("dp"), mesh)
    toks_sh = resolve_tree(PS("dp", None), mesh)
    # 4th entry: img_embeds (None = unconstrained; empty pytree for LMs).
    prefill_j = jax.jit(prefill_fn,
                        in_shardings=(psh, toks_sh, csh, None),
                        out_shardings=(None, csh), **_JIT)
    decode_j = jax.jit(decode_fn,
                       in_shardings=(psh, tok_sh, None, csh),
                       out_shardings=(None, csh),
                       donate_argnums=(3,), **_JIT)
    return prefill_j, decode_j


def compile(cfg, policy: Optional[PrecisionPolicy] = None,
            mode: str = "dense", backend="xla", *,
            params=None, specs=None, rng: int = 0,
            conv_route: str = "fused", mesh=None,
            guarded: bool = False) -> ServingSession:
    """Compile a model for serving: plans + params + jitted entry points.

    cfg: a ``ModelConfig`` (LM: prefill/decode/generate) or ``CNNConfig``
    (classify). ``params``/``specs``: a trained param tree in the DENSE
    layout (converted here when ``mode`` is a serving mode); omitted ->
    randomly initialized from ``rng``. ``backend``: registered name or
    Backend object. ``mesh``: optional jax Mesh — prefill/decode are then
    jitted with resolved in/out shardings (the launch-layer wiring).
    ``guarded``: wrap the backend in a
    :class:`~repro.api.backend.GuardedBackend` — typed fault
    classification, sticky per-op fallback down the degradation chain,
    and numeric-integrity prechecks; bit-identical to unguarded on the
    fault-free path (pair with ``repro.runtime.ServingSupervisor`` for
    request-level retry/timeout/health).
    """
    policy = policy if policy is not None else PrecisionPolicy()
    if params is not None and specs is None:
        raise ValueError("compile(params=...) also needs specs=... "
                         "(the PartitionSpec tree from init_params)")
    if guarded:
        from repro.api.backend import guard_backend
        backend = guard_backend(backend)
    plan = build_plan(cfg, policy, mode, backend, conv_route)

    if hasattr(cfg, "convs"):            # CNN session
        from repro.models import cnn
        if params is None:
            params, specs = cnn.init_params(jax.random.PRNGKey(rng), cfg)
        if mode in _SERVING_MODES:
            from repro.models.model import _convert_tree
            params, specs = _convert_tree(params, specs, policy, mode)
            # Pack-time per-filter-group weight plane counts -> plan
            # (CNN param keys ARE the layer names), before classify
            # traces; the hot path only ever reads plan metadata.
            plan.record_weight_groups(params)
        classify = jax.jit(lambda p, x: cnn.forward(p, cfg, x, plan),
                           **_JIT)
        sess = ServingSession(cfg=cfg, plan=plan, params=params, specs=specs,
                              _classify=classify)
        if mode in _SERVING_MODES:
            sess.refingerprint()
        return sess

    from repro.models import model as M
    if params is None:
        params, specs = M.init_params(jax.random.PRNGKey(rng), cfg)
    if mode in _SERVING_MODES:
        params, specs = M.convert_params_for_serving(params, specs, policy,
                                                     mode)
        # LM blocks are stacked along the scan axis and share one plan
        # per layer class, so per-layer static counts only apply to the
        # unstacked head here.
        plan.record_weight_groups({"lm_head": params.get("head", {})})
    cache_specs = M.cache_spec_tree(cfg) if mesh is not None else None
    prefill_j, decode_j = _jit_lm(cfg, plan, mesh, specs, cache_specs)
    sess = ServingSession(cfg=cfg, plan=plan, params=params, specs=specs,
                          _prefill=prefill_j, _decode=decode_j, _mesh=mesh)
    if mode in _SERVING_MODES:
        sess.refingerprint()
    return sess
