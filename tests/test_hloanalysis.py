"""HLO analyzer validation: FLOP counts vs XLA's own cost analysis on
unrolled graphs, trip-count multiplication on scanned graphs, collective
byte parsing on SPMD modules (subprocess with placeholder devices)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hloanalysis as H

jax.config.update("jax_platform_name", "cpu")


def _layer(x, w):
    return jnp.tanh(x @ w)


def test_dot_flops_match_xla_on_unrolled():
    def f(x, ws):
        for i in range(4):
            x = _layer(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 128, 128), jnp.float32)
    c = jax.jit(f).lower(x, ws).compile()
    ours = H.analyze_hlo(c.as_text()).flops
    xla = c.cost_analysis()["flops"]
    # XLA counts tanh etc.; dots dominate. Expect within 10%.
    assert abs(ours / xla - 1) < 0.10, (ours, xla)


def test_scan_trip_count_multiplication():
    def scanned(x, ws):
        def body(c, w):
            return _layer(c, w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    def unrolled(x, ws):
        for i in range(8):
            x = _layer(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    fs = H.analyze_hlo(jax.jit(scanned).lower(x, ws).compile().as_text()).flops
    fu = H.analyze_hlo(jax.jit(unrolled).lower(x, ws).compile().as_text()).flops
    assert abs(fs / fu - 1) < 0.02, (fs, fu)


def test_nested_scan_trips():
    def f(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return _layer(ci, w), None
            ci, _ = jax.lax.scan(inner, c, jnp.arange(3))
            return ci, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    flops = H.analyze_hlo(jax.jit(f).lower(x, ws).compile().as_text()).flops
    expect = 2 * 32 * 64 * 64 * 5 * 3
    assert abs(flops / expect - 1) < 0.02, (flops, expect)


def test_shape_bytes_tuple_and_comments():
    s = ("(s32[], f32[16,8]{1,0}, /*index=5*/bf16[4,4]{1,0}, "
         "pred[2]{0})")
    assert H._shape_bytes(s) == 4 + 16 * 8 * 4 + 4 * 4 * 2 + 2


_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS, NamedSharding
    import sys
    sys.path.insert(0, "src")
    from repro.launch import hloanalysis as H
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("d",))
    def f(x, w):
        y = x @ w                       # dp x replicated -> psum in bwd only
        return jnp.sum(y * y)
    gf = jax.grad(f, argnums=1)
    xs = NamedSharding(mesh, PS("d", None))
    wsh = NamedSharding(mesh, PS(None, None))
    x = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    c = jax.jit(gf, in_shardings=(xs, wsh), out_shardings=wsh).lower(x, w).compile()
    t = H.analyze_hlo(c.as_text())
    # dw all-reduce over 8 devices: operand is the local [32,16] f32 grad
    ar = t.collective_by_kind.get("all-reduce", 0)
    assert ar >= 32*16*4, t.collective_by_kind
    print("AR_BYTES", ar)
""")


def test_collective_bytes_spmd_subprocess():
    r = subprocess.run([sys.executable, "-c", _SPMD_SCRIPT],
                       capture_output=True, text=True, cwd=".",
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "AR_BYTES" in r.stdout
