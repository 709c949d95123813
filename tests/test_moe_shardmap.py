"""shard_map expert parallelism == einsum-dispatch MoE (subprocess with 8
placeholder devices; values exact, grads within bf16 reduction noise)."""
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    import repro.api as loom
    from repro.launch.mesh import make_mesh
    from repro.models import moe as MOE

    mesh = make_mesh((2, 4), ("data", "model"))
    ec = loom.build_plan(None, mode="dense")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)

    # plain top-2 / 8 experts
    cfg = MOE.MoEConfig(d_model=32, d_ff=16, n_experts=8, top_k=2,
                        expert_parallel=True)
    cfg_sm = dataclasses.replace(cfg, shard_map_ep=True)
    p, _ = MOE.init(jax.random.PRNGKey(0), cfg)
    with jax.set_mesh(mesh):
        y1, a1 = jax.jit(lambda p, x: MOE.apply(p, cfg, x, ec))(p, x)
        y2, a2 = jax.jit(lambda p, x: MOE.apply(p, cfg_sm, x, ec))(p, x)
        g1 = jax.jit(jax.grad(lambda p: MOE.apply(p, cfg, x, ec)[0].sum()))(p)
        g2 = jax.jit(jax.grad(lambda p: MOE.apply(p, cfg_sm, x, ec)[0].sum()))(p)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-5
    f1, _ = jax.tree_util.tree_flatten_with_path(g1)
    f2, _ = jax.tree_util.tree_flatten_with_path(g2)
    for (k1, a), (k2, b) in zip(sorted(f1, key=lambda kv: str(kv[0])),
                                sorted(f2, key=lambda kv: str(kv[0]))):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        denom = max(np.abs(a32).max(), 1e-6)
        assert np.abs(a32 - b32).max() / denom < 0.01, (str(k1),)

    # deepseek-style: shared experts, top-3 of 16
    cfg2 = MOE.MoEConfig(d_model=32, d_ff=8, n_experts=16, top_k=3,
                         n_shared=1, shared_d_ff=24, expert_parallel=True)
    cfg2_sm = dataclasses.replace(cfg2, shard_map_ep=True)
    p2, _ = MOE.init(jax.random.PRNGKey(1), cfg2)
    with jax.set_mesh(mesh):
        y1, _ = jax.jit(lambda p, x: MOE.apply(p, cfg2, x, ec))(p2, x)
        y2, _ = jax.jit(lambda p, x: MOE.apply(p, cfg2_sm, x, ec))(p2, x)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-4

    # non-divisible expert count falls back cleanly (6 experts on tp=4)
    cfg3 = MOE.MoEConfig(d_model=32, d_ff=8, n_experts=6, top_k=2,
                         expert_parallel=True)
    cfg3_sm = dataclasses.replace(cfg3, shard_map_ep=True)
    p3, _ = MOE.init(jax.random.PRNGKey(2), cfg3)
    with jax.set_mesh(mesh):
        y1, _ = jax.jit(lambda p, x: MOE.apply(p, cfg3, x, ec))(p3, x)
        y2, _ = jax.jit(lambda p, x: MOE.apply(p, cfg3_sm, x, ec))(p3, x)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-5
    print("MOE_SHARDMAP_OK")
""")


def test_moe_shardmap_equivalence():
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True, cwd=".", timeout=560)
    assert r.returncode == 0, r.stderr[-2500:]
    assert "MOE_SHARDMAP_OK" in r.stdout
