"""Launch-layer tests: shape grid, param/batch structs, ideal bounds,
logical-rule overrides, and a real (small-arch) dry-run in a subprocess."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as PS

from repro import configs
from repro.dist import sharding
from repro.launch import shapes
from repro.launch.mesh import make_mesh

jax.config.update("jax_platform_name", "cpu")


def test_shape_grid_is_the_assignment():
    assert set(shapes.SHAPE_ORDER) == {"train_4k", "prefill_32k",
                                       "decode_32k", "long_500k"}
    c = shapes.SHAPES["train_4k"]
    assert (c.seq, c.batch, c.kind) == (4096, 256, "train")
    c = shapes.SHAPES["long_500k"]
    assert (c.seq, c.batch, c.kind) == (524288, 1, "decode")


def test_long_500k_applicability():
    assert shapes.cell_is_applicable("mamba2_370m", "long_500k")
    assert shapes.cell_is_applicable("mixtral_8x7b", "long_500k")
    assert shapes.cell_is_applicable("jamba_v0_1_52b", "long_500k")
    assert shapes.cell_is_applicable("gemma3_12b", "long_500k")
    assert not shapes.cell_is_applicable("llama3_405b", "long_500k")
    assert not shapes.cell_is_applicable("qwen3_1_7b", "long_500k")
    assert not shapes.cell_is_applicable("musicgen_large", "long_500k")
    # every arch runs all other shapes
    for a in configs.LM_ARCHS:
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shapes.cell_is_applicable(a, s)


def test_param_structs_no_allocation_and_counts():
    cfg = configs.get("mixtral-8x7b")
    p, specs = shapes.param_structs(cfg)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in jax.tree.leaves(p))
    total, active = shapes.active_param_count(cfg)
    # mixtral-8x7b: ~47B total, ~13B active (2 of 8 experts)
    assert 4.2e10 < total < 5.2e10, total
    assert 1.1e10 < active < 1.6e10, active
    # dense arch: active == total
    t2, a2 = shapes.active_param_count(configs.get("qwen3-1.7b"))
    assert t2 == a2


def test_packed_param_structs_shrink():
    cfg = configs.get("qwen3-1.7b")
    p_dense, _ = shapes.param_structs(cfg)
    p_packed, sp = shapes.param_structs(cfg, serving_mode="serve_packed")
    bytes_d = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(p_dense))
    bytes_p = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(p_packed))
    # Pw=8 packing: linear weights at 8/16 of bf16 -> whole tree ~0.5-0.65x
    assert bytes_p < 0.7 * bytes_d, (bytes_p, bytes_d)
    # spec tree matches struct tree structure
    assert (jax.tree_util.tree_structure(p_packed)
            == jax.tree_util.tree_structure(
                jax.tree.map(lambda x: x, sp,
                             is_leaf=lambda x: isinstance(x, PS))))


def test_batch_structs_match_cells():
    cfg = configs.get("llama-3.2-vision-90b")
    b, sp = shapes.batch_structs(cfg, shapes.SHAPES["train_4k"])
    assert b["tokens"].shape == (256, 4096)
    assert b["img_embeds"].shape == (256, cfg.n_img_tokens, cfg.d_model)
    b, sp = shapes.batch_structs(cfg, shapes.SHAPES["decode_32k"])
    assert b["token"].shape == (128,) and b["pos"].shape == ()


def test_rule_overrides_resolution():
    mesh_axes = {"fsdp": "data", "dp": "data", "tp": "model", "sp": "model"}
    try:
        sharding.set_rule_overrides({"dp": (), "sp": ("data", "model")})
        import jax.sharding as js
        mesh = make_mesh((1, 1), ("data", "model"))
        rules = sharding.rules_for_mesh(mesh)
        spec = sharding.resolve_spec(PS("dp", "sp", None), rules)
        assert spec == PS(None, ("data", "model"), None)
    finally:
        sharding.set_rule_overrides({})


def test_ideal_bounds_modes_track_paper_law():
    """Loom's storage law must show up in the decode ideal: serve_packed at
    Pw=8 halves the weight-byte term vs dense bf16."""
    from repro.launch.dryrun import ideal_bounds
    cfg = configs.get("qwen3-1.7b")
    cell = shapes.SHAPES["decode_32k"]
    d = ideal_bounds(cfg, cell, 256, "dense", cache_bytes=0.0)
    p = ideal_bounds(cfg, cell, 256, "serve_packed", cache_bytes=0.0)
    i8 = ideal_bounds(cfg, cell, 256, "serve_int8", cache_bytes=0.0)
    assert p["ideal_memory_s"] == pytest.approx(d["ideal_memory_s"] / 2)
    assert i8["ideal_memory_s"] == pytest.approx(d["ideal_memory_s"] / 2)


def test_model_flops_orders():
    from repro.launch.dryrun import model_flops
    cfg = configs.get("qwen3-1.7b")
    f_train = model_flops(cfg, shapes.SHAPES["train_4k"])
    f_prefill = model_flops(cfg, shapes.SHAPES["prefill_32k"])
    f_decode = model_flops(cfg, shapes.SHAPES["decode_32k"])
    assert f_train > f_prefill > f_decode > 0
    # train ~ 6ND: N ~2e9, D ~1.05e6 -> ~1.3e16
    assert 0.8e16 < f_train < 2.5e16, f_train


_DRYRUN = [sys.executable, "-m", "repro.launch.dryrun", "--arch",
           "musicgen_large", "--shape", "decode_32k", "--mesh", "single"]


def test_dryrun_cell_subprocess(tmp_path):
    r = subprocess.run(_DRYRUN + ["--out-dir", str(tmp_path)],
                       capture_output=True, text=True, cwd=".",
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"},
                       timeout=560)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "OK" in r.stdout
    import json, glob
    recs = [json.load(open(p)) for p in glob.glob(str(tmp_path) + "/*.json")]
    assert recs and recs[0]["n_devices"] == 256
    assert recs[0]["t_memory_s"] > 0 and recs[0]["flops"] > 0


def test_compile_cache_dir_from_env_or_repo(monkeypatch, tmp_path):
    """The cache directory is JAX_COMPILATION_CACHE_DIR when set (and no
    other path is set then), else the fixed <repo>/.jax_cache."""
    import os

    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
