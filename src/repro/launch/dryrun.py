"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes, prove memory/sharding coherence, and extract the
roofline terms from the compiled artifact.

MUST set the placeholder-device flag before ANY other import (jax locks
device count on first init)."""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs                                  # noqa: E402
from repro.api import build_plan                           # noqa: E402
from repro.dist import sharding                            # noqa: E402
from repro.dist.sharding import resolve_tree               # noqa: E402
from repro.launch import hloanalysis, shapes               # noqa: E402
from repro.launch.mesh import make_production_mesh         # noqa: E402
from repro.launch.serve import make_serve_fns              # noqa: E402
from repro.launch.train import (TrainConfig, make_train_step)  # noqa: E402
from repro.optim import AdamWConfig                        # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _attn_flops(cfg, cell, factor: float) -> float:
    """Attention score/value FLOPs (not in 6ND). factor: 3 for train
    (fwd+bwd), 1 for prefill. Causal halves the S^2 term; windows clamp."""
    total = 0.0
    b, s = cell.batch, cell.seq
    for spec in cfg.pattern:
        if spec.kind == "mamba":
            ssm = cfg.ssm
            # SSD intra-chunk quadratic + state terms per token
            per_tok = 2 * ssm.chunk * ssm.d_inner + 4 * ssm.d_state * ssm.d_inner
            total += per_tok * b * s
            continue
        n_ctx = min(spec.window or s, s) if spec.kind != "cross" \
            else cfg.n_img_tokens
        h, dh = cfg.n_heads, cfg.d_head
        causal_frac = 0.5 if (spec.kind == "attn" and not spec.window) else 1.0
        total += 4.0 * b * h * dh * s * n_ctx * causal_frac
    return total * factor * cfg.n_groups


def model_flops(cfg, cell) -> float:
    """Algorithmic FLOPs for the cell (GLOBAL, not per-device):
    6*N_active*D train / 2*N_active*D prefill / 2*N_active*B decode."""
    _, n_active = shapes.active_param_count(cfg)
    if cell.kind == "train":
        return 6.0 * n_active * cell.batch * cell.seq + _attn_flops(cfg, cell, 3.0)
    if cell.kind == "prefill":
        return 2.0 * n_active * cell.batch * cell.seq + _attn_flops(cfg, cell, 1.0)
    # decode: one token per sequence; KV/state read compute
    kv_term = 0.0
    for spec in cfg.pattern:
        if spec.kind == "mamba":
            kv_term += 4.0 * cfg.ssm.d_state * cfg.ssm.d_inner * cell.batch
        else:
            n_ctx = min(spec.window or cell.seq, cell.seq)
            kv_term += 4.0 * cell.batch * cfg.n_heads * cfg.d_head * n_ctx
    return 2.0 * n_active * cell.batch + kv_term * cfg.n_groups


def ideal_bounds(cfg, cell, n_dev: int, weights: str, cache_bytes: float,
                 w_bits: int = 8) -> dict:
    """Analytic per-device lower bounds for the cell — the roofline 'ideal'.

    compute_ideal: MODEL_FLOPS at peak MXU rate.
    memory_ideal: unavoidable HBM traffic — weights at the mode's storage
    precision (the paper's lever!), KV/SSM state, plus (train) optimizer
    state r/w and one residual-stream activation store+reload per layer.
    roofline_fraction := ideal_bound / achieved_bound  (1.0 = at roofline).
    """
    n_total, n_active = shapes.active_param_count(cfg)
    wb = {"dense": 2.0, "serve_int8": 1.0,
          "serve_packed": 2.0 * w_bits / 16.0}[weights]
    mflops = model_flops(cfg, cell) / n_dev
    if cell.kind == "train":
        # params bf16 r+w, grads bf16 w+r, adam moments f32 r+w each
        weight_traffic = n_total * (2 + 2 + 2 + 2 + 8 + 8) / n_dev
        act_traffic = (6.0 * cell.batch * cell.seq * cfg.d_model
                       * cfg.n_layers) / n_dev
        mem_bytes = weight_traffic + act_traffic
    elif cell.kind == "prefill":
        act_traffic = (4.0 * cell.batch * cell.seq * cfg.d_model
                       * cfg.n_layers) / n_dev
        mem_bytes = n_total * wb / n_dev + act_traffic + cache_bytes / n_dev
    else:  # decode: every live weight + the whole cache, once per token
        mem_bytes = n_active * wb / n_dev + cache_bytes / n_dev
    t_c = mflops / hloanalysis.PEAK_FLOPS
    t_m = mem_bytes / hloanalysis.HBM_BW
    return {"ideal_compute_s": t_c, "ideal_memory_s": t_m,
            "ideal_bound_s": max(t_c, t_m), "ideal_mem_bytes": mem_bytes}


def overrides_for(cell, mesh_kind: str, serve_2d_tp: bool = False) -> dict:
    ov = {}
    if cell.name == "long_500k":
        ov["dp"] = ()
        ov["sp"] = ("pod", "data", "model") if mesh_kind == "multi" \
            else ("data", "model")
    if serve_2d_tp and cell.kind in ("decode", "prefill"):
        # 2D tensor parallelism for serving: weights sharded over
        # (data, model); no per-step FSDP all-gather.
        ov["fsdp"] = ()
        ov["tp"] = ("data", "model") if cell.name != "long_500k" else "model"
    return ov


def apply_opts(cfg, opts):
    """Config-level optimization toggles for §Perf hillclimbing.

    flashvjp   memory-efficient attention backward (custom VJP)
    rematdots  save dot outputs instead of full-recompute remat
    rematnone  no activation checkpointing at all
    moedff     TP-within-expert (d_ff sharded) instead of expert-parallel
    moeep      expert-parallel (experts over tp)
    kvcol      kv projections column-parallel + head-repeat constraint
    pinseq     pin decode attention to the cache's seq sharding
    kv8        int8 KV cache (the paper's precision-scaled memory on KV)
    """
    import dataclasses as dc
    for o in [o for o in opts if o]:
        if o == "flashvjp":
            cfg = dc.replace(cfg, flash_vjp=True)
        elif o == "rematdots":
            cfg = dc.replace(cfg, remat="dots")
        elif o == "rematnone":
            cfg = dc.replace(cfg, remat="none")
        elif o == "moedff":
            cfg = dc.replace(cfg, moe=dc.replace(cfg.moe,
                                                 expert_parallel=False))
        elif o == "moeep":
            cfg = dc.replace(cfg, moe=dc.replace(cfg.moe,
                                                 expert_parallel=True))
        elif o == "kvcol":
            cfg = dc.replace(cfg, kv_col_parallel=True)
        elif o == "pinseq":
            cfg = dc.replace(cfg, decode_pin_seq=True)
        elif o == "kv8":
            cfg = dc.replace(cfg, kv_cache_bits=8)
        elif o == "maskupd":
            cfg = dc.replace(cfg, mask_cache_update=True)
        elif o == "kvrep":
            cfg = dc.replace(cfg, kv_replicated=True)
        elif o == "moesm":
            cfg = dc.replace(cfg, moe=dc.replace(cfg.moe, shard_map_ep=True))
        elif o == "attnint8":
            cfg = dc.replace(cfg, attn_int8=True)
        elif o.startswith("block"):
            cfg = dc.replace(cfg, attn_block=int(o[5:]))
        else:
            raise ValueError(f"unknown opt {o}")
    return cfg


def build_step(arch: str, shape_name: str, weights: str, exec_mode: str,
               opts=()):
    """Returns (fn, args_structs, in_shardings_logical, donate)."""
    cfg = apply_opts(configs.get(arch), opts)
    cell = shapes.SHAPES[shape_name]
    from repro.core.policy import uniform_policy
    policy = uniform_policy(8, 8)
    # Compiled per-layer plan on the XLA backend (the dry-run lowers the
    # oracle paths; Mosaic kernels are out of scope for HLO analysis).
    exec_cfg = build_plan(cfg, policy, mode=exec_mode, backend="xla")

    if cell.kind == "train":
        tc = TrainConfig(opt=AdamWConfig(
            moment_dtype="bfloat16" if cfg.d_model >= 8192 else "float32"))
        state, sspecs = shapes.train_state_structs(cfg, tc.opt)
        batch, bspecs = shapes.batch_structs(cfg, cell)
        fn = make_train_step(cfg, exec_cfg, tc)
        return fn, (state, batch), (sspecs, bspecs), (0,)

    params, pspecs = shapes.param_structs(cfg, serving_mode=weights,
                                          policy=policy)
    cache, cspecs = shapes.cache_structs(cfg, cell)
    batch, bspecs = shapes.batch_structs(cfg, cell)
    prefill_fn, decode_fn = make_serve_fns(cfg, exec_cfg)
    if cell.kind == "prefill":
        if cfg.n_img_tokens:
            fn = lambda p, t, c, img: prefill_fn(p, t, c, img)
            args = (params, batch["tokens"], cache, batch["img_embeds"])
            specs = (pspecs, bspecs["tokens"], cspecs, bspecs["img_embeds"])
        else:
            fn = lambda p, t, c: prefill_fn(p, t, c)
            args = (params, batch["tokens"], cache)
            specs = (pspecs, bspecs["tokens"], cspecs)
        return fn, args, specs, (2,)
    fn = lambda p, tok, pos, c: decode_fn(p, tok, pos, c)
    args = (params, batch["token"], batch["pos"], cache)
    specs = (pspecs, bspecs["token"], bspecs["pos"], cspecs)
    return fn, args, specs, (3,)


def run_cell(arch: str, shape_name: str, mesh_kind: str, weights: str = "dense",
             exec_mode: str = "dense", tag: str = "", serve_2d_tp: bool = False,
             out_dir: str = RESULTS_DIR, verbose: bool = True,
             opts=(), profile_ops: bool = False) -> dict:
    cfg = apply_opts(configs.get(arch), opts)
    if opts and not tag:
        tag = "-".join(opts) + ("-2dtp" if serve_2d_tp else "")
    elif serve_2d_tp and not tag:
        tag = "2dtp"
    cell = shapes.SHAPES[shape_name]
    if not shapes.cell_is_applicable(arch, shape_name):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skipped": "full-attention arch: long_500k inapplicable"}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    sharding.set_rule_overrides(overrides_for(cell, mesh_kind, serve_2d_tp))
    try:
        fn, args, logical_specs, donate = build_step(arch, shape_name,
                                                     weights, exec_mode,
                                                     opts)
        in_sh = tuple(resolve_tree(s, mesh) for s in logical_specs)
        t0 = time.time()
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        mem_d = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            mem_d[attr] = getattr(mem, attr, None)
        cost = dict(compiled.cost_analysis() or {})
        hlo = compiled.as_text()
        totals = hloanalysis.analyze_hlo(hlo)
        profile = hloanalysis.attribute(hlo) if profile_ops else None
        mflops = model_flops(cfg, cell)
        terms = hloanalysis.roofline_terms(totals, mflops / n_dev)
        cache_bytes = 0.0
        if cell.kind != "train":
            import math
            cache_tree, _ = shapes.cache_structs(cfg, cell)
            cache_bytes = sum(
                float(math.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree.leaves(cache_tree))
        ideal = ideal_bounds(cfg, cell, n_dev, weights, cache_bytes)
        terms.update(ideal)
        terms["roofline_fraction"] = ideal["ideal_bound_s"] / terms["bound_s"]
        rec = {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "weights": weights, "exec_mode": exec_mode, "tag": tag,
            "n_devices": n_dev,
            "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
            "memory_analysis": mem_d,
            "xla_cost_flops": cost.get("flops"),
            "xla_cost_bytes": cost.get("bytes accessed"),
            "model_flops_global": mflops,
            **terms,
        }
        if profile is not None:
            rec["profile"] = profile
        if verbose:
            per_dev_gb = (mem_d.get("argument_size_in_bytes") or 0) / 2**30
            print(f"[dryrun] {arch} x {shape_name} x {mesh_kind} "
                  f"({weights}/{exec_mode}{('/' + tag) if tag else ''}): "
                  f"OK args={per_dev_gb:.2f}GiB/dev "
                  f"compute={terms['t_compute_s']*1e3:.2f}ms "
                  f"mem={terms['t_memory_s']*1e3:.2f}ms "
                  f"coll={terms['t_collective_s']*1e3:.2f}ms "
                  f"dominant={terms['dominant']} "
                  f"roofline_frac={terms.get('roofline_fraction', 0):.3f} "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
                  flush=True)
    finally:
        sharding.set_rule_overrides({})

    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_kind}__{weights}"
    if exec_mode != "dense":
        fname += f"__{exec_mode}"
    if tag:
        fname += f"__{tag}"
    with open(os.path.join(out_dir, fname + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def repair_json(out_dir: str = RESULTS_DIR):
    """Recompute the ANALYTIC fields (model_flops, ideal bounds, roofline
    fraction) of existing result JSONs — used after fixes to the analytic
    model so compiled artifacts need not be rebuilt."""
    import glob
    import math
    for p in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(p) as f:
            rec = json.load(f)
        if rec.get("skipped"):
            continue
        tag_opts = tuple(
            o for o in rec.get("tag", "").split("-")
            if o in ("flashvjp", "rematdots", "rematnone", "moedff", "moeep",
                     "moesm", "kvcol", "kvrep", "pinseq", "kv8",
                     "maskupd", "attnint8") or o.startswith("block"))
        cfg = apply_opts(configs.get(rec["arch"]), tag_opts)
        cell = shapes.SHAPES[rec["shape"]]
        n_dev = rec["n_devices"]
        mflops = model_flops(cfg, cell)
        cache_bytes = 0.0
        if cell.kind != "train":
            cache_tree, _ = shapes.cache_structs(cfg, cell)
            cache_bytes = sum(float(math.prod(l.shape)) * l.dtype.itemsize
                              for l in jax.tree.leaves(cache_tree))
        ideal = ideal_bounds(cfg, cell, n_dev, rec.get("weights", "dense"),
                             cache_bytes)
        rec["model_flops_global"] = mflops
        rec["model_flops_per_device"] = mflops / n_dev
        rec["useful_flop_ratio"] = (mflops / n_dev) / max(rec["flops"], 1)
        rec.update(ideal)
        rec["roofline_fraction"] = ideal["ideal_bound_s"] / rec["bound_s"]
        with open(p, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[repair] {os.path.basename(p)}: "
              f"frac={rec['roofline_fraction']:.4f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repair", action="store_true",
                    help="recompute analytic fields of existing JSONs")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all",
                    choices=["all"] + list(shapes.SHAPE_ORDER))
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--weights", default="dense",
                    choices=["dense", "serve_int8", "serve_packed"])
    ap.add_argument("--exec-mode", default="dense",
                    choices=["dense", "fake_quant", "serve_int8",
                             "serve_packed"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--serve-2d-tp", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma list: flashvjp,rematdots,rematnone,"
                         "moedff,moeep,kvcol,kvrep,pinseq,kv8,maskupd")
    ap.add_argument("--profile", action="store_true",
                    help="attach per-op memory/collective attribution")
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    args = ap.parse_args(argv)

    if args.repair:
        repair_json(args.out_dir)
        return

    archs = list(configs.LM_ARCHS) if args.arch == "all" else [args.arch]
    shape_names = list(shapes.SHAPE_ORDER) if args.shape == "all" \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shp in shape_names:
            for mk in meshes:
                try:
                    run_cell(arch, shp, mk, args.weights, args.exec_mode,
                             args.tag, args.serve_2d_tp, args.out_dir,
                             opts=tuple(o for o in args.opt.split(",") if o),
                             profile_ops=args.profile)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shp, mk, repr(e)))
                    print(f"[dryrun] {arch} x {shp} x {mk}: FAIL {e!r}",
                          flush=True)
                    traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("   ", f)
        raise SystemExit(1)
    print("[dryrun] all requested cells compiled.")


if __name__ == "__main__":
    main()
