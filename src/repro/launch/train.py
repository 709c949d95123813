"""Training launcher: pjit train_step + fault-tolerant loop.

``make_train_step`` builds the jitted SPMD step: microbatch gradient
accumulation (lax.scan), optional error-feedback gradient compression for
the cross-pod hop, AdamW with sharded moments, LR schedule. The step is a
pure (state, batch) -> (state, metrics) function — everything the
Supervisor (runtime/supervisor.py) needs for restart/straggler/spike
handling, and everything dryrun.py needs to lower at 256/512 chips.

Run:  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
          --steps 100 --batch 8 --seq 128   (CPU-scale smoke)
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro.api import plan as planlib
from repro.dist.sharding import resolve_tree
from repro.models import model as M
from repro.optim import (AdamWConfig, CompressionConfig, Schedule,
                         adamw_init, adamw_update, compress_state_init,
                         compressed_gradient, make_schedule)
from repro.optim.adamw import opt_state_specs


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: AdamWConfig = AdamWConfig()
    sched: Schedule = Schedule()
    accum: int = 1                    # gradient-accumulation microbatches
    compression: CompressionConfig = CompressionConfig()


def make_train_state(key, cfg, tc: TrainConfig):
    params, specs = M.init_params(key, cfg)
    state = {"params": params, "opt": adamw_init(params, tc.opt)}
    sspecs = {"params": specs, "opt": opt_state_specs(specs)}
    if tc.compression.enabled:
        state["err"] = compress_state_init(params)
        sspecs["err"] = specs
    return state, sspecs


def make_train_step(cfg, exec_cfg: planlib.ExecutionPlan, tc: TrainConfig):
    sched_fn = make_schedule(tc.sched)

    def loss_of(p, mb):
        return M.loss_fn(p, cfg, mb, exec_cfg)

    def train_step(state, batch):
        params = state["params"]
        if tc.accum == 1:
            (loss, parts), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)
        else:
            mbs = jax.tree.map(
                lambda a: a.reshape(tc.accum, a.shape[0] // tc.accum,
                                    *a.shape[1:]), batch)

            def acc(carry, mb):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_of, has_aux=True)(params, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, lsum + l), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            (gsum, lsum), _ = jax.lax.scan(acc, (zeros, jnp.zeros(())), mbs)
            grads = jax.tree.map(lambda g: g / tc.accum, gsum)
            loss = lsum / tc.accum
            parts = {}

        new_state = dict(state)
        if tc.compression.enabled:
            grads, new_state["err"] = compressed_gradient(
                grads, state["err"], tc.compression)
        lr = sched_fn(state["opt"]["step"])
        new_params, new_opt, om = adamw_update(params, grads, state["opt"],
                                               tc.opt, lr)
        new_state["params"] = new_params
        new_state["opt"] = new_opt
        metrics = {"loss": loss, **om}
        return new_state, metrics

    return train_step


def jit_train_step(cfg, exec_cfg, tc: TrainConfig, mesh, state_specs,
                   batch_specs):
    """pjit the step with resolved shardings; donates the state."""
    step = make_train_step(cfg, exec_cfg, tc)
    in_sh = (resolve_tree(state_specs, mesh), resolve_tree(batch_specs, mesh))
    out_sh = (resolve_tree(state_specs, mesh), None)
    return jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                   donate_argnums=(0,))


# ---------------------------------------------------------------------------
# CPU-scale driver (the integration path examples/tests use)
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--mode", default="dense",
                    choices=["dense", "fake_quant"])
    ap.add_argument("--a-bits", type=int, default=8)
    ap.add_argument("--w-bits", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)

    from repro.core.policy import uniform_policy
    from repro.data import DataConfig, make_iterator
    from repro.launch import compile_cache
    from repro.launch.mesh import make_host_mesh

    compile_cache.enable()

    cfg = configs.get(args.arch, smoke=args.smoke)
    exec_cfg = planlib.build_plan(
        cfg, uniform_policy(args.a_bits, args.w_bits), mode=args.mode)
    tc = TrainConfig(accum=args.accum,
                     sched=Schedule(total_steps=args.steps, warmup_steps=5))
    mesh = make_host_mesh()
    state, sspecs = make_train_state(jax.random.PRNGKey(0), cfg, tc)
    from jax.sharding import PartitionSpec as PS
    bspecs = {"tokens": PS("dp", None), "labels": PS("dp", None)}
    if cfg.n_img_tokens:
        bspecs["img_embeds"] = PS("dp", None, None)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch,
                      n_img_tokens=cfg.n_img_tokens, d_model=cfg.d_model)

    with jax.set_mesh(mesh):
        step_fn = jit_train_step(cfg, exec_cfg, tc, mesh, sspecs, bspecs)
        mgr = None
        if args.ckpt_dir:
            from repro.ckpt import CheckpointManager
            mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every)
            restored, rstep = mgr.restore_latest(state)
            start = 0
            if restored is not None:
                state, start = restored, rstep
        else:
            start = 0
        it = make_iterator(dcfg, start_step=start)
        for step, batch in it:
            if step >= args.steps:
                break
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = step_fn(state, batch)
            if step % 5 == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e}", flush=True)
            if mgr and mgr.should_save(step):
                mgr.save_async(step, state)
        if mgr:
            mgr.wait()
    print("done")


if __name__ == "__main__":
    main()
