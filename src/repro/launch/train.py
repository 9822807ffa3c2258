"""Training driver with the Scalify verification gate.

Flow (the paper's technique as a first-class framework feature):
  1. VERIFY: trace the single-device and TP-sharded graphs of the configured
     model and run the equivalence verifier; abort with localized diagnostics
     if the parallelization is not provably equivalent.
  2. TRAIN: shard_map train step over the requested mesh with checkpointing,
     deterministic resumable data, and fault-tolerant restart.

Usage (CPU demo, any arch):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python -m repro.launch.train --arch qwen3_4b --smoke --steps 50 --tp 2 --dp 4

Usage (TPU, full width, Pallas kernels compiled by Mosaic):
  python -m repro.launch.train --arch mamba2_130m --impl pallas --steps 10
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import ARCH_IDS
from repro.data import DataConfig, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models import Model
from repro.parallel.ctx import ParallelCtx
from repro.parallel.sharding import batch_spec, param_specs
from repro.train import checkpoint as ckpt
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.trainer import TrainConfig, make_step_fn


def init_state(model: Model, mesh, key):
    """Params and AdamW state created directly in their mesh shardings (from
    ``param_specs``): no device ever holds an unsharded copy.  Returns
    ``(params, opt, shardings)``, ``shardings`` being the matching pair of
    NamedSharding pytrees."""
    pspecs = param_specs(jax.eval_shape(model.init, key))
    ospecs = {"m": pspecs, "v": pspecs, "step": P()}
    named = lambda specs: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs)
    shardings = (named(pspecs), named(ospecs))
    params = jax.jit(model.init, out_shardings=shardings[0])(key)
    opt = jax.jit(adamw_init, out_shardings=shardings[1])(params)
    return params, opt, shardings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3_4b")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--compress", default="none", choices=["none", "bf16", "int8"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--skip-verify", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", choices=["reference", "pallas"], default="reference",
                    help="attention/SSD kernels: jnp reference, or the Pallas "
                         "kernels compiled by Mosaic (TPU only)")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)

    # ---- 1. verification gate (paper technique) ---------------------------------
    # Declare the launch's parallelism as a Plan and verify each axis before
    # committing devices: TP forward equivalence, and (non-MoE archs) DP
    # batch-shard equivalence.
    if not args.skip_verify and (args.tp > 1 or args.dp > 1):
        from repro.verify import Plan, PlanError, Session

        dp_gate = args.dp if args.dp > 1 and cfg.n_experts == 0 else 1
        try:
            plan = Plan(tp=args.tp, dp=dp_gate,
                        layers=min(cfg.n_layers, 4), seq=32, smoke=args.smoke)
        except PlanError:
            plan = None  # tp=1 and dp gate skipped: nothing to verify
        if plan is not None:
            print(f"[verify] checking {args.arch} plan {plan.describe()} "
                  f"graph equivalence ...")
            t0 = time.time()
            with Session() as session:
                rep = session.verify(args.arch, plan)
            print(f"[verify] {rep.summary().splitlines()[0]} "
                  f"({time.time()-t0:.2f}s)")
            if not rep.verified:
                print(rep.summary())
                print("[verify] ABORTING: parallelization not semantically "
                      "equivalent")
                return 2

    # ---- 2. training ----------------------------------------------------------------
    n_dev = len(jax.devices())
    if args.tp * args.dp > n_dev:
        print(f"need {args.tp * args.dp} devices, have {n_dev} "
              f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        return 1
    mesh = make_debug_mesh(tp=args.tp, dp=args.dp)
    ctx = ParallelCtx.from_mesh(mesh, dp=("data",), sp=args.sp)
    model = Model(cfg, ctx, impl=args.impl)
    tcfg = TrainConfig(opt=AdamWConfig(lr=args.lr, warmup_steps=10,
                                       total_steps=max(args.steps, 100)),
                       microbatches=args.micro, remat=False, zero1=args.zero1,
                       grad_compress=args.compress)

    params, opt, shardings = init_state(model, mesh, jax.random.PRNGKey(args.seed))
    start_step = 0
    ckpt_dir = Path(args.ckpt_dir) if args.ckpt_dir else None
    if args.resume and ckpt_dir:
        latest = ckpt.latest(ckpt_dir)
        if latest:
            (params, opt), meta = ckpt.restore(
                latest, jax.eval_shape(lambda: (params, opt)), shardings=shardings)
            start_step = meta["step"]
            print(f"[ckpt] resumed from {latest} at step {start_step}")

    pspecs, ospecs = jax.tree_util.tree_map(lambda s: s.spec, shardings)
    data = SyntheticLM(DataConfig(cfg.vocab, args.seq, args.batch, seed=args.seed))
    sample = data.batch_at(0)
    bspecs = batch_spec(sample, ("data",))
    mspecs = {"loss": P(), "grad_norm": P(), "lr": P()}
    # params and moments are updated in place: their old buffers are donated
    step_fn = jax.jit(shard_map(
        make_step_fn(model, tcfg), mesh=mesh,
        in_specs=(pspecs, ospecs, bspecs), out_specs=(pspecs, ospecs, mspecs),
        check_vma=False), donate_argnums=(0, 1))

    t0 = time.time()
    nonfinite = False  # any step's loss non-finite; read only when logging
    with mesh:
        for step in range(start_step, args.steps):
            batch = data.batch_at(step)
            params, opt, metrics = step_fn(params, opt, batch)
            nonfinite = nonfinite | ~jnp.isfinite(metrics["loss"])
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"({(time.time()-t0)/(step-start_step+1):.2f}s/step)")
                if nonfinite:
                    print(f"[abort] non-finite loss by step {step}")
                    return 3
            if ckpt_dir and (step + 1) % args.ckpt_every == 0:
                ckpt.save(ckpt_dir, step + 1, (params, opt))
                print(f"[ckpt] saved step {step + 1}")
    print(f"[done] {args.steps - start_step} steps in {time.time()-t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
