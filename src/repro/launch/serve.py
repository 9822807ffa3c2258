"""Serving driver: batched prefill/decode with the verification gate.

``--verify-tp N`` runs the decode-plan pre-flight (``repro.verify``,
``Plan.decode(tp=N)``): the serving TP parallelization is proven equivalent
to the single-device decode step before the engine starts.

Usage (CPU demo):
  python -m repro.launch.serve --arch qwen3_4b --smoke --requests 4 --max-new 8 \
      --verify-tp 2

Usage (TPU, full published width):
  python -m repro.launch.serve --arch qwen3_4b --slots 1 --max-len 512
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ARCH_IDS
from repro.launch.compile_cache import use_compile_cache
from repro.models import Model
from repro.serve import Engine, ServeConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3_4b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction, default=False,
                    help="reduced config (default: the full published config)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify-tp", type=int, default=0,
                    help="pre-flight: verify the decode-step TP plan at this "
                         "degree before serving (0 = skip)")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.encoder_only:
        print(f"{args.arch} is encoder-only: no decode serving")
        return 1

    if args.verify_tp > 1:
        from repro.verify import Plan, Session

        plan = Plan.decode(tp=args.verify_tp, smoke=args.smoke,
                           layers=min(cfg.n_layers, 4), max_len=args.max_len)
        print(f"[verify] checking {args.arch} plan {plan.describe()} ...")
        try:
            with Session() as session:
                rep = session.verify(args.arch, plan)
        except ValueError as e:
            print(f"[verify] ABORTING: plan {plan.describe()} invalid for "
                  f"{args.arch}: {e}")
            return 2
        print(f"[verify] {rep.summary().splitlines()[0]}")
        if not rep.verified:
            print(rep.summary())
            print("[verify] ABORTING: serving parallelization not "
                  "semantically equivalent")
            return 2
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    eng = Engine(model, params, ServeConfig(max_len=args.max_len,
                                            batch_slots=args.slots))
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        prompt = rng.integers(1, cfg.vocab, size=rng.integers(3, 9)).tolist()
        rid = eng.submit(prompt, max_new=args.max_new)
        print(f"[submit] req {rid} prompt={prompt}")
    results = eng.run()
    dt = time.time() - t0
    total = sum(len(v) for v in results.values())
    for rid in sorted(results):
        print(f"[done] req {rid} -> {results[rid]}")
    print(f"[stats] {total} tokens in {dt:.2f}s ({total/dt:.1f} tok/s incl. prefill)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
