#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, in one process:
the program against the plain reference over many seeds, and the control
and the faults against the same reference on a few.

    python bench/readings.py --workload <cell> --seeds 11 12 ... \\
        [--control-seeds 11 12 13] [--rehearse]

Training cells print, per seed, one JSON line for the program and, on the
control seeds, one for the reference computed with its matrix products in
float8 (scaled per tensor, e4m3 forward and e5m2 backward: the precision
below the configuration's bfloat16) and one for the fault "half of the batch left out, the mean taken
over the rest".  A step that returns its state unchanged reads 1 on
``change_gap`` by construction and needs no run.  Verifier cells print, per
control seed, the wrong answers of the control: the launcher's own gate,
which verifies only the first ``control_layers`` layers of the plan.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]


def _quantize(x, dtype, top: float):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def fp8(x):
    """Round to float8 scaled per tensor: e4m3 on the way forward, e5m2 for
    the gradient on the way back (the usual float8 training recipe)."""
    return _quantize(x, jnp.float8_e4m3fn, 448.0)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, g: (_quantize(g, jnp.float8_e5m2, 57344.0),))


def fp8_matmul(a, b):
    return jnp.matmul(fp8(a), fp8(b), precision=jax.lax.Precision.HIGHEST)


def train_readings(run, seeds, control_seeds, out):
    from bench.drivers import train

    s = train.Setup(run)
    names = None
    for seed in seeds:
        s.reseed(seed)
        pool = s.pool()
        params, opt, prog = train.program_readings(s, pool)
        names = names or train.leaf_names(params)
        del params, opt, pool
        ref = train.reference_readings(s)
        out({"seed": seed, "side": "program", **train.compare(prog, ref, names),
             "losses": [prog["loss"], ref["loss"]], "grad_norm": prog["grad_norm"],
             "leaves": {n: [a, b, g] for n, a, b, g in zip(
                 names, prog["change"], ref["change"], ref["raw_grad"])}})
        if seed in control_seeds:
            ctrl = train.reference_readings(s, mm=fp8_matmul)
            out({"seed": seed, "side": "control_fp8",
                 **train.compare(ctrl, ref, names)})
            half = train.reference_readings(s, rows=s.batch // 2)
            out({"seed": seed, "side": "fault_half_batch",
                 **train.compare(half, ref, names)})


def verify_readings(run, control_seeds, n, out):
    from bench import harness
    from bench.drivers import verify

    tr = run.cell.traffic
    layers = tr["plan"]["layers"]
    gate = verify.plan_of(tr, tr["control_layers"])
    for seed in control_seeds:
        r = harness.Run(run.cell, seed, 0, False)
        plants = verify.schedule(r, n, layers)
        wrong = 0
        for plant in plants:
            rep, graph = verify.ask(run.cell.config["program"]["arch"], gate, plant)
            wrong += verify.wrong(rep, graph, plant)
        out({"seed": seed, "side": "control_gate", "verdicts": n,
             "wrong_answers": wrong})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--verdicts", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    run = harness.Run(cell, (args.seeds or args.control_seeds)[0], 0, False,
                      rehearse=args.rehearse)
    if not args.rehearse:
        run.devices = harness.check_chips(cell.chips)
    harness.use_compile_cache()

    def out(d):
        print(json.dumps({"workload": cell.name, **d}), flush=True)

    if cell.traffic["driver"] == "train":
        train_readings(run, args.seeds, set(args.control_seeds), out)
    else:
        verify_readings(run, args.control_seeds, args.verdicts, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
