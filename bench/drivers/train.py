"""Training cells: the program's jitted train step in a closed loop.

Set-up builds one step (``make_step_fn`` under ``shard_map``, jitted with
donated parameters and moments, as ``repro.launch.train`` builds it), the
weights from the seed with the configuration's own ``init``, and a pool of
distinct batches on the device.  The first ``check_steps`` steps go through
that same step on the pool's first batches; they compile it, and their
losses, the first gradient (read from the AdamW moments after step 1) and
the parameters' change are kept for the check.  The window then runs the
same step on the pool (see ``window``).

After the window the program's state is freed and the plain reference
(``configs/<config>.py``) repeats the checked steps in float32 at the
highest matmul precision, one batch row at a time.  ``correct`` compares:

  loss_gap    each checked step's loss, the largest relative gap
  grad_gap    each leaf's first-gradient norm, the largest gap
  change_gap  each leaf's change over the checked steps, the largest gap

A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf.  Leaves whose
reference gradient is under a thousandth of the median leaf's are left out
of ``change_gap``: they move under AdamW by rounding alone.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, lm_data

WEIGHTS, DATA = 0, 1  # seed streams


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


@jax.jit
def change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old))]


@jax.jit
def tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def worst_leaf_gap(prog, ref, keep=None) -> tuple:
    """(gap, leaf index): the largest |prog - ref| over max(ref, median ref)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    idx = np.arange(len(ref)) if keep is None else np.flatnonzero(keep)
    floor = np.median(ref[idx])
    gaps = np.abs(prog[idx] - ref[idx]) / np.maximum(ref[idx], floor)
    i = int(np.argmax(gaps))
    return float(gaps[i]), int(idx[i])


class Setup:
    """The cell's sizes, program objects and step, built from its files."""

    def __init__(self, run: harness.Run):
        from jax import shard_map
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.configs import get_config
        from repro.launch.mesh import make_debug_mesh
        from repro.models import Model
        from repro.parallel.ctx import ParallelCtx
        from repro.parallel.sharding import batch_spec, param_specs
        from repro.train.optimizer import AdamWConfig, adamw_init
        from repro.train.trainer import TrainConfig, make_step_fn

        cell, tr = run.cell, run.cell.traffic
        prog = cell.config["program"]
        if run.rehearse:  # the CPU runs Pallas kernels only in interpret mode
            from repro.kernels import ops

            ops.ssd_scan = partial(ops.ssd_scan, interpret=True)
        self.ref = cell.reference()
        self.d = self.ref.dims(cell.config, rehearse=run.rehearse)
        size = cell.config["rehearse"] if run.rehearse else tr
        self.seq, self.batch = size["seq"], size["batch"]
        self.cfg = dataclasses.replace(get_config(prog["arch"]), **self.d,
                                       **prog["overrides"])
        self.tp = tr.get("tp", 1)
        self.mesh = make_debug_mesh(tp=self.tp, dp=1)
        ctx = ParallelCtx.from_mesh(self.mesh, dp=("data",))
        self.model = Model(self.cfg, ctx, impl=prog["impl"])
        self.opt_cfg = AdamWConfig(**tr["optimizer"])
        self.micro = size.get("microbatches", 1)
        tcfg = TrainConfig(opt=self.opt_cfg, remat=prog["remat"],
                           microbatches=self.micro)
        self.check_steps = tr["check_steps"]

        wkey = lm_data.seed_key(run.seed, WEIGHTS)
        shapes = jax.eval_shape(partial(self.ref.init, self.d), wkey)
        want = jax.eval_shape(self.model.init, wkey)
        if (jax.tree_util.tree_structure(shapes) != jax.tree_util.tree_structure(want)
                or [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(shapes)]
                != [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(want)]):
            raise ValueError("the configuration's weights do not match the "
                             "program's parameter tree")
        pspecs = param_specs(want)
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        named = lambda specs: jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs)
        self.make_params = jax.jit(partial(self.ref.init, self.d),
                                   out_shardings=named(pspecs))
        self.make_opt = jax.jit(adamw_init, out_shardings=named(ospecs))
        self.reseed(run.seed)
        self.data_kw = dict(vocab=self.d["vocab"], seq=self.seq, batch=self.batch,
                            n=tr["pool"], **tr["data"])
        row = jax.ShapeDtypeStruct((self.batch, self.seq), jnp.int32)
        bspecs = batch_spec({"tokens": row, "labels": row}, ("data",))
        mspecs = {"loss": P(), "grad_norm": P(), "lr": P()}
        self.step = jax.jit(shard_map(
            make_step_fn(self.model, tcfg), mesh=self.mesh,
            in_specs=(pspecs, ospecs, bspecs), out_specs=(pspecs, ospecs, mspecs),
            check_vma=False), donate_argnums=(0, 1))

    def reseed(self, seed: int) -> None:
        self.wkey = lm_data.seed_key(seed, WEIGHTS)
        self.dkey = lm_data.seed_key(seed, DATA)

    def pool(self) -> list:
        toks, labels = lm_data.batches(self.dkey, **self.data_kw)
        return [{"tokens": toks[i], "labels": labels[i]} for i in range(toks.shape[0])]


def program_readings(s: Setup, pool: list):
    """Run the checked steps through the timed step; return the state for
    the window and the readings: losses, first-gradient and change norms."""
    params = s.make_params(s.wkey)
    opt = s.make_opt(params)
    losses, gnorm, grad = [], [], None
    with s.mesh:
        for i in range(s.check_steps):
            params, opt, m = s.step(params, opt, pool[i])
            losses.append(float(m["loss"]))
            gnorm.append(float(m["grad_norm"]))
            if i == 0:
                grad = [float(x) / (1 - s.opt_cfg.b1) for x in leaf_norms(opt["m"])]
    p0 = s.make_params(s.wkey)
    change = [float(x) for x in change_norms(params, p0)]
    del p0
    return params, opt, {"loss": losses, "grad": grad, "change": change,
                         "grad_norm": gnorm}


def reference_readings(s: Setup, mm=None, rows=None) -> dict:
    """The checked steps in the plain reference (float32, HIGHEST), or with
    ``mm`` in place of its matrix product (the precision control), over the
    first ``rows`` rows of each batch (all of them by default)."""
    from bench import adamw_ref

    mm = mm or s.ref.matmul
    stored = s.make_params(s.wkey)
    dtypes = tuple(a.dtype.name for a in jax.tree_util.tree_leaves(stored))
    params = start = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), stored)
    del stored
    row_grad = jax.jit(jax.value_and_grad(
        lambda p, t, lab: s.ref.loss(p, {"tokens": t[None], "labels": lab[None]},
                                     s.d, mm)))
    toks, labels = lm_data.batches(s.dkey, **s.data_kw)
    state = adamw_ref.init(params)
    losses, grad = [], None
    for i in range(s.check_steps):
        tot_l, tot_g = 0.0, None
        for r in range(rows or s.batch):
            lv, g = row_grad(params, toks[i, r], labels[i, r])
            tot_l += float(lv)
            tot_g = g if tot_g is None else tree_add(tot_g, g)
        g = jax.tree_util.tree_map(lambda x: x / (rows or s.batch), tot_g)
        losses.append(tot_l / (rows or s.batch))
        params, state, clipped = adamw_ref.step(s.opt_cfg, params, g, state, dtypes)
        if i == 0:
            grad = [float(x) for x in leaf_norms(clipped)]
            raw = [float(x) for x in leaf_norms(g)]
    change = [float(x) for x in change_norms(params, start)]
    return {"loss": losses, "grad": grad, "change": change, "raw_grad": raw}


def compare(prog: dict, ref: dict, leaves=None) -> dict:
    """The numbers that decide ``correct`` (see the module docstring); with
    ``leaves``, the name of the worst leaf of each, too."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    grad_gap, gi = worst_leaf_gap(prog["grad"], ref["grad"])
    raw = np.asarray(ref["raw_grad"])
    keep = raw >= 1e-3 * np.median(raw)
    change_gap, ci = worst_leaf_gap(prog["change"], ref["change"], keep)
    out = {"loss_gap": loss_gap, "grad_gap": grad_gap, "change_gap": change_gap}
    if leaves is not None:
        out["grad_leaf"], out["change_leaf"] = leaves[gi], leaves[ci]
    return out


def window(run: harness.Run, s: Setup, params, opt, pool) -> dict:
    """The measured window: steps on the pool in a closed loop, the next step
    dispatched before the host waits for the last, as a training loop keeps
    the chip fed.  Steps are dispatched until ``run.seconds`` have passed;
    the step in flight then is finished and counted.

    The traced run profiles from the completion of step ``1`` to that of
    step ``1 + trace_steps``: a step writes ~150k device events (11 MB of
    trace), so a few steps keep the trace and its reading short.  Its
    per-layer rate is taken over the steps that complete after the trace has
    been read, since tracing slows the steps it records."""
    prof = harness.Profile(run)
    first, last = 1, 1 + run.cell.traffic["trace_steps"]
    i = s.check_steps
    done, dispatch_s, wait_s = [], [], []
    in_use = 0  # the most bytes the runtime counts in use while a step runs
    t_read = None  # when the trace had been read
    with s.mesh:
        t_win = time.perf_counter()
        t_end = t_win + run.seconds
        params, opt, met = s.step(params, opt, pool[i % len(pool)])
        while True:
            more = time.perf_counter() < t_end
            if more:
                i += 1
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("dispatch"):
                    params, opt, nxt = s.step(params, opt, pool[i % len(pool)])
                dispatch_s.append(time.perf_counter() - t0)
            if run.devices:
                in_use = max(in_use, harness.memory_in_use(run.devices))
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("wait"):
                jax.block_until_ready(met)
            done.append(time.perf_counter())
            wait_s.append(done[-1] - t0)
            if len(done) == first:
                prof.start()
            elif len(done) == last and prof.on:
                prof.stop()
                t_read = time.perf_counter()
            if not more:
                break
            met = nxt
    prof.stop()
    times = np.diff([t_win] + done)
    k = int(np.argmax(times))
    run.say(f"[window] {len(done)} steps in {done[-1] - t_win} s; slowest step "
            f"{times[k]} s (step {k}), median {np.median(times)} s; longest "
            f"dispatch {max(dispatch_s, default=0.0)} s, longest wait {max(wait_s)} s")
    after = [t for t in done if t_read is not None and t > t_read]
    return {"params": params, "opt": opt, "loss": float(met["loss"]), "in_use": in_use,
            "times": times, "window": done[-1] - t_win,
            # traced runs: the rate of the steps after the trace was read
            "after": (len(after) - 1, after[-1] - after[0]) if len(after) > 1 else None}


def compiled_bytes(s: Setup, params, opt, batch) -> int:
    """What the compiled step holds on a chip while it runs, by XLA's memory
    analysis: arguments, outputs not aliased to them, temporaries and code."""
    ma = s.step.lower(params, opt, batch).compile().memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes
               + ma.generated_code_size_in_bytes)


def run(run: harness.Run, stats: harness.CompileStats) -> dict:
    s = Setup(run)
    pool = s.pool()
    params, opt, prog = program_readings(s, pool)
    setup_s = harness.since_start()
    c, h, m = stats.snapshot()
    run.say(f"[setup] {setup_s} s to the window; backend compile {c} s, "
            f"cache hits {h} misses {m}")

    w = window(run, s, params, opt, pool)
    params, opt, times = w.pop("params"), w.pop("opt"), w["times"]
    tokens = s.batch * s.seq
    nonfinite = int(not np.isfinite(w["loss"]))
    c2, h2, m2 = stats.snapshot()
    run.say(f"[window] last loss {w['loss']}; compiles in window: {m2 - m} "
            f"misses, {h2 - h} hits, {c2 - c} s")
    peak = 0
    if run.devices:
        stats_peak = harness.memory_peak(run.devices)
        held = compiled_bytes(s, params, opt, pool[0])
        run.say(f"[memory] runtime peak_bytes_in_use {stats_peak}; bytes_in_use "
                f"while a step runs, at most {w['in_use']}; the step's compiled "
                f"footprint {held}; memory_stats {run.devices[0].memory_stats()}")
        peak = max(stats_peak, held)
    del params, opt, pool

    ref = reference_readings(s)
    checks = compare(prog, ref)
    limits = run.cell.limits
    correct = all(checks[k] <= limits[k] for k in limits) and nonfinite == 0
    p95 = statistics.quantiles(times, n=20)[-1] if len(times) >= 2 else times[0]
    steps_after, secs_after = w["after"] or (0, 0.0)
    run.data.update(
        steps=len(times),
        tokens_per_s=steps_after * tokens / secs_after if steps_after else None,
        flops_per_token=s.ref.train_flops_per_token(s.d, s.cfg.ssm_chunk),
        ssd_scan=s.ref.ssd_scan_shape(s.d, s.batch // s.micro, s.seq, s.cfg.ssm_chunk),
        chips=len(run.devices) or 1)
    bd = None
    if run.trace and "trace" in run.data:
        t = run.data["trace"]
        bd = {"device_ops": [[n, v] for n, v in t.top_ops()],
              "idle_gaps": [[n, v] for n, v in t.top_gaps()]}
    return {
        "correct": bool(correct),
        "attempted": len(times),
        "failed": int(nonfinite),
        "memory_peak_bytes": int(peak),
        "end_to_end": {
            "train_tokens_per_s": len(times) * tokens / w["window"],
            "train_step_p95_ms": p95 * 1e3,
            "setup_s": setup_s,
        },
        "checks": {k: (v, limits[k]) for k, v in checks.items()},
        "breakdown": bd,
    }
