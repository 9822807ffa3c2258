"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell: build the SPMD step
function (shard_map with explicit collectives), ``.lower().compile()`` it for
the production mesh, and record memory_analysis / cost_analysis / collective
wire bytes into a JSON artifact consumed by EXPERIMENTS.md §Dry-run/§Roofline.

The 512-device mesh is virtual: ``main`` pins JAX to the CPU platform with
512 host devices before any backend starts, also on a machine with a TPU.
``--all`` spawns one child process per cell and starts no backend itself.

Usage:
  python -m repro.launch.dryrun --arch qwen3_4b --shape train_4k
  python -m repro.launch.dryrun --arch jamba_1_5_large --shape long_500k --multi-pod
  python -m repro.launch.dryrun --all            # spawn one subprocess per cell
Options: --zero1 --sp --micro N --compress {none,bf16,int8} --out DIR
"""
import argparse
import json
import sys
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config, input_specs, skip_reason
from repro.configs.base import ARCH_IDS
from repro.launch import analysis
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import Model
from repro.parallel.ctx import ParallelCtx
from repro.parallel.sharding import batch_spec, cache_specs, param_specs
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import TrainConfig, make_step_fn

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _f32_like(spec_tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), spec_tree
    )


def _opt_specs(pspecs, *, zero1: bool, dp_last: str | None, flags=None):
    """Optimizer-moment PartitionSpecs: same as params; with ZeRO-1 the shard
    dim per leaf (from _zero_flags_from_specs; -1 = replicated) additionally
    shards over the given axis."""

    def visit(spec, dim):
        if not zero1 or dp_last is None or dim is None or dim < 0:
            return spec
        entries = list(tuple(spec))
        entries += [None] * (dim + 1 - len(entries))
        entries[dim] = dp_last
        return P(*entries)

    if flags is None:
        flags = jax.tree_util.tree_map(lambda _: 0, pspecs)
    m = jax.tree_util.tree_map(visit, pspecs, flags)
    return {"m": m, "v": m, "step": P()}


def _zero_flags_from_specs(param_shapes, dp_size: int, pspecs):
    """Per-leaf ZeRO shard dim: the first dim that is spec-unsharded and
    divisible by the shard group size (-1 = keep replicated)."""

    def visit(s, spec):
        entries = tuple(spec)
        for i, size in enumerate(s.shape):
            e = entries[i] if i < len(entries) else None
            if e is None and size % dp_size == 0 and size >= dp_size:
                return i
        return -1

    return jax.tree_util.tree_map(visit, param_shapes, pspecs)


def _zero_opt_shapes(param_shapes, flags, dp_size: int):
    def visit(s, flag):
        # global view: moments keep full shape; sharding comes from specs
        return jax.ShapeDtypeStruct(s.shape, jnp.float32)

    m = jax.tree_util.tree_map(visit, param_shapes, flags)
    return {"m": m, "v": m, "step": jax.ShapeDtypeStruct((), jnp.int32)}


def _weight_gather_plan(param_shapes, pspecs, dp: int):
    """Per-block-position pytrees of gather dims for 2D-sharded serving
    weights: the first spec-None dim (excluding the stacked nb dim 0) whose
    size divides dp gets the extra 'data' sharding; -1 = stay resident."""
    blocks = param_shapes["blocks"]
    bspecs = pspecs["blocks"]

    def visit(s, spec):
        entries = tuple(spec)
        for i in range(1, len(s.shape)):  # skip the stacked nb dim
            e = entries[i] if i < len(entries) else None
            if e is None and s.shape[i] % dp == 0 and s.shape[i] >= dp * 8:
                return i - 1  # dim index after the per-layer slice drops nb
        return -1

    return tuple(
        jax.tree_util.tree_map(visit, blocks[j], bspecs[j]) for j in range(len(blocks))
    )


def _apply_gather_specs(pspecs, param_shapes, plan, dp_axis="data"):
    """Insert the extra 'data' entry into block param specs per the plan."""
    def visit(spec, s, dim):
        if dim is None or dim < 0:
            return spec
        entries = list(tuple(spec)) + [None] * (len(s.shape) - len(tuple(spec)))
        entries[dim + 1] = dp_axis  # +1: stacked nb dim precedes
        return P(*entries)

    new_blocks = tuple(
        jax.tree_util.tree_map(visit, pspecs["blocks"][j], param_shapes["blocks"][j],
                               plan[j])
        for j in range(len(plan))
    )
    out = dict(pspecs)
    out["blocks"] = new_blocks
    return out


def build_cell(arch: str, shape: str, mesh, *, zero1=False, sp=False, micro=0,
               compress="none", gather_weights=False, pure_dp=False,
               unroll_attn_chunk=None):
    cfg = get_config(arch)
    spec = SHAPES[shape]
    dp = dp_axes(mesh) + (("model",) if pure_dp else ())
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_total = 1
    for a in dp:
        dp_total *= sizes[a]

    batch_shardable = spec.global_batch % dp_total == 0 and spec.global_batch >= dp_total
    if pure_dp and not batch_shardable:
        raise ValueError(
            f"--pure-dp needs global_batch ({spec.global_batch}) divisible by and >= "
            f"the chip count ({dp_total}); use the hybrid TP x DP layout instead")
    dp_entry = dp if batch_shardable else None
    use_cp = shape == "long_500k" and cfg.attn_period > 0  # hybrid flash-decode
    if pure_dp:
        # beyond-paper resharding: treat the whole mesh as data-parallel
        # (small models waste TP wire); params replicated, ZeRO-1 shards
        # optimizer state over the innermost axis
        ctx = ParallelCtx(
            dp_axis=dp_entry, dp_size=dp_total,
            dp_axis_sizes=tuple(sizes[a] for a in (dp_entry or ())),
        )
    else:
        ctx = ParallelCtx.from_mesh(
            mesh, dp=dp_entry if dp_entry else None, sp=sp,
            cp="data" if use_cp else None,
        )
    model = Model(cfg, ctx)
    key = jax.random.PRNGKey(0)
    param_shapes = jax.eval_shape(model.init, key)
    pspecs = param_specs(param_shapes)
    if pure_dp:
        pspecs = jax.tree_util.tree_map(
            lambda s: P(*([None] * len(s.shape))), param_shapes)
    batch = input_specs(cfg, shape)
    bspecs = batch_spec(batch, dp_entry)

    if spec.kind == "train":
        if micro <= 0:
            micro = max(1, spec.global_batch // dp_total // 2)
        tcfg = TrainConfig(opt=AdamWConfig(), microbatches=micro, remat=True,
                           zero1=zero1, grad_compress=compress)
        zero_axis_size = sizes.get("model", 1) if pure_dp else sizes.get("data", 1)
        flags = _zero_flags_from_specs(param_shapes, zero_axis_size, pspecs) if zero1 else None
        step = make_step_fn(model, tcfg, shard_flags=flags)
        opt_shapes = _zero_opt_shapes(param_shapes, flags, zero_axis_size) \
            if zero1 else {"m": _f32_like(param_shapes), "v": _f32_like(param_shapes),
                           "step": jax.ShapeDtypeStruct((), jnp.int32)}
        zero_axis = ("model" if pure_dp else "data") if zero1 else None
        ospecs = _opt_specs(pspecs, zero1=zero1, dp_last=zero_axis, flags=flags)
        mspecs = {"loss": P(), "grad_norm": P(), "lr": P()}
        fn = shard_map(step, mesh=mesh, in_specs=(pspecs, ospecs, bspecs),
                           out_specs=(pspecs, ospecs, mspecs), check_vma=False)
        avals = (param_shapes, opt_shapes, batch)
    elif spec.kind == "prefill":
        def step(params, b):
            return model.forward(params, b)

        lspec = P(dp_entry, None, "model")
        fn = shard_map(step, mesh=mesh, in_specs=(pspecs, bspecs),
                           out_specs=lspec, check_vma=False)
        avals = (param_shapes, batch)
    else:  # decode
        if gather_weights:
            plan = _weight_gather_plan(param_shapes, pspecs, sizes.get("data", 1))
            pspecs = _apply_gather_specs(pspecs, param_shapes, plan)
            model = Model(cfg, ctx, weight_gather=plan)
        gmodel = Model(cfg, ParallelCtx.single())
        cache_shapes = jax.eval_shape(
            partial(gmodel.init_cache, spec.global_batch, spec.seq_len))
        cspecs = cache_specs(cache_shapes, dp_entry,
                             cp="data" if use_cp else None)
        token = batch["token"]
        position = batch["position"]

        def step(params, tok, caches, pos):
            return model.decode_step(params, tok, caches, pos)

        fn = shard_map(
            step, mesh=mesh,
            in_specs=(pspecs, P(dp_entry), cspecs, P()),
            out_specs=(P(dp_entry, "model"), cspecs), check_vma=False)
        avals = (param_shapes, token, cache_shapes, position)

    return cfg, ctx, fn, avals, sizes


def model_flops_per_device(cfg, shape: str, mesh_devices: int) -> float:
    """Analytic MODEL_FLOPS: 6·N_active·D for training, 2·N_active·D for
    inference forward, divided evenly across chips."""
    spec = SHAPES[shape]
    n_active = cfg.active_param_count()
    if spec.kind == "train":
        tokens = spec.global_batch * spec.seq_len
        total = 6.0 * n_active * tokens
    elif spec.kind == "prefill":
        tokens = spec.global_batch * spec.seq_len
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * spec.global_batch
    return total / mesh_devices


def run_cell(arch: str, shape: str, *, multi_pod=False, zero1=False, sp=False,
             micro=0, compress="none", gather_weights=False, pure_dp=False,
             out_dir: Path = ARTIFACT_DIR, tag: str = "") -> dict:
    cfg = get_config(arch)
    reason = skip_reason(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{arch}--{shape}--{mesh_name}" + (f"--{tag}" if tag else "")
    result: dict = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "tag": tag,
        "zero1": zero1, "sp": sp, "micro": micro, "compress": compress,
    }
    if reason:
        result["status"] = "skipped"
        result["skip_reason"] = reason
        _write(out_dir, cell_id, result)
        return result

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        cfg, ctx, fn, avals, sizes = build_cell(
            arch, shape, mesh, zero1=zero1, sp=sp, micro=micro, compress=compress,
            gather_weights=gather_weights, pure_dp=pure_dp)
        with mesh:
            lowered = jax.jit(fn).lower(*avals)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis() or {}
            hlo_counts = analysis.parse_hlo_collectives(compiled.as_text())
        ir = analysis.collect_ir_stats(fn, avals, sizes)
        n_dev = 1
        for s in mesh.devices.shape:
            n_dev *= s
        mf = model_flops_per_device(cfg, shape, n_dev)
        roof = analysis.roofline_terms(cost, ir, model_flops_per_device=mf)
        result.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
            cost={k: cost.get(k) for k in ("flops", "bytes accessed", "optimal_seconds")
                  if k in cost},
            hlo_collective_instances=hlo_counts,
            collectives=ir["collectives"][:64],
            collective_wire_bytes=ir["collective_wire_bytes"],
            roofline=roof,
        )
    except Exception as e:  # record failures as artifacts, they are bugs to fix
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    result["wall_s"] = round(time.time() - t0, 2)
    _write(out_dir, cell_id, result)
    return result


def _write(out_dir: Path, cell_id: str, result: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{cell_id}.json", "w") as f:
        json.dump(result, f, indent=1, default=str)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--micro", type=int, default=0)
    ap.add_argument("--compress", default="none", choices=["none", "bf16", "int8"])
    ap.add_argument("--pure-dp", action="store_true",
                    help="re-shard as pure data parallelism over the whole mesh "
                         "(params replicated; pair with --zero1)")
    ap.add_argument("--gather-weights", action="store_true",
                    help="2D-shard serving weights over (model x data); "
                         "re-gather per block inside the layer scan")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=str(ARTIFACT_DIR))
    ap.add_argument("--all", action="store_true", help="run every cell in subprocesses")
    args = ap.parse_args(argv)

    if args.all:
        import subprocess

        failures = []
        for arch in ARCH_IDS:
            for shape in SHAPES:
                for mp in (False, True):
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--out", args.out]
                    if mp:
                        cmd.append("--multi-pod")
                    rc = subprocess.run(cmd).returncode
                    if rc != 0:
                        failures.append((arch, shape, mp))
        print("failures:", failures)
        sys.exit(1 if failures else 0)

    # the production mesh is virtual: 512 CPU devices, set before any
    # backend starts (also on a machine with a TPU)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 512)
    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod, zero1=args.zero1,
                   sp=args.sp, micro=args.micro, compress=args.compress,
                   gather_weights=args.gather_weights, pure_dp=args.pure_dp,
                   out_dir=Path(args.out), tag=args.tag)
    status = res["status"]
    print(f"[{status}] {args.arch} {args.shape} mesh={res['mesh']} "
          f"wall={res.get('wall_s')}s")
    if status == "ok":
        print("  memory:", res["memory"])
        print("  cost:", res["cost"])
        print("  roofline:", {k: (f'{v:.4g}' if isinstance(v, float) else v)
                              for k, v in res["roofline"].items()})
    elif status == "skipped":
        print("  skip:", res["skip_reason"])
    else:
        print(res["error"])
        print(res["traceback"])
        sys.exit(1)


if __name__ == "__main__":
    main()
