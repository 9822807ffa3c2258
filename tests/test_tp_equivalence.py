"""Distributed-vs-single-device numerical equivalence.

Runs in a SUBPROCESS with ``--xla_force_host_platform_device_count=8`` (the
device count must be set before jax initializes; the main pytest process
stays single-device).  Checks, per architecture family:

  * shard_map TP forward == single-device forward
  * TP+DP train step == single-device train step (loss + params)
  * ZeRO-1 step == replicated AdamW step
  * sequence parallelism == plain TP
  * int8-compressed gradient all-reduce within quantization error
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

_SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout


_PRELUDE = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.models import Model
    from repro.parallel.ctx import ParallelCtx
    from repro.parallel.sharding import param_specs, batch_spec
    from repro.launch.mesh import make_debug_mesh
    from repro.data import make_batch_for
    from repro.configs.base import ShapeSpec

    assert len(jax.devices()) == 8, jax.devices()

    def tp_forward(arch, tp=2, dp=4, sp=False, steps=0, zero1=False, compress="none"):
        import dataclasses
        # structural equivalence is checked in f32 (bf16 reassociation noise
        # and MoE top-k tie flips are covered by tests/test_arch_smoke.py)
        cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
        mesh = make_debug_mesh(tp=tp, dp=dp)
        ctx = ParallelCtx.from_mesh(mesh, dp=("data",), sp=sp)
        model_d = Model(cfg, ctx)
        model_s = Model(cfg)
        key = jax.random.PRNGKey(0)
        params = model_s.init(key)
        shp = ShapeSpec("t", 16, 4 * dp, "train")
        batch = make_batch_for(cfg, shp, seed=1)
        pspecs = param_specs(jax.eval_shape(lambda: params))
        bspecs = batch_spec(batch, ("data",))
        return cfg, mesh, ctx, model_d, model_s, params, batch, pspecs, bspecs
""")


FWD_TEMPLATE = _PRELUDE + textwrap.dedent("""
    arch = "{arch}"
    cfg, mesh, ctx, md, ms, params, batch, pspecs, bspecs = tp_forward(arch, sp={sp})
    ref = np.asarray(ms.loss(params, batch), np.float32)
    from jax import shard_map
    fn = shard_map(lambda p, b: jax.lax.pmean(md.loss(p, b), "data"), mesh=mesh,
                   in_specs=(pspecs, bspecs), out_specs=P(), check_vma=False)
    with mesh:
        dist = np.asarray(jax.jit(fn)(params, batch), np.float32)
    err = abs(float(dist) - float(ref)) / max(abs(float(ref)), 1e-6)
    print("arch", arch, "ref", ref, "dist", dist, "relerr", err)
    assert err < 0.005, (ref, dist)
    print("OK")
""")


@pytest.mark.parametrize("arch", [
    "qwen3_4b", "gemma_2b", "granite_moe_3b", "mamba2_130m", "jamba_1_5_large",
    "hubert_xlarge",
])
def test_tp_loss_matches_single_device(arch):
    _run(FWD_TEMPLATE.format(arch=arch, sp=False))


@pytest.mark.parametrize("arch", ["qwen3_4b", "mamba2_130m"])
def test_sequence_parallel_matches(arch):
    _run(FWD_TEMPLATE.format(arch=arch, sp=True))


TRAIN_TEMPLATE = _PRELUDE + textwrap.dedent("""
    from repro.train.trainer import TrainConfig, make_step_fn
    from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update

    arch = "{arch}"
    cfg, mesh, ctx, md, ms, params, batch, pspecs, bspecs = tp_forward(
        arch, zero1={zero1}, compress="{compress}")
    tcfg = TrainConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=1), microbatches={micro},
                       remat=False, zero1={zero1}, grad_compress="{compress}")

    # single-device reference step
    loss_ref, grads = jax.value_and_grad(lambda p: ms.loss(p, batch))(params)
    opt_ref = adamw_init(params)
    newp_ref, _, _ = adamw_update(tcfg.opt, params, grads, opt_ref)

    # distributed step
    if {zero1}:
        from repro.launch.dryrun import _zero_flags_from_specs, _opt_specs, _zero_opt_shapes
        flags = _zero_flags_from_specs(jax.eval_shape(lambda: params), 4, pspecs)
        step = make_step_fn(md, tcfg, shard_flags=flags)
        opt = {{"m": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
               "v": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
               "step": jnp.zeros((), jnp.int32)}}
        ospecs = _opt_specs(pspecs, zero1=True, dp_last="data", flags=flags)
    else:
        step = make_step_fn(md, tcfg)
        opt = adamw_init(params)
        ospecs = {{"m": pspecs, "v": pspecs, "step": P()}}
    mspecs = {{"loss": P(), "grad_norm": P(), "lr": P()}}
    from jax import shard_map
    fn = shard_map(step, mesh=mesh, in_specs=(pspecs, ospecs, bspecs),
                       out_specs=(pspecs, ospecs, mspecs), check_vma=False)
    with mesh:
        newp, newopt, metrics = jax.jit(fn)(params, opt, batch)
    loss_d = float(metrics["loss"])
    err = abs(loss_d - float(loss_ref)) / max(abs(float(loss_ref)), 1e-6)
    print("loss ref/dist:", float(loss_ref), loss_d, "err", err)
    assert err < 0.02
    # parameters after one step must agree
    worst = 0.0
    for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_flatten_with_path(newp_ref)[0][:50],
        jax.tree_util.tree_flatten_with_path(newp)[0][:50],
    ):
        diff = np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)))
        worst = max(worst, float(diff))
    print("worst param delta:", worst)
    assert worst < {tol}, worst
    print("OK")
""")


def test_train_step_matches_single_device():
    _run(TRAIN_TEMPLATE.format(arch="qwen3_4b", zero1=False, compress="none",
                               micro=1, tol=2e-2))


def test_train_step_microbatched():
    _run(TRAIN_TEMPLATE.format(arch="qwen3_4b", zero1=False, compress="none",
                               micro=4, tol=2e-2))


def test_zero1_matches_adamw():
    _run(TRAIN_TEMPLATE.format(arch="qwen3_4b", zero1=True, compress="none",
                               micro=1, tol=2e-2))


def test_int8_compressed_allreduce_close():
    _run(TRAIN_TEMPLATE.format(arch="qwen3_4b", zero1=False, compress="int8",
                               micro=1, tol=5e-2))


CP_TEMPLATE = _PRELUDE + textwrap.dedent("""
    # context-parallel flash decode == single-device decode (jamba family)
    import dataclasses
    cfg = dataclasses.replace(get_config("jamba_1_5_large", smoke=True), dtype="float32")
    mesh = make_debug_mesh(tp=2, dp=4)   # data axis = 4 -> cp shards
    ctx = ParallelCtx.from_mesh(mesh, dp=None, sp=False, cp="data")
    md, ms = Model(cfg, ctx), Model(cfg)
    key = jax.random.PRNGKey(0)
    params = ms.init(key)
    B, MAXLEN = 2, 32
    tok = jnp.arange(B, dtype=jnp.int32) + 3
    pos = jnp.int32(5)
    cache_s = ms.init_cache(B, MAXLEN)
    logits_ref, _ = ms.decode_step(params, tok, cache_s, pos)

    from repro.parallel.sharding import cache_specs
    pspecs = param_specs(jax.eval_shape(lambda: params))
    cshapes = jax.eval_shape(lambda: ms.init_cache(B, MAXLEN))
    cspecs = cache_specs(cshapes, None, cp="data")
    cache_d = ms.init_cache(B, MAXLEN)  # zeros; same content
    from jax import shard_map
    fn = shard_map(lambda p, t, c, q: md.decode_step(p, t, c, q)[0],
                       mesh=mesh, in_specs=(pspecs, P(), cspecs, P()),
                       out_specs=P(None, "model"), check_vma=False)
    with mesh:
        logits_d = jax.jit(fn)(params, tok, cache_d, pos)
    a = np.asarray(logits_ref, np.float32); b = np.asarray(logits_d, np.float32)
    scale = max(a.std(), 1.0)
    bad = np.mean(np.abs(a - b) / scale > 0.1)
    print("cp decode mismatch frac:", bad)
    assert bad < 0.02
    print("OK")
""")


def test_context_parallel_flash_decode():
    _run(CP_TEMPLATE)


GRAD_TEMPLATE = _PRELUDE + textwrap.dedent("""
    from repro.train.trainer import TrainConfig, make_step_fn
    from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update

    cfg, mesh, ctx, md, ms, params, batch, pspecs, bspecs = tp_forward("{arch}")
    tcfg = TrainConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=1), remat=False,
                       zero1={zero1})
    grads = jax.grad(lambda p: ms.loss(p, batch))(params)
    _, ref, _ = adamw_update(tcfg.opt, params, grads, adamw_init(params))
    if {zero1}:
        from repro.launch.dryrun import _zero_flags_from_specs, _opt_specs
        flags = _zero_flags_from_specs(jax.eval_shape(lambda: params), 4, pspecs)
        step = make_step_fn(md, tcfg, shard_flags=flags)
        opt = {{"m": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
               "v": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
               "step": jnp.zeros((), jnp.int32)}}
        ospecs = _opt_specs(pspecs, zero1=True, dp_last="data", flags=flags)
    else:
        step = make_step_fn(md, tcfg)
        opt = adamw_init(params)
        ospecs = {{"m": pspecs, "v": pspecs, "step": P()}}
    from jax import shard_map
    fn = shard_map(step, mesh=mesh, in_specs=(pspecs, ospecs, bspecs),
                   out_specs=(pspecs, ospecs, {{"loss": P(), "grad_norm": P(), "lr": P()}}),
                   check_vma=False)
    with mesh:
        _, newopt, metrics = jax.jit(fn)(params, opt, batch)
    gn = float(jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads))))
    assert abs(float(metrics["grad_norm"]) - gn) <= 1e-4 * gn, (metrics["grad_norm"], gn)
    for (k, a), b in zip(jax.tree_util.tree_flatten_with_path(ref["m"])[0],
                         jax.tree_util.tree_leaves(newopt["m"])):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-4 * np.abs(a).max(),
                                   err_msg=jax.tree_util.keystr(k))
    print("OK")
""")


@pytest.mark.parametrize("arch,zero1", [("qwen3_4b", False), ("granite_moe_3b", False),
                                        ("qwen3_4b", True)])
def test_tp_gradients_match_single_device(arch, zero1):
    """The TP step's gradient, as its first AdamW moment and its global norm,
    equals the single-device one leaf by leaf: sharded leaves are not
    counted once per rank, and replicated leaves (norms, router) sum every
    rank's share.  AdamW's update is nearly scale-free, so the parameter
    checks above cannot see this."""
    _run(GRAD_TEMPLATE.format(arch=arch, zero1=zero1))
