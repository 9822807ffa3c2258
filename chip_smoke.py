#!/usr/bin/env python3
"""Chip smoke: drive the launch gate's main path once on a TPU, at published
widths with random weights made from a seed.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # tensor-parallel training, four chips

One process holds the chip and calls the launchers' ``main(argv)`` in
process.  Phases on one chip, in order:

  gate     Session.verify(qwen3_4b, Plan.decode(tp=4, layers=4)) is VERIFIED
  serve    repro.launch.serve: full qwen3_4b, 1 slot, 4 requests of 16 new
           tokens, max_len 512; every request completes, every id < vocab
  train    repro.launch.train: full mamba2_130m, 8 steps, --impl pallas (the
           SSD kernel compiled by Mosaic); every step's loss is finite
  kernels  flash_attention at qwen3_4b widths (prefill and decode) against
           chunked_attention, ssd_scan at mamba2_130m widths against
           ssd_chunked, each within a stated bf16 tolerance

``--four-chips`` runs only repro.launch.train on full qwen3_4b at --tp 4 for
3 steps (its tp-4 gate first), then compares the step-0 loss with a
forward-only loss at tp 2 x dp 2 on the same weights.

A failed phase raises, and the exit code is nonzero.  Earlier lines give each
phase's wall time, backend compile time and persistent-cache hits; the last
line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import re
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
SEED = 0

SERVE_REQUESTS, SERVE_NEW = 4, 16
TRAIN_STEPS = 8
# seq and batch cut so that params, AdamW moments, grads and activations fit
# 16 GB per chip (14.4 GB by memory_analysis on a described v5e:2x2)
FOUR_CHIP_STEPS, FOUR_CHIP_SEQ, FOUR_CHIP_BATCH = 3, 64, 2

# bf16 tolerances: flash attention's max |kernel - reference| (outputs are
# softmax averages of unit-normal values, bf16 rounding is ~4e-3 of them);
# the SSD scan's max |kernel - reference| over max |reference|; the tp 4
# step-0 loss against the tp 2 x dp 2 forward-only loss, relative
FLASH_TOL = 2e-2
SSD_TOL = 1e-2
LOSS_TOL = 2e-3


class _Tee(io.TextIOBase):
    """stdout that is also kept, so a launcher's report can be checked."""

    def __init__(self, out):
        self.out, self.kept = out, io.StringIO()

    def write(self, s):
        self.kept.write(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def run_launcher(main, argv: list[str]) -> str:
    """Call a launcher's ``main(argv)`` in this process; its stdout."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = main(argv)
    if rc != 0:
        raise RuntimeError(f"{main.__module__}.main{tuple(argv)} returned {rc}")
    return tee.kept.getvalue()


def step_losses(out: str) -> list[float]:
    return [float(x) for x in re.findall(r"^step\s+\d+ loss (\S+)", out, re.M)]


# ---------------------------------------------------------------- phases
def phase_gate(smoke: bool = False) -> None:
    from repro.verify import Plan, Session

    plan = Plan.decode(tp=4, smoke=smoke, layers=4)
    with Session() as session:
        rep = session.verify("qwen3_4b", plan)
    print(f"[gate] {rep.summary().splitlines()[0]}")
    if not rep.verified:
        raise RuntimeError(f"gate not verified:\n{rep.summary()}")


def phase_serve(smoke: bool = False) -> None:
    from repro.configs import get_config
    from repro.launch import serve

    cfg = get_config("qwen3_4b", smoke=smoke)
    out = run_launcher(serve.main, [
        "--arch", "qwen3_4b", "--smoke" if smoke else "--no-smoke",
        "--slots", "1", "--requests", str(SERVE_REQUESTS),
        "--max-new", str(SERVE_NEW), "--max-len", "512", "--seed", str(SEED)])
    done = {int(r): json.loads(ids) for r, ids in
            re.findall(r"^\[done\] req (\d+) -> (\[.*\])$", out, re.M)}
    if sorted(done) != list(range(SERVE_REQUESTS)):
        raise RuntimeError(f"requests completed: {sorted(done)}")
    for rid, ids in done.items():
        if len(ids) != SERVE_NEW or not all(0 <= t < cfg.vocab for t in ids):
            raise RuntimeError(f"request {rid}: bad ids {ids}")
    print(f"[serve] {SERVE_REQUESTS} requests x {SERVE_NEW} tokens, "
          f"ids in [0, {cfg.vocab})")


def phase_train(smoke: bool = False) -> None:
    from repro.launch import train

    out = run_launcher(train.main, [
        "--arch", "mamba2_130m", *(["--smoke"] if smoke else []),
        "--impl", "pallas", "--steps", str(TRAIN_STEPS), "--seq", "256",
        "--batch", "8", "--seed", str(SEED)])
    # the launcher returns nonzero if any step's loss was non-finite
    losses = step_losses(out)
    if f"[done] {TRAIN_STEPS} steps" not in out or not all(map(math.isfinite, losses)):
        raise RuntimeError(f"losses: {losses}")
    print(f"[train] {TRAIN_STEPS} steps, loss {losses[0]} -> {losses[-1]}")


def _max_err(out, ref):
    import jax.numpy as jnp

    d = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))
    return float(d.max()), float(jnp.abs(ref.astype(jnp.float32)).max())


def phase_kernels(smoke: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models.attention import chunked_attention
    from repro.models.ssm import ssd_chunked

    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    normal = lambda shape, dt: jax.random.normal(next(keys), shape, f32).astype(dt)

    cfg = get_config("qwen3_4b", smoke=smoke)
    for name, sq, sk, causal in (("prefill", 2048, 2048, True),
                                 ("decode", 1, 512, False)):
        q = normal((1, cfg.n_heads, sq, cfg.hd), bf16)
        k = normal((1, cfg.n_kv_heads, sk, cfg.hd), bf16)
        v = normal((1, cfg.n_kv_heads, sk, cfg.hd), bf16)
        out = ops.flash_attention(q, k, v, causal=causal)
        with jax.default_matmul_precision("highest"):
            ref = chunked_attention(q, k, v, causal=causal)
        err, _ = _max_err(out, ref)
        print(f"[kernels] flash_attention {name} q{tuple(q.shape)} "
              f"k{tuple(k.shape)}: max|err| {err} (tol {FLASH_TOL})")
        if not err <= FLASH_TOL:
            raise RuntimeError(f"flash_attention {name} off by {err}")

    cfg = get_config("mamba2_130m", smoke=smoke)
    B, S, H, P, N = 2, 1024, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    x = normal((B, S, H, P), bf16)
    dt = jax.nn.softplus(normal((B, S, H), f32))
    A = -jnp.exp(0.5 * normal((H,), f32))
    Bm, Cm = normal((B, S, N), bf16), normal((B, S, N), bf16)
    out = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    with jax.default_matmul_precision("highest"):
        ref = ssd_chunked(x, dt, A, Bm, Cm, cfg.ssm_chunk)
    err, scale = _max_err(out, ref)
    print(f"[kernels] ssd_scan x{tuple(x.shape)} N={N} chunk={cfg.ssm_chunk}: "
          f"max|err|/max|ref| {err / scale} (tol {SSD_TOL})")
    if not err / scale <= SSD_TOL:
        raise RuntimeError(f"ssd_scan off by {err / scale} of its scale")


def phase_four_chip(smoke: bool = False) -> None:
    import jax
    from jax import lax, shard_map
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticLM
    from repro.launch import train
    from repro.launch.mesh import make_debug_mesh
    from repro.models import Model
    from repro.parallel.ctx import ParallelCtx
    from repro.parallel.sharding import batch_spec, param_specs

    argv = ["--arch", "qwen3_4b", *(["--smoke"] if smoke else []), "--tp", "4",
            "--steps", str(FOUR_CHIP_STEPS), "--seq", str(FOUR_CHIP_SEQ),
            "--batch", str(FOUR_CHIP_BATCH), "--seed", str(SEED)]
    out = run_launcher(train.main, argv)
    gate = re.findall(r"^\[verify\] (VERIFIED.*)$", out, re.M)
    losses = step_losses(out)  # steps 0 and FOUR_CHIP_STEPS - 1
    if not gate or f"[done] {FOUR_CHIP_STEPS} steps" not in out:
        raise RuntimeError(f"tp-4 gate {gate}, losses {losses}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    print(f"[four-chips] tp 4: gate {gate[0]}; losses {losses}; "
          f"peak bytes per chip {peaks}")

    # the same weights and first batch, forward only, at tp 2 x dp 2
    cfg = get_config("qwen3_4b", smoke=smoke)
    mesh = make_debug_mesh(tp=2, dp=2)
    model = Model(cfg, ParallelCtx.from_mesh(mesh, dp=("data",)))
    key = jax.random.PRNGKey(SEED)
    pspecs = param_specs(jax.eval_shape(model.init, key))
    params = jax.jit(model.init, out_shardings=jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs))(key)
    batch = SyntheticLM(DataConfig(cfg.vocab, FOUR_CHIP_SEQ, FOUR_CHIP_BATCH,
                                   seed=SEED)).batch_at(0)
    loss_fn = jax.jit(shard_map(
        lambda p, b: lax.pmean(model.loss(p, b), "data"), mesh=mesh,
        in_specs=(pspecs, batch_spec(batch, ("data",))), out_specs=P(),
        check_vma=False))
    ref = float(loss_fn(params, batch))
    rel = abs(losses[0] - ref) / abs(ref)
    print(f"[four-chips] step-0 loss tp4 {losses[0]} vs tp2xdp2 forward {ref}: "
          f"rel diff {rel} (tol {LOSS_TOL})")
    if not rel <= LOSS_TOL:
        raise RuntimeError(f"tp-4 loss disagrees with tp2xdp2 by {rel}")


# ---------------------------------------------------------------- driver
class CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from JAX's
    monitoring events."""

    def __init__(self, monitoring):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp-4 training path and its tp2xdp2 check")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1

    stats = CompileStats(jax.monitoring)
    phases = ([("four-chips", phase_four_chip)] if args.four_chips else
              [("gate", phase_gate), ("serve", phase_serve),
               ("train", phase_train), ("kernels", phase_kernels)])
    for name, phase in phases:
        t0, (c0, h0, m0) = time.time(), stats.snapshot()
        phase()
        c1, h1, m1 = stats.snapshot()
        print(f"[phase] {name} ok: {time.time() - t0}s wall, "
              f"{c1 - c0}s backend compile, cache hits {h1 - h0} "
              f"misses {m1 - m0}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
