"""Granite-3.0-3B-A800M on the training path, against plain references at a
small size on the CPU: the model with Granite's four multipliers against the
benchmark's float32 reference (``bench/configs/granite_moe_3b_train.py``),
the dropless MoE against the dense-masked formulation (also where a
capacity would have dropped tokens), the Pallas grouped matmul against
``jax.lax.ragged_dot``, the TP-4 ranks' expert parts against the whole layer,
and flash attention at a softmax scale of its own."""
import dataclasses
import functools
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import Model
from repro.models.attention import chunked_attention, flash_pallas
from repro.models.mlp import _layout, moe_dense_fwd, moe_fwd, moe_init
from repro.parallel.ctx import ParallelCtx

ROOT = Path(__file__).resolve().parents[1]
REF_PATH = ROOT / "bench" / "configs" / "granite_moe_3b_train.py"


@pytest.fixture
def interpret_kernels(monkeypatch):
    monkeypatch.setattr(ops, "flash_attention",
                        functools.partial(ops.flash_attention, interpret=True))
    monkeypatch.setattr(ops, "moe_gmm", functools.partial(ops.moe_gmm, interpret=True))


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("granite_ref", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _f32(cfg):
    return dataclasses.replace(cfg, dtype="float32", n_experts_padded=0)


def test_registry_holds_the_published_model():
    pub = json.loads((ROOT / "bench" / "configs" / "granite_moe_3b_train.json").read_text())
    cfg = get_config("granite_moe_3b")
    assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (pub["embedding_multiplier"], pub["attention_multiplier"],
                                    pub["residual_multiplier"], pub["logits_scaling"])
    assert cfg.norm_eps == pub["rms_norm_eps"] and cfg.tie_embeddings
    assert cfg.attn_scale == pub["attention_multiplier"]
    assert get_config("qwen3_4b").attn_scale == get_config("qwen3_4b").hd ** -0.5


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_smoke_loss_and_grads_match_reference(ref, interpret_kernels, impl):
    """The SMOKE model in float32, with the multipliers, against the plain
    reference on the same seeded weights: the loss and every gradient leaf."""
    cfg = _f32(get_config("granite_moe_3b", smoke=True))
    d = {f: getattr(cfg, f) for f in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "n_experts",
        "top_k", "d_ff_expert", "vocab", "rope_theta", "norm_eps",
        "embedding_multiplier", "attention_multiplier", "residual_multiplier",
        "logits_scaling")}
    d["vocab_padded"] = cfg.vocab_p
    # std 0.2: logits and routing far from uniform, so the multipliers show
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    ref.init(d, jax.random.PRNGKey(3), std=0.2))
    model = Model(cfg, impl=impl)
    assert (jax.tree_util.tree_structure(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
            == jax.tree_util.tree_structure(params))
    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(model.loss)(params, batch)
        lr, gr = jax.value_and_grad(lambda p: ref.loss(p, batch, d))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gr)):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-4, atol=1e-4 * np.abs(b).max())
    # without the multipliers it is a different model, and the loss says so
    plain = dataclasses.replace(cfg, embedding_multiplier=1.0, attention_multiplier=0.0,
                                residual_multiplier=1.0, logits_scaling=1.0)
    assert abs(float(Model(plain, impl=impl).loss(params, batch)) - float(lr)) > 1e-2


def _moe_case(cfg, skew: bool):
    p = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), jnp.float32)
    if skew:  # every token's first choice is expert 0
        x = jnp.abs(x)
        p["router"]["w"] = p["router"]["w"].at[:, 0].set(10.0)
    return p, x


@pytest.mark.parametrize("skew", [False, True], ids=["random", "one_expert"])
@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_dropless_moe_matches_dense(interpret_kernels, impl, skew):
    """Output and gradients of the dropless MoE equal the dense-masked
    formulation; with every token routed to one expert (64 pairs where a
    capacity factor of 1.25 would hold 32) nothing is dropped either."""
    cfg = _f32(get_config("granite_moe_3b", smoke=True))
    p, x = _moe_case(cfg, skew)
    ctx = ParallelCtx.single()
    if skew:
        _, idx = jax.lax.top_k(x.reshape(-1, cfg.d_model) @ p["router"]["w"], cfg.top_k)
        assert int(jnp.sum(idx == 0)) == 64
    run = lambda f: (lambda p, x: jnp.sum(jnp.sin(f(p, x))))
    ours = lambda p, x: moe_fwd(cfg, ctx, p, x, impl=impl)
    dense = lambda p, x: moe_dense_fwd(cfg, ctx, p, x)
    np.testing.assert_allclose(np.asarray(ours(p, x)), np.asarray(dense(p, x)),
                               rtol=1e-5, atol=1e-5)
    ga = jax.grad(run(ours), argnums=(0, 1))(p, x)
    gb = jax.grad(run(dense), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("counts", [[5, 0, 17, 9], [0, 0, 40, 0]], ids=["mixed", "one_group"])
def test_moe_gmm_matches_ragged_dot(counts):
    """The Pallas grouped matmul (interpret mode) and its vjp against
    ``jax.lax.ragged_dot`` on the same tile-aligned layout, empty groups
    included."""
    tm, G, k, n = 8, len(counts), 32, 16
    idx = jnp.concatenate([jnp.full((c,), g, jnp.int32) for g, c in enumerate(counts)])
    idx = jax.random.permutation(jax.random.PRNGKey(0), idx)[:, None]  # K = 1
    _, _, row_pair, tile_group, n_tiles, sizes = _layout(idx, 0, G, tm)
    used = int(n_tiles[0]) * tm
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    real = (row_pair < idx.size)[:, None]
    lhs = jnp.where(real, jax.random.normal(ks[0], (row_pair.size, k)), 0.0)
    rhs = jax.random.normal(ks[1], (G, k, n))
    ct = jax.random.normal(ks[2], (row_pair.size, n)).at[used:].set(0.0)
    gmm = lambda a, b: ops.moe_gmm(a, b, tile_group, n_tiles, tm=tm, interpret=True)
    rdot = lambda a, b: jax.lax.ragged_dot(a, b, sizes)
    out, vjp = jax.vjp(gmm, lhs, rhs)
    want, want_vjp = jax.vjp(rdot, lhs, rhs)
    np.testing.assert_allclose(np.asarray(out[:used]), np.asarray(want[:used]),
                               rtol=1e-5, atol=1e-5)
    (da, db), (wa, wb) = vjp(ct), want_vjp(ct)
    np.testing.assert_allclose(np.asarray(da[:used]), np.asarray(wa[:used]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(db), np.asarray(wb), rtol=1e-5, atol=1e-5)


SHARE = textwrap.dedent("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.models.mlp import moe_dense_fwd, moe_fwd, moe_init, moe_part
    from repro.parallel.ctx import ParallelCtx
    from repro.parallel.sharding import param_specs

    assert len(jax.devices()) == 4, jax.devices()
    cfg = dataclasses.replace(get_config("{arch}", smoke=True), dtype="float32",
                              n_experts_padded=8)
    p = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
    whole = np.asarray(moe_dense_fwd(cfg, ParallelCtx.single(), p, x))
    mesh = make_debug_mesh(tp=4, dp=1)
    ctx = ParallelCtx.from_mesh(mesh, dp=None)
    specs = param_specs({{"blocks": ({{"moe": jax.eval_shape(lambda: p)}},)}})
    specs = jax.tree_util.tree_map(lambda s: P(*tuple(s)[1:]), specs["blocks"][0]["moe"])

    def parts(p, x):  # each rank's experts' part, unsummed
        e_loc = cfg.experts // 4
        first = jax.lax.axis_index("model") * e_loc
        return moe_part(cfg, p, x.reshape(-1, cfg.d_model), first, e_loc)[None]

    with mesh:
        got = jax.jit(shard_map(parts, mesh=mesh, in_specs=(specs, P()),
                                out_specs=P("model"), check_vma=False))(p, x)
        full = jax.jit(shard_map(lambda p, x: moe_fwd(cfg, ctx, p, x), mesh=mesh,
                                 in_specs=(specs, P()), out_specs=P(),
                                 check_vma=False))(p, x)
    shared = 0.0
    if "shared" in p:  # what every rank computes alike counts once
        from repro.models.mlp import _shared_fwd
        shared = np.asarray(_shared_fwd(cfg, p["shared"], x))
    summed = np.asarray(got).sum(0).reshape(whole.shape) + shared
    np.testing.assert_allclose(summed, whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(full), whole, rtol=1e-5, atol=1e-5)
    print("OK")
""")


@pytest.mark.parametrize("arch", ["granite_moe_3b", "moonshot_v1_16b"])
def test_expert_shares_sum_to_whole_layer(arch):
    """On 4 host CPU devices, each holding a quarter of the experts: the four
    ranks' local expert parts, summed (a shared expert counted once), equal
    the whole layer, and so does the layer as the TP-4 step runs it (its
    psums, the shared expert's included)."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", SHARE.format(arch=arch)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]


@pytest.mark.parametrize("scale", [0.015625, 0.3])
def test_flash_attention_custom_scale(interpret_kernels, scale):
    """Pallas flash attention and its vjp at a softmax scale other than
    1/sqrt(head dim) (Granite: 1/64) against the jnp path."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (1, 6, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    ref_fn = lambda q, k, v: chunked_attention(q, k, v, causal=True, scale=scale, chunk=32)
    out, vjp = jax.vjp(lambda q, k, v: flash_pallas(q, k, v, True, scale), q, k, v)
    want, want_vjp = jax.vjp(ref_fn, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)
    ct = jax.random.normal(ks[3], out.shape)
    for a, b in zip(vjp(ct), want_vjp(ct)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)
    # the scale is applied: the default 1/8 gives another answer
    other = chunked_attention(q, k, v, causal=True, chunk=32)
    assert float(jnp.abs(other - want).max()) > 1e-2


REHEARSE = textwrap.dedent("""
    import functools, sys
    sys.path[:0] = [{root!r}, {root!r} + "/src"]
    from repro.kernels import ops
    ops.flash_attention = functools.partial(ops.flash_attention, interpret=True)
    ops.moe_gmm = functools.partial(ops.moe_gmm, interpret=True)
    from bench import run
    sys.exit(run.main(["--workload", "granite_moe_3b.train_tp4", "--seed", "2147483001",
                       "--seconds", "2", "--trace", "0", "--rehearse"]))
""")


def test_train_tp4_cell_rehearses_correct():
    """The benchmark cell end to end at its rehearsal size on 4 host CPU
    devices: the benchmark's TP-4 train step against the plain reference,
    within the cell's limits."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", REHEARSE.format(root=str(ROOT))],
                         capture_output=True, text=True, env=env, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


def _metric(name: str):
    """A benchmark metric reader, loaded from its file as the harness does."""
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    metric = importlib.util.module_from_spec(spec)
    sys.path.insert(0, str(ROOT))
    try:
        spec.loader.exec_module(metric)
    finally:
        sys.path.remove(str(ROOT))
    return metric


def test_moe_gmm_roofline_reads_both_kernels():
    """The cell's kernel metric counts the forward's and the backward's
    grouped-matmul calls (``moe_gmm``, ``moe_tgmm``) against 2 R D F
    operations and their bytes each, R = 4096 x 8 / 4 routed rows a chip,
    and reads nothing where the trace holds no such call."""
    from types import SimpleNamespace

    metric = _metric("moe_gmm_roofline")
    conf = json.loads((ROOT / "bench" / "configs" / "granite_moe_3b_train.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "train_tp4.json").read_text())
    calls = {"jvp_moe_gmm_.1": (6, 6e-4), "transpose_jvp_moe_tgmm__.3": (3, 6e-4),
             "flash_attention.2": (8, 1.0)}
    trace = SimpleNamespace(kernel_calls=lambda w: tuple(
        map(sum, zip((0, 0.0), *[v for n, v in calls.items() if w in n]))))
    run = SimpleNamespace(data={"trace": trace}, devices=[SimpleNamespace(device_kind="TPU v5 lite")],
                          cell=SimpleNamespace(config=conf, traffic=traffic))
    flops, nbytes = metric.call_cost(4096, 8, 4, 40, 1536, 512)
    assert flops == 2 * 8192 * 1536 * 512
    assert nbytes == 2 * (8192 * (1536 + 512) + 10 * 1536 * 512)
    want = 100 * 9 * max(flops / 197e12, nbytes / 819e9) / 1.2e-3
    assert metric.read(run) == pytest.approx(want)
    calls.clear()
    assert metric.read(run) is None


def test_collective_share_counts_primitive_named_all_reduces():
    """The TP all-reduces reach the trace under the names of the jax
    primitives they lower from (``psum.275``, ``pmax.20``), which the trace
    reader's collective words miss: the cell's collective metric adds their
    time to the exposed time the reader found, and leaves other ops out."""
    from types import SimpleNamespace

    metric = _metric("collective.exposed_share")
    op_s = {"psum.275": 0.3, "pmax.20": 0.1, "all-reduce.8": 0.05,
            "fusion.psum_like": 0.7, "flash_attention.6": 1.0}
    t = SimpleNamespace(busy_s=2.0, op_s=op_s, collective_s=0.05,
                        exposed_collective_s=0.04)
    run = SimpleNamespace(data={"trace": t})
    assert metric.read(run) == pytest.approx(100 * (0.04 + 0.3 + 0.1) / 2.0)
    t.busy_s = 0.0
    assert metric.read(run) is None
    assert metric.read(SimpleNamespace(data={})) is None
