"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles across
shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import attention_ref, rmsnorm_ref, ssd_ref
from repro.models.attention import chunked_attention
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,D", [
    (1, 2, 2, 128, 128, 64),     # MHA square
    (2, 4, 2, 128, 128, 64),     # GQA
    (1, 4, 1, 64, 256, 64),      # MQA, cross lengths
    (1, 2, 2, 256, 256, 128),    # head_dim 128
    (1, 8, 2, 96, 160, 32),      # non-multiple of block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(B, Hq, Hkv, Sq, Sk, D, dtype, causal):
    if causal and Sq != Sk:
        pytest.skip("causal offset semantics only tested square here")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_matches_chunked_jnp():
    """Three-way: pallas == chunked-jnp == naive reference."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, 128, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 128, 64), jnp.float32)
    a = np.asarray(ops.flash_attention(q, k, v, causal=True, interpret=True))
    b = np.asarray(chunked_attention(q, k, v, causal=True, chunk=32))
    c = np.asarray(attention_ref(q, k, v, causal=True))
    np.testing.assert_allclose(a, c, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(b, c, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 128, 2, 32, 16, 32),
    (2, 256, 3, 64, 32, 64),
    (1, 64, 1, 16, 8, 64),     # single chunk
    (1, 512, 2, 32, 128, 128), # full state width
])
def test_ssd_scan_vs_recurrence(B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    ref = np.asarray(ssd_ref(x, dt, A, Bm, Cm))
    pallas = np.asarray(ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True))
    chunked = np.asarray(ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk))
    np.testing.assert_allclose(pallas, ref, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(chunked, ref, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("shape", [(4, 128), (2, 64, 256), (1, 7, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_vs_ref(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), dtype)
    out = ops.rmsnorm(x, s, interpret=True, block_rows=8)
    ref = rmsnorm_ref(x, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype))


def test_ssd_decode_state_consistency():
    """Sequential decode steps reproduce the full-sequence SSD output."""
    from repro.configs import get_config
    from repro.models.ssm import ssm_decode, ssm_fwd, ssm_init, ssm_init_cache
    from repro.parallel.ctx import ParallelCtx

    cfg = get_config("mamba2_130m", smoke=True)
    key = jax.random.PRNGKey(3)
    p = jax.tree_util.tree_map(
        lambda a: a[0], ssm_init(key, cfg, stacked=(1,), dtype=jnp.float32))
    ctx = ParallelCtx.single()
    B, S = 2, 16
    x = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32) * 0.1
    full = np.asarray(ssm_fwd(cfg, ctx, p, x), np.float32)
    cache = ssm_init_cache(cfg, B, dtype=jnp.float32)
    outs = []
    for t in range(S):
        y, cache = ssm_decode(cfg, ctx, p, x[:, t : t + 1], cache)
        outs.append(np.asarray(y, np.float32))
    dec = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(dec, full, rtol=2e-3, atol=2e-3)


@pytest.fixture
def interpret_kernels(monkeypatch):
    import functools

    monkeypatch.setattr(ops, "flash_attention",
                        functools.partial(ops.flash_attention, interpret=True))
    monkeypatch.setattr(ops, "ssd_scan", functools.partial(ops.ssd_scan, interpret=True))


def _assert_vjp_matches(fn, ref_fn, args, tol):
    """Same forward, and the same gradients of a random projection."""
    out, vjp = jax.vjp(fn, *args)
    ref, ref_vjp = jax.vjp(ref_fn, *args)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    ct = jax.random.normal(jax.random.PRNGKey(11), out.shape, out.dtype)
    for g, r in zip(vjp(ct), ref_vjp(ct)):
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(r, np.float32),
                                   rtol=tol, atol=tol)


def test_ssd_pallas_vjp_matches_chunked(interpret_kernels):
    from repro.models.ssm import ssd_pallas

    ks = jax.random.split(KEY, 5)
    B, S, H, P, N, chunk = 1, 128, 2, 32, 16, 64
    args = (jax.random.normal(ks[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))),
            -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5),
            jax.random.normal(ks[3], (B, S, N)), jax.random.normal(ks[4], (B, S, N)))
    _assert_vjp_matches(lambda *a: ssd_pallas(*a, chunk),
                        lambda *a: ssd_chunked(*a, chunk), args, 5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_vjp_matches_chunked(interpret_kernels, causal):
    from repro.models.attention import flash_pallas

    ks = jax.random.split(KEY, 3)
    args = (jax.random.normal(ks[0], (1, 4, 128, 64)),
            jax.random.normal(ks[1], (1, 2, 128, 64)),
            jax.random.normal(ks[2], (1, 2, 128, 64)))
    _assert_vjp_matches(lambda *a: flash_pallas(*a, causal),
                        lambda *a: chunked_attention(*a, causal=causal), args, 2e-4)
