"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles across
shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels.ref import attention_ref, rmsnorm_ref, ssd_ref
from repro.models.attention import chunked_attention
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


def _case(B, Hq, Hkv, Sq, Sk, D, bq=64, bk=64, id=None):
    return pytest.param(B, Hq, Hkv, Sq, Sk, D, bq, bk,
                        id=id or f"{B}-{Hq}-{Hkv}-{Sq}-{Sk}-{D}")


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,D,bq,bk", [
    _case(1, 2, 2, 128, 128, 64),     # MHA square
    _case(2, 4, 2, 128, 128, 64),     # GQA
    _case(1, 4, 1, 64, 256, 64),      # MQA, cross lengths
    _case(1, 2, 2, 256, 256, 128),    # head_dim 128
    _case(1, 8, 2, 96, 160, 32),      # non-multiple of block
    # bq != bk: the diagonal crosses blocks inside and runs along their edges
    _case(1, 2, 1, 384, 384, 64, 128, 64, id="bq128-bk64"),
    _case(1, 2, 1, 384, 384, 64, 64, 128, id="bq64-bk128"),
    _case(1, 2, 2, 200, 200, 64, id="kv-tail"),  # last key block padded 56 of 64
    # the four-chip Granite cell's heads a chip (6 query, 2 kv, head_dim 64),
    # tiles from the shape: two 512-row blocks a side, three pairs visited
    _case(1, 6, 2, 1024, 1024, 64, None, None, id="gqa-6-2-shape-tiles"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(B, Hq, Hkv, Sq, Sk, D, bq, bk, dtype, causal):
    if causal and Sq != Sk:
        pytest.skip("causal offset semantics only tested square here")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                              interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk", [(None, None), (128, 128), (256, 128), (128, 512)])
def test_flash_attention_visited_blocks(bq, bk, causal):
    """The causal schedule's count: at 4096 tokens, the block pairs on or under
    the diagonal (every pair without ``causal``)."""
    S = 4096
    if bq is None:
        bq, bk = fa.block_sizes(S, S)
        assert (bq, bk) == (512, 512)
    n_q, n_k = S // bq, S // bk
    lower = sum(ik * bk <= iq * bq + bq - 1 for iq in range(n_q) for ik in range(n_k))
    if bq == bk:
        assert lower == n_q * (n_q + 1) // 2
    assert fa.visited_blocks(S, S, bq, bk, causal) == (
        lower if causal else n_q * n_k, n_q * n_k)


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


def _assert_vjp_matches(fn, ref_fn, args, tol, scaled=False):
    """Same forward, and the same gradients of a random projection.  With
    ``scaled`` the absolute tolerance is ``tol`` times the reference's largest
    magnitude, for gradients that reach 1e5."""
    out, vjp = jax.vjp(fn, *args)
    ref, ref_vjp = jax.vjp(ref_fn, *args)
    ct = jax.random.normal(jax.random.PRNGKey(11), out.shape, out.dtype)
    for g, r in zip((out, *vjp(ct)), (ref, *ref_vjp(ct))):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        atol = tol * np.abs(r).max() if scaled else tol
        np.testing.assert_allclose(g, r, rtol=tol, atol=atol)


# one chunk, two chunks, and 32 chunks at the full state width; decays weak
# enough that the state entering a chunk carries over several chunks
SSD_GRAD_SHAPES = [
    (1, 64, 2, 16, 8, 64),
    (1, 128, 2, 32, 16, 64),
    (1, 1024, 2, 32, 128, 32),
]


def _ssd_args(B, S, H, P, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))),
            -0.02 * jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5),
            jax.random.normal(ks[3], (B, S, N)), jax.random.normal(ks[4], (B, S, N)))


@pytest.mark.parametrize("B,S,H,P,N,chunk", SSD_GRAD_SHAPES)
def test_ssd_pallas_vjp_matches_chunked(interpret_kernels, B, S, H, P, N, chunk):
    from repro.models.ssm import ssd_pallas

    _assert_vjp_matches(lambda *a: ssd_pallas(*a, chunk),
                        lambda *a: ssd_chunked(*a, chunk), _ssd_args(B, S, H, P, N),
                        5e-4)


@pytest.mark.parametrize("B,S,H,P,N,chunk", SSD_GRAD_SHAPES)
def test_ssd_chunked_vjp_matches_recurrence(B, S, H, P, N, chunk):
    """The chunked form and its gradients against the literal recurrence,
    a different algorithm (one step per token)."""
    _assert_vjp_matches(lambda *a: ssd_chunked(*a, chunk), ssd_ref,
                        _ssd_args(B, S, H, P, N), 2e-5, scaled=True)


def _primitives(jaxpr) -> set:
    """Names of every primitive in a jaxpr and in the jaxprs nested in it."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def test_ssd_chunked_has_no_chunk_loop():
    """The deployed form, and so the Pallas path's backward, holds no loop
    over chunks: not in the forward and not in its vjp."""
    args = _ssd_args(1, 1024, 2, 32, 128)
    fwd = lambda *a: ssd_chunked(*a, 32)

    def vjp(*a):
        out, pullback = jax.vjp(fwd, *a)
        return pullback(out)

    for fn in (fwd, vjp):
        prims = _primitives(jax.make_jaxpr(fn)(*args).jaxpr)
        assert "dot_general" in prims
        assert not prims & {"scan", "while"}, prims


@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_chunked_unroll_matches_contraction(with_init):
    """The unrolled form that verification traces and the deployed closed
    form give the same output and final state, and the same gradients."""
    B, S, H, P, N, chunk = 2, 512, 3, 16, 32, 32
    args = _ssd_args(B, S, H, P, N, seed=1)
    if with_init:
        args += (jax.random.normal(jax.random.PRNGKey(5), (B, H, P, N)),)

    def run(unroll):
        return lambda x, dt, A, Bm, Cm, s0=None: ssd_chunked(
            x, dt, A, Bm, Cm, chunk, init_state=s0, return_state=True, unroll=unroll)

    (y, s), vjp = jax.vjp(run(False), *args)
    (y_u, s_u), vjp_u = jax.vjp(run(True), *args)
    cts = (jax.random.normal(jax.random.PRNGKey(6), y.shape),
           jax.random.normal(jax.random.PRNGKey(7), s.shape))
    for a, b in zip((y, s, *vjp(cts)), (y_u, s_u, *vjp_u(cts))):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=2e-5, atol=2e-5 * np.abs(b).max())
    np.testing.assert_array_equal(np.asarray(ssd_chunked(*args[:5], chunk, *args[5:])),
                                  np.asarray(y))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_vjp_matches_chunked(interpret_kernels, causal):
    from repro.models.attention import flash_pallas

    ks = jax.random.split(KEY, 3)
    args = (jax.random.normal(ks[0], (1, 4, 128, 64)),
            jax.random.normal(ks[1], (1, 2, 128, 64)),
            jax.random.normal(ks[2], (1, 2, 128, 64)))
    _assert_vjp_matches(lambda *a: flash_pallas(*a, causal, 64 ** -0.5),
                        lambda *a: chunked_attention(*a, causal=causal), args, 2e-4)
