"""The Pallas kernels compile with Mosaic for a described TPU v5e at the
widths the chip smoke runs them: flash attention at qwen3_4b (prefill and
decode) and at one chip's heads of the four-chip Granite training step, the
SSD scan at mamba2_130m, RMSNorm at qwen3_4b.  Nothing runs — this catches
what interpret mode accepts and the chip's compiler refuses (block shapes off
the (8, 128) tiling, too much VMEM)."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd_scan as ssd

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep the persistent cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch,tp,sq,sk,causal", [
    ("qwen3_4b", 1, 2048, 2048, True), ("qwen3_4b", 1, 1, 512, False),
    ("granite_moe_3b", 4, 4096, 4096, True),
], ids=["prefill", "decode", "granite_train_tp4"])
def test_flash_attention_compiles_qwen3_4b(spec, arch, tp, sq, sk, causal):
    """Tiles from the shape; granite_train_tp4 is one chip's heads of the
    four-chip Granite training step (6 query, 2 kv, head_dim 64), whose causal
    schedule clamps the k/v index maps at 512-row tiles."""
    cfg = get_config(arch)
    q = spec((1, cfg.n_heads // tp, sq, cfg.hd), BF16)
    kv = spec((1, cfg.n_kv_heads // tp, sk, cfg.hd), BF16)
    _compiles_to_kernel(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, scale=cfg.attn_scale), q, kv, kv)


@pytest.mark.parametrize("heads", ["published", "padded"])
def test_ssd_scan_compiles_mamba2_130m(spec, heads):
    cfg = get_config("mamba2_130m")
    B, S, P, N = 2, 1024, cfg.ssm_head_dim, cfg.ssm_state
    H = cfg.ssm_heads if heads == "published" else cfg.ssm_heads_p
    _compiles_to_kernel(
        lambda *a: ssd.ssd_scan(*a, chunk=cfg.ssm_chunk),
        spec((B, S, H, P), BF16), spec((B, S, H), F32), spec((H,), F32),
        spec((B, S, N), BF16), spec((B, S, N), BF16))


@pytest.mark.parametrize("rows", [2048, 2])
def test_rmsnorm_compiles_qwen3_4b(spec, rows):
    d = get_config("qwen3_4b").d_model
    _compiles_to_kernel(lambda x, s: rn.rmsnorm(x, s), spec((rows, d), BF16),
                        spec((d,), BF16))


@pytest.mark.parametrize("which", ["gate", "down", "grad_lhs", "grad_w"])
def test_moe_gmm_compiles_granite_moe_3b(spec, which):
    """The grouped expert matmuls of one TP-4 rank of granite_moe_3b: 10 of 40
    experts, one 4096-token row routed top-8."""
    from repro.kernels import moe_gmm
    from repro.models.mlp import GMM_TILE as tm

    cfg = get_config("granite_moe_3b")
    E, D, F = cfg.n_experts // 4, cfg.d_model, cfg.d_ff_expert
    m = 4096 * cfg.top_k + E * tm
    grp, nt = spec((m // tm,), jnp.int32), spec((1,), jnp.int32)
    if which == "gate":
        fn = lambda a, w, g, n: moe_gmm.gmm(a, w, g, n, tm=tm)
        args = (spec((m, D), BF16), spec((E, D, F), BF16))
    elif which == "down":
        fn = lambda a, w, g, n: moe_gmm.gmm(a, w, g, n, tm=tm)
        args = (spec((m, F), BF16), spec((E, F, D), BF16))
    elif which == "grad_lhs":
        fn = lambda a, w, g, n: moe_gmm.gmm(a, w, g, n, tm=tm, transpose_rhs=True)
        args = (spec((m, F), BF16), spec((E, D, F), BF16))
    else:
        fn = lambda a, b, g, n: moe_gmm.tgmm(a, b, g, n, groups=E, tm=tm)
        args = (spec((m, D), BF16), spec((m, F), BF16))
    text = jax.jit(fn).lower(*args, grp, nt).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("moe_tgmm" if which == "grad_w" else "moe_gmm") in text
