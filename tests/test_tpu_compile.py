"""The Pallas kernels compile with Mosaic for a described TPU v5e at the
widths the chip smoke runs them: flash attention at qwen3_4b (prefill and
decode), the SSD scan at mamba2_130m, RMSNorm at qwen3_4b.  Nothing runs —
this catches what interpret mode accepts and the chip's compiler refuses
(block shapes off the (8, 128) tiling, too much VMEM)."""
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


@pytest.mark.parametrize("sq,sk,causal", [(2048, 2048, True), (1, 512, False)],
                         ids=["prefill", "decode"])
def test_flash_attention_compiles_qwen3_4b(spec, sq, sk, causal):
    cfg = get_config("qwen3_4b")
    q = spec((1, cfg.n_heads, sq, cfg.hd), BF16)
    kv = spec((1, cfg.n_kv_heads, sk, cfg.hd), BF16)
    _compiles_to_kernel(lambda q, k, v: fa.flash_attention(q, k, v, causal=causal),
                        q, kv, kv)


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
