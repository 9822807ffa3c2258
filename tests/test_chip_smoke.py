"""Guards around the chip path, on CPU: ``chip_smoke.py`` refuses to report
without a TPU, the compile-cache helper places the cache from outside, the
Pallas kernels never fall back to the interpreter, and the smoke's phases
run end to end at smoke size (kernels in interpret mode, forced here)."""
import dataclasses
import functools
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, qwen3_4b
from repro.kernels import ops
from repro.launch import compile_cache
from repro.models import Model

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _run_smoke(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, str(cwd / "chip_smoke.py")], cwd=cwd,
                          capture_output=True, text=True, env=env, timeout=300)


def _no_ok_line(stdout: str) -> bool:
    return all('"ok": true' not in line for line in stdout.splitlines())


def test_chip_smoke_refuses_cpu():
    out = _run_smoke(ROOT)
    assert out.returncode != 0, out.stdout
    assert _no_ok_line(out.stdout), out.stdout
    assert "platform=cpu" in out.stdout


def test_chip_smoke_refuses_without_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run_smoke(tmp_path)
    assert out.returncode != 0 and _no_ok_line(out.stdout), out.stdout


# ---------------------------------------------------------------- compile cache
@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert first == compile_cache.use_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


# ---------------------------------------------------------------- no fallback
_X = jnp.zeros((1, 2, 128, 64), jnp.float32)
_DT = jnp.ones((1, 128, 2), jnp.float32)
_BC = jnp.zeros((1, 128, 16), jnp.float32)


@pytest.mark.parametrize("call", [
    lambda: ops.flash_attention(_X, _X, _X),
    lambda: ops.ssd_scan(_X.transpose(0, 2, 1, 3), _DT, -jnp.ones((2,)), _BC, _BC),
    lambda: ops.rmsnorm(_X, jnp.ones((64,))),
], ids=["flash_attention", "ssd_scan", "rmsnorm"])
def test_pallas_defaults_refuse_cpu(call):
    if jax.default_backend() != "cpu":
        pytest.skip("checks the CPU backend")
    with pytest.raises(ValueError, match="interpret"):
        call()


@pytest.mark.parametrize("arch", ["qwen3_4b", "mamba2_130m"])
def test_model_pallas_impl_refuses_cpu(arch):
    if jax.default_backend() != "cpu":
        pytest.skip("checks the CPU backend")
    model = Model(get_config(arch, smoke=True), impl="pallas")
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 128), jnp.int32)
    with pytest.raises(ValueError, match="interpret"):
        model.forward(params, {"tokens": tokens})


# ---------------------------------------------------------------- phases
def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the published qwen3_4b smoke config has 2 kv heads; the tp-4 gate needs 4
_KV_PAD = "qwen3_4b.SMOKE = dataclasses.replace(qwen3_4b.SMOKE, n_kv_heads_padded=4)"


@pytest.fixture
def smoke_sized(monkeypatch, tmp_path):
    """Kernels in interpret mode, and a tp-4-able qwen3_4b smoke config; the
    launchers' cache helper sees a cache placed from outside, so it leaves
    this process's JAX config alone."""
    monkeypatch.setattr(qwen3_4b, "SMOKE",
                        dataclasses.replace(qwen3_4b.SMOKE, n_kv_heads_padded=4))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(ops, "flash_attention",
                        functools.partial(ops.flash_attention, interpret=True))
    monkeypatch.setattr(ops, "ssd_scan", functools.partial(ops.ssd_scan, interpret=True))


@pytest.mark.parametrize("phase", ["gate", "serve", "train", "kernels"])
def test_chip_smoke_phase_at_smoke_size(phase, smoke_sized, capsys):
    getattr(_load_smoke(), f"phase_{phase}")(smoke=True)
    assert f"[{phase}]" in capsys.readouterr().out


_FOUR_CHIP = textwrap.dedent("""
    import dataclasses, importlib.util
    from repro.configs import qwen3_4b
    {kv_pad}
    spec = importlib.util.spec_from_file_location("chip_smoke", {path!r})
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.phase_four_chip(smoke=True)
""")


def test_chip_smoke_four_chip_phase_at_smoke_size(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", _FOUR_CHIP.format(path=str(SMOKE), kv_pad=_KV_PAD)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr[-3000:]
    assert "gate VERIFIED" in out.stdout and "rel diff" in out.stdout
