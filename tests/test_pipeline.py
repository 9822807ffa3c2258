"""Pipeline parallelism over the pod axis: GPipe schedule == sequential."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

_SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_pipeline_matches_sequential():
    _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.parallel.pipeline import pipeline_forward

        N_STAGES, N_MICRO, MB, D = 4, 6, 2, 8
        mesh = Mesh(np.array(jax.devices()[:N_STAGES]).reshape(N_STAGES), ("pod",))
        key = jax.random.PRNGKey(0)
        Ws = jax.random.normal(key, (N_STAGES, D, D), jnp.float32) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (N_MICRO, MB, D), jnp.float32)

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        ref = x
        for s in range(N_STAGES):
            ref = jax.vmap(lambda xx: stage_fn(Ws[s], xx))(ref)

        from jax import shard_map
        fn = shard_map(
            lambda w, xx: pipeline_forward(lambda p, h: stage_fn(p[0], h), w, xx,
                                           n_stages=N_STAGES),
            mesh=mesh, in_specs=(P("pod"), P()), out_specs=P(), check_vma=False)
        with mesh:
            out = jax.jit(fn)(Ws, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """))


def test_pipeline_grad_matches_sequential():
    """jax.grad through the ppermute pipeline equals the sequential grad."""
    _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.parallel.pipeline import pipeline_forward

        N_STAGES, N_MICRO, MB, D = 2, 4, 2, 6
        mesh = Mesh(np.array(jax.devices()[:N_STAGES]).reshape(N_STAGES), ("pod",))
        Ws = jax.random.normal(jax.random.PRNGKey(0), (N_STAGES, D, D), jnp.float32) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (N_MICRO, MB, D), jnp.float32)

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        def seq_loss(w):
            h = x
            for s in range(N_STAGES):
                h = jax.vmap(lambda xx: stage_fn(w[s], xx))(h)
            return jnp.sum(h * h)

        def pipe_loss(w, xx):
            out = pipeline_forward(lambda p, h: stage_fn(p[0], h), w, xx,
                                   n_stages=N_STAGES)
            # replicated output => the per-rank loss is counted n_stages
            # times under shard_map grad; normalize (see pipeline.py note)
            return jnp.sum(out * out) / N_STAGES

        gref = jax.grad(seq_loss)(Ws)
        from jax import shard_map
        fn = shard_map(jax.grad(pipe_loss), mesh=mesh,
                           in_specs=(P("pod"), P()), out_specs=P("pod"),
                           check_vma=False)
        with mesh:
            gpipe = jax.jit(fn)(Ws, x)
        np.testing.assert_allclose(np.asarray(gpipe), np.asarray(gref),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """))
