"""Quickstart: verify a Megatron-style TP parallelization with Scalify-JAX.

Runs on a single CPU (tracing only — no multi-device runtime needed):

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import trace_sharded, trace, verify_graphs
from repro.core.inject import drop_all_reduce
from repro.core.relations import DUP, SHARD
from repro.core.verifier import InputFact
from repro.verify import Session

B, H, F, LAYERS, TP = 4, 64, 256, 4, 8


def baseline(x, w1s, w2s):
    """Trusted single-device MLP stack."""
    for i in range(LAYERS):
        with jax.named_scope(f"layer{i}"):
            x = jnp.tanh(x @ w1s[i]) @ w2s[i] + x
    return x


def distributed(x, w1s, w2s):
    """Tensor-parallel version: column/row sharded with one psum per layer."""
    for i in range(LAYERS):
        with jax.named_scope(f"layer{i}"):
            x = jax.lax.psum(jnp.tanh(x @ w1s[i]) @ w2s[i], "model") + x
    return x


avals = (
    jax.ShapeDtypeStruct((B, H), jnp.float32),
    jax.ShapeDtypeStruct((LAYERS, H, F), jnp.float32),
    jax.ShapeDtypeStruct((LAYERS, F, H), jnp.float32),
)
specs = (P(), P(None, None, "model"), P(None, "model", None))

print("=== 1. verify the correct parallelization ===")
session = Session()
report = session.verify_sharded(baseline, distributed, *avals, size=TP,
                                in_specs=specs, out_specs=P())
print(report.summary())
assert report.verified

print("\n=== 2. inject a missing all-reduce and catch it ===")
from jax.sharding import AbstractMesh

mesh = AbstractMesh((TP,), ("model",))
gb, b_in, _ = trace(baseline, *avals, name="base")
gd, d_in, _ = trace_sharded(distributed, mesh, specs, P(), *avals)
bug = drop_all_reduce(gd, index=1)
facts = [InputFact(DUP, 0, 0), InputFact(SHARD, 1, 1, 2), InputFact(SHARD, 2, 2, 1)]
report = verify_graphs(gb, bug.graph, size=TP, input_facts=facts,
                       base_inputs=b_in, dist_inputs=d_in)
print(report.summary())
assert not report.verified
print(f"\ninjected at: {bug.site}  -> localized: "
      f"{any(b.src == bug.site for b in report.bug_sites)}")
