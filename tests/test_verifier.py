"""End-to-end verifier tests: real jax traces, partitioning/memoization,
the injected-bug suite (paper Tables 4/5 analogue), and framework layers."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core import (
    inject_all,
    trace,
    trace_sharded,
    verify_graphs,
    verify_sharded,
)
from repro.core.relations import DUP, SHARD
from repro.core.verifier import InputFact, VerifyOptions

C = 8
B, H, F, L = 4, 32, 64, 6


def base_fn(x, w1s, w2s):
    for i in range(L):
        with jax.named_scope(f"layer{i}"):
            h = jnp.tanh(x @ w1s[i])
            x = h @ w2s[i] + x
    return x


def dist_fn(x, w1s, w2s):
    for i in range(L):
        with jax.named_scope(f"layer{i}"):
            h = jnp.tanh(x @ w1s[i])
            x = jax.lax.psum(h @ w2s[i], "model") + x
    return x


AVALS = (
    jax.ShapeDtypeStruct((B, H), jnp.float32),
    jax.ShapeDtypeStruct((L, H, F), jnp.float32),
    jax.ShapeDtypeStruct((L, F, H), jnp.float32),
)
SPECS = (P(), P(None, None, "model"), P(None, "model", None))


def test_verify_megatron_stack():
    rep = verify_sharded(base_fn, dist_fn, *AVALS, size=C, in_specs=SPECS, out_specs=P())
    assert rep.verified
    assert rep.memo is not None and rep.memo.memo_hits == L - 1
    assert rep.num_facts > 50


def test_verify_without_partitioning_agrees():
    rep = verify_sharded(
        base_fn, dist_fn, *AVALS, size=C, in_specs=SPECS, out_specs=P(),
        options=VerifyOptions(partition=False))
    assert rep.verified


@pytest.fixture(scope="module")
def traced_pair():
    mesh = AbstractMesh((C,), ("model",))
    gb, b_in, _ = trace(base_fn, *AVALS, name="base")
    gd, d_in, _ = trace_sharded(dist_fn, mesh, SPECS, P(), *AVALS)
    facts = [InputFact(DUP, 0, 0), InputFact(SHARD, 1, 1, 2), InputFact(SHARD, 2, 2, 1)]
    return gb, gd, b_in, d_in, facts


def test_injection_suite_detected_and_localized(traced_pair):
    """Every injected silent error is detected; the bug site is localized to
    the exact source line (paper §5.3 / Tables 4-5)."""
    gb, gd, b_in, d_in, facts = traced_pair
    clean = verify_graphs(gb, gd, size=C, input_facts=facts,
                          base_inputs=b_in, dist_inputs=d_in)
    assert clean.verified

    injections = inject_all(gd)
    assert len(injections) >= 6
    detected = localized = categorized = 0
    for inj in injections:
        rep = verify_graphs(gb, inj.graph, size=C, input_facts=facts,
                            base_inputs=b_in, dist_inputs=d_in)
        assert not rep.verified, f"{inj.name} NOT detected"
        detected += 1
        if any(b.src == inj.site for b in rep.bug_sites):
            localized += 1
        if any(b.category == inj.category for b in rep.bug_sites):
            categorized += 1
    assert detected == len(injections)
    assert localized == len(injections), "all bugs must localize to their site"
    assert categorized >= len(injections) - 2  # category labels are best-effort


def test_layout_bug_repair_suggestion(traced_pair):
    """The BSH-style reshape bug must come with a synthesized repair
    bijection (Algorithm 2 output, as in paper Fig. 9/10)."""
    from repro.core.inject import swap_reshape_dims

    gb, gd, b_in, d_in, facts = traced_pair
    inj = swap_reshape_dims(gd)
    assert inj is not None
    rep = verify_graphs(gb, inj.graph, size=C, input_facts=facts,
                        base_inputs=b_in, dist_inputs=d_in)
    assert not rep.verified
    repairs = [b.repair for b in rep.bug_sites if b.repair]
    assert repairs, "expected a synthesized repair sequence"
    ops = [op for op, _ in repairs[0]]
    assert "transpose" in ops


def test_verify_sequence_parallel_region():
    """SP (reduce_scatter + all_gather) verifies equivalent to plain psum."""

    def base(x, w1, w2):
        h = jnp.tanh(x @ w1)
        return h @ w2

    def dist_sp(x, w1, w2):
        h = jnp.tanh(x @ w1)
        y = h @ w2
        y = jax.lax.psum_scatter(y, "model", scatter_dimension=0, tiled=True)
        return jax.lax.all_gather(y, "model", axis=0, tiled=True)

    avals = (
        jax.ShapeDtypeStruct((16, H), jnp.float32),
        jax.ShapeDtypeStruct((H, F), jnp.float32),
        jax.ShapeDtypeStruct((F, H), jnp.float32),
    )
    rep = verify_sharded(
        base, dist_sp, *avals, size=C,
        in_specs=(P(), P(None, "model"), P("model", None)), out_specs=P())
    assert rep.verified, rep.summary()


def test_verify_vocab_parallel_loss_pattern():
    """Vocab-parallel logsumexp: pmax(max) + psum(sum exp) == full-logit."""

    def base(lg):
        m = lg.max(axis=-1)
        return jnp.log(jnp.exp(lg - m[..., None]).sum(-1)) + m

    def dist(lg):
        m = jax.lax.pmax(lg.max(axis=-1), "model")
        return jnp.log(jax.lax.psum(jnp.exp(lg - m[..., None]).sum(-1), "model")) + m

    avals = (jax.ShapeDtypeStruct((B, 64), jnp.float32),)
    rep = verify_sharded(base, dist, *avals, size=C,
                         in_specs=(P(None, "model"),), out_specs=P())
    assert rep.verified, rep.summary()
