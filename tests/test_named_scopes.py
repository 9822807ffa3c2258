"""The train step names its parts for the device trace: ``ssd_bwd`` (the
jnp backward of the Pallas SSD scan), ``microbatch`` (each value_and_grad)
and ``optimizer`` (the AdamW/ZeRO-1 update) reach the lowered step's HLO
metadata, where a profile's op names and scopes come from."""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import Model
from repro.train.optimizer import adamw_init
from repro.train.trainer import TrainConfig, make_step_fn


@pytest.mark.parametrize("microbatches", [1, 2])
def test_step_hlo_carries_the_scope_names(monkeypatch, microbatches):
    monkeypatch.setattr(ops, "ssd_scan",
                        functools.partial(ops.ssd_scan, interpret=True))
    cfg = get_config("mamba2_130m", smoke=True)
    model = Model(cfg, impl="pallas")
    step = make_step_fn(model, TrainConfig(microbatches=microbatches,
                                           remat=False))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw_init, params)
    row = jax.ShapeDtypeStruct((2, cfg.ssm_chunk), jnp.int32)
    text = jax.jit(step).lower(params, opt, {"tokens": row, "labels": row}
                               ).as_text(debug_info=True)
    for scope in ("ssd_bwd", "microbatch", "optimizer"):
        assert f"{scope}/" in text, scope
