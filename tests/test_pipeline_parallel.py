"""GPipe pipeline-parallel training step vs single-device reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.models import get_model_by_name
from kaito_tpu.parallel.mesh import build_mesh
from kaito_tpu.parallel.pipeline import (
    merge_stage_params,
    pipeline_loss_fn,
    split_stage_params,
)
from kaito_tpu.parallel.plan import make_mesh_spec
from kaito_tpu.tuning.train_step import cross_entropy_loss

TINY = get_model_by_name("tiny-llama-test").arch  # 4 layers


def _batch(B=4, T=16, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "tokens": jnp.asarray(rng.randint(0, TINY.vocab_size, (B, T + 1)),
                              jnp.int32),
        "mask": jnp.ones((B, T), jnp.float32),
    }


def _reference_loss(model, params, batch):
    logits = model.forward_train(params, batch["tokens"][:, :-1], remat=False)
    return cross_entropy_loss(logits, batch["tokens"][:, 1:], batch["mask"])


@pytest.mark.parametrize("stages,microbatches", [(2, 2), (4, 4), (2, 4)])
def test_pipeline_loss_matches_reference(cpu_devices, stages, microbatches):
    model = TransformerLM(TINY, dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _batch(B=microbatches * 2)
    ref = _reference_loss(model, params, batch)

    mesh = build_mesh(make_mesh_spec(pipeline=stages),
                      cpu_devices[:stages])
    staged = split_stage_params(model, params, stages)
    loss_fn = pipeline_loss_fn(model, mesh, microbatches)
    got = jax.jit(loss_fn)(staged, batch)
    np.testing.assert_allclose(float(got), float(ref), rtol=2e-5)


# slow: 95 s alone under the check's command (the backward pass of every
# schedule compiles)
@pytest.mark.slow
def test_pipeline_gradients_match_reference(cpu_devices):
    model = TransformerLM(TINY, dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(1))
    batch = _batch(B=4, seed=2)

    g_ref = jax.grad(lambda p: _reference_loss(model, p, batch))(params)

    stages = 2
    mesh = build_mesh(make_mesh_spec(pipeline=stages), cpu_devices[:stages])
    staged = split_stage_params(model, params, stages)
    loss_fn = pipeline_loss_fn(model, mesh, 2)
    g_pp = jax.grad(loss_fn)(staged, batch)
    g_pp = merge_stage_params(model, g_pp)

    for key in ("q", "down", "attn_norm"):
        np.testing.assert_allclose(
            np.asarray(g_pp["dense"][key]), np.asarray(g_ref["dense"][key]),
            rtol=5e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_pp["embed"]),
                               np.asarray(g_ref["embed"]),
                               rtol=5e-4, atol=1e-6)


def test_split_merge_roundtrip():
    model = TransformerLM(TINY, dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    staged = split_stage_params(model, params, 2)
    assert staged["dense"]["q"].shape[0] == 2
    back = merge_stage_params(model, staged)
    np.testing.assert_array_equal(np.asarray(back["dense"]["q"]),
                                  np.asarray(params["dense"]["q"]))
    with pytest.raises(ValueError, match="stages"):
        split_stage_params(model, params, 3)
