import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.models import get_model_by_name
from kaito_tpu.parallel.mesh import build_mesh
from kaito_tpu.parallel.plan import make_mesh_spec
from kaito_tpu.tuning import TrainState, make_train_step, shard_train_state
from kaito_tpu.tuning.train_step import cross_entropy_loss, data_sharding

TINY = get_model_by_name("tiny-llama-test").arch


def _state(model, optimizer):
    params = model.init_params(jax.random.PRNGKey(0))
    return TrainState(params=params, opt_state=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32))


def test_loss_decreases_single_device():
    model = TransformerLM(TINY, dtype=jnp.float32)
    opt = optax.adamw(1e-3)
    state = _state(model, opt)
    step = jax.jit(make_train_step(model, opt))
    rng = np.random.RandomState(0)
    batch = {
        "tokens": jnp.asarray(rng.randint(0, TINY.vocab_size, (2, 33)), jnp.int32),
        "mask": jnp.ones((2, 32), jnp.float32),
    }
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert int(state.step) == 5


def test_masked_loss_ignores_padding():
    logits = jnp.zeros((1, 4, 8), jnp.float32)
    targets = jnp.zeros((1, 4), jnp.int32)
    full = cross_entropy_loss(logits, targets, jnp.ones((1, 4)))
    half = cross_entropy_loss(logits, targets, jnp.asarray([[1.0, 1.0, 0.0, 0.0]]))
    np.testing.assert_allclose(float(full), float(half), rtol=1e-6)


def test_sharded_train_step_8dev(cpu_devices):
    """Full train step over fsdp×seq×tensor mesh matches single-device."""
    model = TransformerLM(TINY, dtype=jnp.float32)
    opt = optax.adamw(1e-3)
    rng = np.random.RandomState(1)
    batch_np = rng.randint(0, TINY.vocab_size, (4, 65))

    # single device reference
    state1 = _state(model, opt)
    step1 = jax.jit(make_train_step(model, opt))
    batch = {"tokens": jnp.asarray(batch_np, jnp.int32),
             "mask": jnp.ones((4, 64), jnp.float32)}
    _, m1 = step1(state1, batch)

    spec = make_mesh_spec(fsdp=2, sequence=2, tensor=2)
    mesh = build_mesh(spec)
    ring_model = TransformerLM(TINY, dtype=jnp.float32)
    ring_model.ring = (mesh, "sequence")   # real SP in the sharded step
    with mesh:
        state8 = shard_train_state(ring_model, _state(ring_model, opt), mesh)
        ds = data_sharding(mesh)
        batch8 = {
            "tokens": jax.device_put(batch["tokens"], ds["tokens"]),
            "mask": jax.device_put(batch["mask"], ds["mask"]),
        }
        step8 = jax.jit(make_train_step(ring_model, opt), donate_argnums=(0,))
        state8, m8 = step8(state8, batch8)
    np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]), rtol=1e-4)


# slow: 296 s alone under the check's command (six workers on eight cores): it
# boots the multi-chip dry run's child clusters one after another
@pytest.mark.slow
def test_graft_entry_dryrun(cpu_devices):
    spec = importlib.util.spec_from_file_location("graft", "__graft_entry__.py")
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    m.dryrun_multichip(8)
    m.dryrun_multichip(4)
