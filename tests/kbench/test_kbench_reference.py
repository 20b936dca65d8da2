"""The plain reference against the program's own CPU float32 path, at
the tiny preset, for both configurations' architectures."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from manifest import Manifest
from paths import KBENCH

sys.path.insert(0, os.path.join(KBENCH, "reference"))
import dense_decoder  # noqa: E402

REHEARSAL = os.path.join(KBENCH, "testdata", "rehearsal", "BENCHMARK.json")


def _program_logprobs(config, params, tokens):
    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.models.autogen import arch_from_hf_config

    model = TransformerLM(arch_from_hf_config(config), dtype=jnp.float32)
    logits = model.forward_train(params, jnp.asarray([tokens]))[0]
    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)


def _params(config, seed=3):
    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.models.autogen import arch_from_hf_config

    model = TransformerLM(arch_from_hf_config(config), dtype=jnp.float32)
    return model.init_params(jax.random.PRNGKey(seed))


# the two architectures of BENCHMARK.json: tied head with partial
# rotary (phi-4-mini), untied head with full rotary (Mistral)
@pytest.mark.parametrize("name", ["tiny-tied-partial", "tiny-untied"])
@pytest.mark.parametrize("start", [0, 60])
def test_reference_agrees_with_the_program_on_the_cpu(name, start):
    config = Manifest(REHEARSAL).config(name)["config"]
    params = _params(config)
    tokens = [int(t) for t in np.random.RandomState(0).randint(
        0, config["vocab_size"], size=75)]
    ref = dense_decoder.forward(config, params, tokens, start)
    lp = _program_logprobs(config, params, tokens)
    want_t = np.array([lp[p, tokens[p + 1]] for p in range(start, 74)])
    want_top = np.asarray(lp.max(-1))[start:]
    # float32 on both sides: the orders of summation differ, no more
    assert np.abs(np.asarray(ref["target"])[:-1] - want_t).max() < 2e-5
    assert np.abs(np.asarray(ref["top"]) - want_top).max() < 2e-5
    assert np.isnan(ref["target"][-1])


def test_architectures_differ_as_the_configs_say():
    tied = Manifest(REHEARSAL).config("tiny-tied-partial")["config"]
    untied = Manifest(REHEARSAL).config("tiny-untied")["config"]
    assert tied["tie_word_embeddings"] and not untied["tie_word_embeddings"]
    assert tied["partial_rotary_factor"] == 0.75
    # the configuration file the first stands in for
    import json

    with open(os.path.join(KBENCH, "configs", "phi-4-mini-instruct.json")) as f:
        real = json.load(f)["config"]
    assert real["tie_word_embeddings"]
    assert real["partial_rotary_factor"] == 0.75


def test_the_reference_lists_the_perturbations_it_accepts():
    """``tolerance.py`` reads the tuple from the file's source: it must
    stay off JAX, and the device, while the reference child runs."""
    import tolerance

    path = os.path.join(KBENCH, "reference", "dense_decoder.py")
    assert tolerance.perturbations(path) == dense_decoder.PERTURBATIONS
    assert "" not in dense_decoder.PERTURBATIONS
    with pytest.raises(ValueError, match="no PERTURBATIONS"):
        tolerance.perturbations(os.path.join(KBENCH, "rooflines.py"))


@pytest.mark.parametrize("perturb", dense_decoder.PERTURBATIONS)
def test_a_cruder_computation_moves_the_reference(perturb):
    config = Manifest(REHEARSAL).config("tiny-tied-partial")["config"]
    params = _params(config)
    tokens = list(range(5, 45))
    clean = dense_decoder.forward(config, params, tokens, 0)
    crude = dense_decoder.forward(config, params, tokens, 0, perturb=perturb)
    diff = np.abs(np.asarray(clean["top"]) - np.asarray(crude["top"])).max()
    assert diff > 1e-4


def test_the_reference_refuses_what_it_does_not_implement():
    config = dict(Manifest(REHEARSAL).config("tiny-untied")["config"],
                  rope_scaling={"rope_type": "linear", "factor": 2.0})
    with pytest.raises(ValueError):
        dense_decoder.forward(config, _params(config), [1, 2, 3], 0)
