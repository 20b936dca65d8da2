"""The second configuration: Falcon-H1's plain reference against the
program's own CPU float32 path at a tiny size, the state-update
kernel's roofline reader on planted numbers, and the CPU rehearsal of a
tiny copy of ``falconh1-d6-batch`` in a temporary manifest (the
rehearsal manifest is a benchmark file and stays as it is)."""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from manifest import Manifest, load_json, validate
from paths import KBENCH, MANIFEST, ROOT
from test_kbench_rehearsal import REHEARSAL, _last_line

sys.path.insert(0, os.path.join(KBENCH, "reference"))
import falcon_h1  # noqa: E402

CELL = "falconh1-d6-batch"
CONFIG = "falcon-h1-34b-instruct-d6"
TINY_CELL = "tiny-falconh1-batch"       # no other test file runs this cell

# every key of the real configuration, the widths cut to a CPU's size
TINY = dict(load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
            ["config"],
            vocab_size=2048, hidden_size=128, intermediate_size=256,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, mamba_d_ssm=128,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=32,
            mamba_chunk_size=32, max_position_embeddings=2048)


def _model(config):
    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.models.autogen import arch_from_hf_config

    return TransformerLM(arch_from_hf_config(config), dtype=jnp.float32)


def _params(config, seed=3):
    return _model(config).init_params(jax.random.PRNGKey(seed))


@pytest.mark.parametrize("start", [0, 60])
def test_reference_agrees_with_the_program_on_the_cpu(start):
    params = _params(TINY)
    tokens = [int(t) for t in np.random.RandomState(0).randint(
        0, TINY["vocab_size"], size=75)]
    ref = falcon_h1.forward(TINY, params, tokens, start)
    with jax.default_matmul_precision("highest"):
        logits = _model(TINY).forward_train(params, jnp.asarray([tokens]),
                                            remat=False)[0]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    want_t = np.array([lp[p, tokens[p + 1]] for p in range(start, 74)])
    want_top = np.asarray(lp.max(-1))[start:]
    # float32 on both sides: a chunked scan against the token-by-token
    # recurrence, and the orders of summation differ, no more
    assert np.abs(np.asarray(ref["target"])[:-1] - want_t).max() < 5e-5
    assert np.abs(np.asarray(ref["top"]) - want_top).max() < 5e-5
    assert np.isnan(ref["target"][-1])


def test_the_reference_lists_the_perturbations_it_accepts():
    import tolerance

    path = os.path.join(KBENCH, "reference", "falcon_h1.py")
    assert tolerance.perturbations(path) == falcon_h1.PERTURBATIONS
    assert set(falcon_h1.PERTURBATIONS) == {
        "drop_ssm_branch", "drop_attention_branch", "drop_last_layer",
        "state_fp8", "no_conv", "weights_fp8"}


@pytest.mark.parametrize("perturb", falcon_h1.PERTURBATIONS)
def test_a_cruder_computation_moves_the_reference(perturb):
    """With the draws scaled to the published multipliers every branch
    moves the logits: each perturbation shows, the mixer's among them."""
    params = _params(TINY)
    tokens = [int(t) for t in np.random.RandomState(1).randint(
        0, TINY["vocab_size"], size=120)]
    clean = falcon_h1.forward(TINY, params, tokens, 0)
    crude = falcon_h1.forward(TINY, params, tokens, 0, perturb=perturb)
    diff = np.abs(np.asarray(clean["top"]) - np.asarray(crude["top"])).max()
    assert diff > 1e-2, diff


@pytest.mark.parametrize("change", [
    {"model_type": "llama"}, {"rope_scaling": {"rope_type": "linear"}},
    {"attn_layer_indices": [0, 2]}, {"mamba_norm_before_gate": True},
    {"mamba_proj_bias": True}, {"tie_word_embeddings": True},
    {"hidden_act": "gelu"}])
def test_the_reference_refuses_what_it_does_not_implement(change):
    config = dict(TINY, **change)
    with pytest.raises(ValueError):
        falcon_h1.forward(config, _params(TINY), [1, 2, 3], 0)
    with pytest.raises(ValueError):
        falcon_h1.forward(TINY, _params(TINY), [1, 2, 3], 0,
                          perturb="head_int8")


def test_the_configuration_is_the_published_one_but_for_its_depth():
    cfg = Manifest().config(CONFIG)
    entry = next(c for c in Manifest().data["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/" \
        "config.json"
    c = cfg["config"]
    assert c["num_hidden_layers"] == 6
    assert (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"]) == (5120, 21504, 261120, 20, 4, 128)
    assert (c["mamba_d_ssm"], c["mamba_n_heads"], c["mamba_d_head"],
            c["mamba_n_groups"], c["mamba_d_state"], c["mamba_d_conv"],
            c["mamba_chunk_size"]) == (4096, 32, 128, 2, 256, 4, 128)
    assert cfg["server"]["config_file"] == {"max_model_len": 2048,
                                            "max_num_seqs": 96}
    assert cfg["server"]["expect"]["prefix_cache"] == "off"
    # what the roofline's bytes are counted in, and what is served
    assert cfg["assumed"]["state_dtype"] == "bfloat16"
    cell = Manifest().cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "batch-wide", 1)
    mix = Manifest().traffic("batch-wide")
    assert mix["check"] == {"prompt_lens": [150, 500, 1100],
                            "decode_tokens": 24}
    assert (mix["loop"], mix["concurrency_per_slot"], mix["distinct"],
            mix["mix_seed"], mix["count"]) == ("closed", 2, 48, 505, 6144)


def test_the_published_keys_stand_at_the_top_of_the_file_too():
    """The driver compares the file's top level with the catalog's row;
    the harness serves the ``config`` group.  One model, written twice:
    the two may not drift."""
    cfg = load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
    served = dict(cfg["config"])
    # the class the published model_type names; the catalog leaves it out
    assert served.pop("architectures") == ["FalconH1ForCausalLM"]
    assert {k: cfg[k] for k in served} == served
    assert cfg["attention_in_multiplier"] == 1
    assert cfg["num_hidden_layers"] == 6 and \
        cfg["reduced"] == ["num_hidden_layers"]


def test_the_cell_reports_what_the_issue_lists():
    m = Manifest()
    got = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert {"kernel.ssm_decode_roofline", "cache.state_pool_bytes",
            "cache.state_recomputes", "kernel.decode_attn_roofline",
            "device.idle_share.active", "step.wall_ms.batch"} <= got
    # pinned to its one cell by a benchmark file
    assert "sched.first_token_deferred_pct" not in got
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == \
        {"out_tok_s", "setup_s"}
    for name in ("kernel.ssm_decode_roofline", "cache.state_pool_bytes",
                 "cache.state_recomputes"):
        entry = next(x for x in m.data["per_layer"] if x["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "out_tok_s"


def test_state_update_roofline_from_tokens_and_bytes():
    import rooflines_ssm
    from readers import trace_ssm_decode_roofline_pct as reader

    whole = Manifest().config(CONFIG)
    config = whole["config"]
    assert rooflines_ssm.ssm_dims(config) == (32, 128, 2, 256)
    assert rooflines_ssm.ssm_state_bytes_per_row(config, 2) == 2 * 2 ** 20
    row = rooflines_ssm.ssm_decode_update_bytes(config, 1, 2)
    assert row == 2 * 2 * 2 ** 20 + 4 * (2 * 4096 + 2 * 512 + 64)
    # two requests stream through the span; their first chunks are the
    # prefill's and a third request's chunks fall outside it
    reqs = [{"chunk_s": [1.9, 2.1, 2.2, 2.3]}, {"chunk_s": [2.5, 2.6, 3.5]},
            {"chunk_s": [0.1, 0.2]}, {"chunk_s": []}]
    assert reader.decoded_tokens(reqs, 2.0, 3.0) == 4
    ctx = {"trace": {"devices": 1, "window_s": 1.0,
                     "ops": {"jit_decode_multi/%ssm_state_update.3": 0.0004,
                             "jit_prefill_step/%fusion.1": 0.5}},
           "traced_s": [2.0, 3.0], "requests": reqs,
           "config": whole,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics", "kernel.ssm_decode_roofline.json"))["args"]
    got = reader.read(ctx, **pattern)
    assert got == pytest.approx(100.0 * (4 * 6 * row / 819e9) / 0.0004)
    assert 0 < got < 100
    # a program with no such kernel, a run with no trace, another
    # architecture: nothing to read, and no exception
    no_kernel = dict(ctx, trace=dict(ctx["trace"], ops={
        "jit_decode_multi/%attention.11": 0.002}))
    assert reader.read(no_kernel, **pattern) is None
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, traced_s=[]), **pattern) is None
    dense = dict(ctx, config={"config": {"num_hidden_layers": 32}})
    assert reader.read(dense, **pattern) is None
    # the bytes follow the type the file states; a file that states
    # none gives the reader nothing to count
    f32 = dict(ctx, config=dict(whole, assumed={"state_dtype": "float32"}))
    assert reader.read(f32, **pattern) == pytest.approx(
        got * rooflines_ssm.ssm_decode_update_bytes(config, 1, 4) / row)
    assert reader.read(dict(ctx, config={"config": config}),
                       **pattern) is None


@pytest.fixture
def manifest_with_the_cell(tmp_path):
    """The rehearsal's files with a tiny copy of the new configuration,
    its cell on the rehearsal's closed-loop mix, and the new metrics'
    entries under the rehearsal's cell name."""
    root = str(tmp_path / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    real = load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
    tiny = dict(real, config=TINY, deployment="CPU rehearsal only",
                tolerance={"logprob_abs": 0.002, "reason": "float32 on the "
                           "CPU against the float32 reference"})
    tiny["server"] = dict(real["server"], config_file={
        "max_model_len": 2048, "max_num_seqs": 8, "max_prefill_tokens": 64})
    with open(os.path.join(root, "kbench", "configs",
                           "tiny-falcon-h1.json"), "w") as f:
        json.dump(tiny, f)
    mix = load_json(os.path.join(root, "kbench", "traffic", "batch.json"))
    # three chunks of 64 for the longer check prompt: the state is
    # carried from chunk to chunk
    mix["check"] = {"prompt_lens": [70, 150], "decode_tokens": 8}
    with open(os.path.join(root, "kbench", "traffic", "batch-wide.json"),
              "w") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    data["configs"].append({
        "name": "tiny-falcon-h1", "source": real["source"],
        "file": "kbench/configs/tiny-falcon-h1.json",
        "reduced": ["num_hidden_layers"],
        "why": "CPU rehearsal of the mixer-beside-attention architecture"})
    data["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-falcon-h1",
         "traffic": "batch-wide", "chips": 1,
         "why": "rehearsal of the closed-loop mix on the state pool"})
    for m in data["end_to_end"]:
        if "workloads" in m and "tiny-batch" in m["workloads"]:
            m["workloads"].append(TINY_CELL)
    ours = {m["name"]: m for m in load_json(MANIFEST)["per_layer"]}
    data["per_layer"] += [dict(ours[name], workloads=[TINY_CELL])
                          for name in ("kernel.ssm_decode_roofline",
                                       "cache.state_pool_bytes",
                                       "cache.state_recomputes")]
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    return path


def test_the_rehearsal_of_the_new_cell(manifest_with_the_cell):
    assert validate(Manifest(manifest_with_the_cell)) == []
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest",
         manifest_with_the_cell, "--workload", TINY_CELL, "--seed",
         str(2 ** 31 + 79), "--seconds", "4", "--trace", "1",
         "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    out = _last_line(res)
    assert out["correct"] is True, res.stderr[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 0
    got = out["metrics"]
    # a CPU run takes no trace: the kernel's reader found nothing
    assert "kernel.ssm_decode_roofline" not in got
    arch_bytes = 8 * 3 * (128 * 32 + 3 * (128 + 2 * 2 * 32)) * 4
    assert got["cache.state_pool_bytes"]["value"] == arch_bytes
    assert got["cache.state_recomputes"]["value"] == 0
