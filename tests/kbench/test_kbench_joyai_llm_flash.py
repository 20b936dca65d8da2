"""The fourth configuration: JoyAI-LLM-Flash's plain reference against
the program's own CPU float32 path at a tiny size, the configuration's
keys against the catalog row, the roofline module on planted shapes and
the two readers on planted traces, the one benchmark test an appended
entry breaks held here by name, and ONE CPU rehearsal of a tiny copy of
``joyai-flash-ep16-long-out`` in a temporary manifest, which every test
of the cell reads (the rehearsal manifest is a benchmark file and stays
as it is)."""

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
import joyai_llm_flash  # noqa: E402

CELL = "joyai-flash-ep16-long-out"
CONFIG = "joyai-llm-flash-ep16"
TINY_CELL = "tiny-joyai-long-out"       # no other test file runs this cell
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OLD_CELLS = ["phi4mini-batch", "falconh1-d6-batch", "mimo-v25-d7-ep16-long"]

# every key of the real configuration, the widths cut to a CPU's size:
# four layers (one dense), a latent of 128 + 16 (stored at 256 lanes on
# a chip), 16 experts of which a quarter is held, one shared expert
TINY = dict(load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
            ["config"],
            vocab_size=2048, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, kv_lora_rank=128,
            q_lora_rank=48, qk_head_dim=40, qk_nope_head_dim=24,
            qk_rope_head_dim=16, v_head_dim=24, moe_intermediate_size=32,
            n_routed_experts=4, expert_shards=4, num_experts_per_tok=4,
            max_position_embeddings=2048)


def _model(config):
    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.models.autogen import arch_from_hf_config

    m = TransformerLM(arch_from_hf_config(config), dtype=jnp.float32)
    m.moe_impl = "ragged"
    return m


def _params(config, seed=3):
    return _model(config).init_params(jax.random.PRNGKey(seed))


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], size=n)]


@pytest.mark.parametrize("start", [0, 60])
def test_reference_agrees_with_the_program_on_the_cpu(start):
    params = _params(TINY)
    tokens = _tokens(75)
    ref = joyai_llm_flash.forward(TINY, params, tokens, start)
    with jax.default_matmul_precision("highest"):
        logits = _model(TINY).forward_train(params, jnp.asarray([tokens]),
                                            remat=False)[0]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    want_t = np.array([lp[p, tokens[p + 1]] for p in range(start, 74)])
    want_top = np.asarray(lp.max(-1))[start:]
    assert np.abs(np.asarray(ref["target"])[:-1] - want_t).max() < 5e-5
    assert np.abs(np.asarray(ref["top"]) - want_top).max() < 5e-5
    assert np.isnan(ref["target"][-1])


def test_the_reference_lists_the_perturbations_it_accepts():
    import tolerance

    path = os.path.join(KBENCH, "reference", "joyai_llm_flash.py")
    assert tolerance.perturbations(path) == joyai_llm_flash.PERTURBATIONS
    assert set(joyai_llm_flash.PERTURBATIONS) == {
        "rope_split_half", "k_rope_unrotated", "scale_by_nope_dim",
        "no_kv_norm", "softmax_router", "no_correction_bias",
        "no_routed_scale", "shared_expert_dropped", "experts_dropped",
        "one_expert_dropped", "last_layer_dropped", "weights_fp8"}


@pytest.mark.parametrize("perturb", joyai_llm_flash.PERTURBATIONS)
def test_a_cruder_computation_moves_the_reference(perturb):
    params = _params(TINY)
    tokens = _tokens(120, seed=1)
    clean = joyai_llm_flash.forward(TINY, params, tokens, 0)
    crude = joyai_llm_flash.forward(TINY, params, tokens, 0, perturb=perturb)
    diff = np.abs(np.asarray(clean["top"]) - np.asarray(crude["top"])).max()
    assert diff > 1e-3, diff


@pytest.mark.parametrize("change", [
    {"model_type": "deepseek_v3"}, {"rope_scaling": {"rope_type": "yarn"}},
    {"n_group": 4}, {"scoring_func": "softmax"}, {"topk_method": "greedy"},
    {"attention_bias": True}, {"tie_word_embeddings": True},
    {"hidden_act": "gelu"}, {"kv_lora_rank": None}, {"moe_layer_freq": 2}])
def test_the_reference_refuses_what_it_does_not_implement(change):
    config = dict(TINY, **change)
    with pytest.raises(ValueError):
        joyai_llm_flash.forward(config, _params(TINY), [1, 2, 3], 0)
    with pytest.raises(ValueError):
        joyai_llm_flash.forward(TINY, _params(TINY), [1, 2, 3], 0,
                                perturb="head_int8")


def test_the_configuration_is_the_published_one_but_for_its_cut():
    cfg = Manifest().config(CONFIG)
    entry = next(c for c in Manifest().data["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["n_routed_experts"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/" \
        "config.json"
    c = cfg["config"]
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["moe_layer_freq"]) == (40, 1, 1)
    assert (c["n_routed_experts"], c["expert_shards"], c["expert_shard"],
            c["num_experts_per_tok"], c["n_shared_experts"]) == (16, 16, 0,
                                                                 8, 1)
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["vocab_size"], c["kv_lora_rank"],
            c["q_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["num_attention_heads"]) == (
        2048, 7168, 768, 129280, 512, 1536, 128, 64, 128, 32)
    assert cfg["published"] == {"n_routed_experts": 256}
    assert cfg["server"]["config_file"] == {"max_model_len": 5120,
                                            "max_num_seqs": 24,
                                            "max-num-batched-tokens": 4096}
    assert cfg["server"]["args"] == {"enable-prefix-caching": True,
                                     "prefill-pack": 1}
    assert cfg["server"]["expect"] == {"attention": "pallas",
                                       "prefix_cache": "off",
                                       "hbm_sizing_source": "measured"}
    assert float(cfg["tolerance"]["logprob_abs"]) > 0 \
        and len(cfg["tolerance"]["reason"]) > 200
    cell = Manifest().cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "batch-long-out", 1)
    mix = Manifest().traffic("batch-long-out")
    assert mix["check"] == {"prompt_lens": [150, 1100, 4500],
                            "decode_tokens": 24}
    assert (mix["loop"], mix["concurrency_per_slot"], mix["distinct"],
            mix["mix_seed"], mix["count"]) == ("closed", 2, 24, 707, 1536)
    assert mix["prompt"]["unique"] == {"dist": "uniform", "min": 1024,
                                       "max": 4096}
    assert mix["output"] == {"dist": "uniform", "min": 256, "max": 768}
    # what this model's export fits into the profiler call's limit with
    # (test_kbench_trace_window.py holds every mix's budget)
    assert mix["trace_seconds"] == 2.5 and mix["drain_timeout_s"] == 180


def test_the_top_level_keys_are_the_catalog_rows_but_for_reduced():
    """The driver compares the file's top level with the catalog's row;
    the harness serves the ``config`` group.  One model, written twice:
    the two may not drift, and only the reduced key may differ from the
    row."""
    cfg = load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
    served = dict(cfg["config"])
    assert served.pop("architectures") == ["JoyAILLMFlashForCausalLM"]
    assert {k: cfg[k] for k in served} == served
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "JoyAI-LLM-Flash")
    assert row["source_url"] == cfg["source"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def test_the_cell_reports_what_the_issue_lists():
    m = Manifest()
    assert validate(m) == []
    got = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    new = ["kernel.mla_decode_attn_roofline",
           "kernel.mla_prefill_attn_roofline", "cache.latent_pool_used_pct"]
    # appended behind the last entry, in ISSUE 42's order
    assert [x["name"] for x in m.data["per_layer"]][-3:] == new
    for name in new:
        entry = next(x for x in m.data["per_layer"] if x["name"] == name)
        spec = m.layer_metric(name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == spec["moves"] == "out_tok_s"
        assert entry["layer"] == spec["layer"]
        assert (entry["unit"], entry["better"]) == ("%", "higher")
        assert os.path.exists(os.path.join(KBENCH, "readers",
                                           spec["reader"] + ".py"))
    # every metric all three older cells report, but PR 34's (whose
    # benchmark test holds its list to three)
    shared = set.intersection(*({x["name"] for x in
                                 m.metrics_for(c, "per_layer")}
                                for c in OLD_CELLS))
    assert shared - got == {"sched.prefill_multi_pct"}
    # left to a benchmark PR (PERF.md section 7): the expert layer's
    # three, whose benchmark tests hold their lists to MiMo's cell
    assert not got & {"kernel.moe_experts_roofline",
                      "moe.experts_touched_pct", "moe.held_pairs_pct"}
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == \
        {"out_tok_s", "setup_s"}
    spec = m.layer_metric("cache.latent_pool_used_pct")
    assert spec["reader"] == "gauge_mean" and spec["args"] == {
        "name": "kaito:kv_cache_usage_perc", "scale": 100}


def test_pr_40s_eleven_entries_stand_where_they_stood():
    """What test_kbench_part_metrics.py::
    test_the_eleven_entries_are_appended_for_every_cell holds, every
    assertion of it, with the eleven found by name, the tail behind
    them compared as a prefix and each one's cells as a prefix of three
    (tests/conftest.py marks that test as expected to fail, for the
    tail's and the lists' lengths alone)."""
    from test_kbench_part_metrics import COUNTERS, TRACED

    m = Manifest()
    assert validate(m) == []
    names = [x["name"] for x in m.data["per_layer"]]
    at = names.index(COUNTERS[0])
    assert names[at:at + 11] == list(COUNTERS) + list(TRACED)
    assert names[at + 11:at + 14] == [
        "kernel.mla_decode_attn_roofline",
        "kernel.mla_prefill_attn_roofline", "cache.latent_pool_used_pct"]
    layers = {"sched": "Scheduler (engine/engine.py)",
              "step": "Step programs (engine/model.py)",
              "http": "HTTP front (engine/server.py)",
              "device": "Device (TPU v5e)"}
    for entry in m.data["per_layer"][at:at + 11]:
        spec = m.layer_metric(entry["name"])
        assert entry["workloads"][:3] == OLD_CELLS
        assert entry["workloads"][3:] == [CELL]
        assert entry["better"] == "lower"
        assert entry["moves"] == spec["moves"] == "out_tok_s"
        assert entry["layer"] == spec["layer"] \
            == layers[entry["name"].split(".")[0]]
        assert entry["unit"] == spec["unit"]
        assert entry["source"] == ("program_span" if entry["name"] in TRACED
                                   else "program_counter")
        assert os.path.exists(os.path.join(
            KBENCH, "readers", spec["reader"] + ".py"))
    for cell in OLD_CELLS + [CELL]:
        assert set(COUNTERS + TRACED) <= {
            x["name"] for x in m.metrics_for(cell, "per_layer")}


def test_latent_rooflines_on_planted_shapes():
    import rooflines_mla as rm

    config = Manifest().config(CONFIG)["config"]
    assert rm.is_latent(config) and rm.layers(config) == 40
    assert not rm.is_latent({"num_hidden_layers": 32})
    assert rm.latent_bytes_per_token_per_layer(config) == 1152.0
    assert rm.decode_bytes_per_step(config, [1000, 50]) == 40 * 1152 * 1050
    assert rm.decode_ops_per_step(config, [1000, 50]) == \
        40 * 32 * 2 * (576 + 512) * 1050
    # 60 operations a byte: under the v5e's ridge of 240, memory decides
    ratio = rm.decode_ops_per_step(config, [3000]) \
        / rm.decode_bytes_per_step(config, [3000])
    assert 60 < ratio < 61 and ratio < 197e12 / 819e9
    assert rm.prefill_ops(config, 4096) == \
        40 * 32 * (4096 * 4097 / 2) * 2 * 320
    assert 6.8e12 < rm.prefill_ops(config, 4096) < 6.9e12


def test_latent_decode_roofline_reader_on_a_planted_trace():
    import rooflines_mla as rm
    from readers import trace_mla_decode_attn_roofline_pct as reader

    whole = Manifest().config(CONFIG)
    config = whole["config"]
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    reqs = [{"prompt_tokens": 2999, "chunk_s": [1.0, 4.0]},
            {"prompt_tokens": 1499, "chunk_s": [1.5, 3.5]},
            {"prompt_tokens": 10, "chunk_s": [0.1, 0.2]}]
    step_s = rm.decode_bytes_per_step(config, [3000, 1500]) / 819e9
    assert reader.mean_step_need_s(config, reqs, 2.0, 3.0, peaks) == \
        pytest.approx(step_s)
    # 100 steps of 40 layers: 4,000 calls of the kernel in the span;
    # MiMo's kernel name and a prefill program's are not read
    ctx = {"trace": {"devices": 1, "window_s": 1.0,
                     "ops": {"jit_decode_multi/%mla_attention.2": 0.7,
                             "jit_decode_multi/%mla_attention.5": 0.3,
                             "jit_decode_multi/%attention.4": 9.0,
                             "jit_prefill_step/%attention.7": 0.5},
                     "op_counts": {"jit_decode_multi/%mla_attention.2": 3000.0,
                                   "jit_decode_multi/%mla_attention.5": 1000.0,
                                   "jit_decode_multi/%attention.4": 77.0}},
           "traced_s": [2.0, 3.0], "requests": reqs, "config": whole,
           "peaks": peaks}
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics",
        "kernel.mla_decode_attn_roofline.json"))["args"]
    got = reader.read(ctx, **pattern)
    assert got == pytest.approx(100.0 * 100 * step_s / 1.0)
    assert 0 < got < 100
    # where the operations take longer than the bytes they decide
    slow = dict(ctx, peaks=dict(peaks, bf16_flops_per_s=197e12 / 8))
    assert reader.read(slow, **pattern) == pytest.approx(
        got * 8 * 819e9 * rm.decode_ops_per_step(config, [1])
        / (197e12 * rm.decode_bytes_per_step(config, [1])))
    # the parent's program has no such kernel, a CPU run no trace, and
    # another configuration no latent: nothing to read, no exception
    none = dict(ctx, trace=dict(ctx["trace"], ops={
        "jit_decode_multi/%attention.4": 9.0}, op_counts={}))
    assert reader.read(none, **pattern) is None
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, traced_s=[]), **pattern) is None
    dense = dict(ctx, config={"config": {"num_hidden_layers": 32}})
    assert reader.read(dense, **pattern) is None


def test_latent_prefill_roofline_reader_on_a_planted_trace():
    import rooflines_mla as rm
    from readers import trace_mla_prefill_attn_roofline_pct as reader

    whole = Manifest().config(CONFIG)
    config = whole["config"]
    reqs = [{"prompt_tokens": 4096, "chunk_s": [2.1, 4.0]},
            {"prompt_tokens": 1024, "chunk_s": [2.9]},
            {"prompt_tokens": 3000, "chunk_s": [1.9, 2.5]},   # before
            {"prompt_tokens": 3000, "chunk_s": []}]           # no token
    ctx = {"trace": {"devices": 1, "window_s": 1.0,
                     "ops": {"jit_prefill_step/%attention.7": 0.06,
                             "jit_prefill_step/%attention.9": 0.04,
                             "jit_decode_multi/%mla_attention.2": 0.7},
                     "op_counts": {}},
           "traced_s": [2.0, 3.0], "requests": reqs, "config": whole,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics",
        "kernel.mla_prefill_attn_roofline.json"))["args"]
    got = reader.read(ctx, **pattern)
    ops = rm.prefill_ops(config, 4096) + rm.prefill_ops(config, 1024)
    assert got == pytest.approx(100.0 * (ops / 197e12) / 0.1)
    assert 0 < got < 100
    assert reader.read(dict(ctx, requests=reqs[2:]), **pattern) is None
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, trace=dict(ctx["trace"], ops={})),
                       **pattern) is None
    dense = dict(ctx, config={"config": {"num_hidden_layers": 32}})
    assert reader.read(dense, **pattern) is None


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """ONE run of the tiny copy of the cell, traced flag on (a CPU takes
    no trace, the counters' readers still read), for every test below."""
    root = str(tmp_path_factory.mktemp("joyai") / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    real = load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
    tiny = dict(real, config=TINY, deployment="CPU rehearsal only",
                tolerance={"logprob_abs": 0.002, "reason": "float32 on the "
                           "CPU against the float32 reference"})
    tiny["server"] = dict(
        real["server"],
        config_file={"max_model_len": 512, "max_num_seqs": 8,
                     "page_size": 16, "max-num-batched-tokens": 128})
    with open(os.path.join(root, "kbench", "configs", "tiny-joyai.json"),
              "w") as f:
        json.dump(tiny, f)
    mix = load_json(os.path.join(root, "kbench", "traffic", "batch.json"))
    # the longest check prompt is two chunks of the 128-token budget:
    # the second attends the paged latent cache
    mix["check"] = {"prompt_lens": [20, 70, 150], "decode_tokens": 24}
    mix["output"] = {"dist": "uniform", "min": 24, "max": 48}
    with open(os.path.join(root, "kbench", "traffic", "batch-long-out.json"),
              "w") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    data["configs"].append({
        "name": "tiny-joyai", "source": real["source"],
        "file": "kbench/configs/tiny-joyai.json", "reduced": real["reduced"],
        "why": "CPU rehearsal of the latent cache and the held experts"})
    data["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-joyai",
         "traffic": "batch-long-out", "chips": 1,
         "why": "rehearsal of the closed-loop mix on latent pages"})
    for m in data["end_to_end"]:
        if "workloads" in m and "tiny-batch" in m["workloads"]:
            m["workloads"].append(TINY_CELL)
    ours = {m["name"]: m for m in load_json(MANIFEST)["per_layer"]}
    data["per_layer"] += [dict(ours[name], workloads=[TINY_CELL])
                          for name in ("kernel.mla_decode_attn_roofline",
                                       "kernel.mla_prefill_attn_roofline",
                                       "cache.latent_pool_used_pct",
                                       "moe.experts_touched_pct",
                                       "moe.held_pairs_pct")]
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    assert validate(Manifest(path)) == []
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest", path,
         "--workload", TINY_CELL, "--seed", str(2 ** 31 + 97), "--seconds",
         "4", "--trace", "1", "--expect-platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    return res, _last_line(res)


def test_the_rehearsal_of_the_new_cell_is_correct(rehearsal):
    res, out = rehearsal
    assert out["correct"] is True, res.stderr[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "INCORRECT" not in res.stderr


def test_the_rehearsal_reports_the_counters_and_no_device_metric(rehearsal):
    _, out = rehearsal
    got = out["metrics"]
    # a CPU run takes no trace: the kernels' readers found nothing
    assert "kernel.mla_decode_attn_roofline" not in got
    assert "kernel.mla_prefill_attn_roofline" not in got
    # the latent pool's pages in use, polled
    assert 0.0 < got["cache.latent_pool_used_pct"]["value"] <= 100.0
    # a quarter of the experts is held: a quarter of the pairs, near
    # enough, lands here (the expert layer's counters count for this
    # model as they do for MiMo)
    assert 10.0 < got["moe.held_pairs_pct"]["value"] < 45.0
    assert 0.0 < got["moe.experts_touched_pct"]["value"] <= 100.0
