"""The fifth configuration: LFM2-8B-A1B's plain reference against the
program's own CPU float32 path at a tiny size, the configuration's keys
against the catalog row, the roofline module on planted shapes and the
three readers on planted traces, the two benchmark tests an appended
entry breaks held here by name, and ONE CPU rehearsal of a tiny copy of
``lfm2-8b-a1b-d14-long`` in a temporary manifest, which every test of
the cell reads (the rehearsal manifest is a benchmark file and stays as
it is)."""

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
import lfm2_moe  # noqa: E402

CELL = "lfm2-8b-a1b-d14-long"
CONFIG = "lfm2-8b-a1b-d14"
TINY_CELL = "tiny-lfm2-long"            # no other test file runs this cell
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OLD_CELLS = ["phi4mini-batch", "falconh1-d6-batch", "mimo-v25-d7-ep16-long",
             "joyai-flash-ep16-long-out"]
NEW = ["kernel.moe_all_experts_roofline",
       "kernel.moe_prefill_experts_roofline",
       "kernel.decode_attn_d64_roofline", "moe.all_experts_touched_pct",
       "cache.conv_state_pool_bytes"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

# every key of the real configuration, the widths cut to a CPU's size:
# the two dense conv layers and one period and a half of the pattern,
# 4 query heads over 2 KV heads of 64 (two to a lane row, as on the
# chip), 8 experts of 32, 2 a token, all held
TINY = dict(load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
            ["config"],
            vocab_size=2048, hidden_size=256, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=7,
            num_attention_heads=4, num_key_value_heads=2,
            layer_types=["conv", "conv", "full_attention", "conv", "conv",
                         "conv", "full_attention"],
            num_experts=8, num_experts_per_tok=2,
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
    ref = lfm2_moe.forward(TINY, params, tokens, start)
    with jax.default_matmul_precision("highest"):
        logits = _model(TINY).forward_train(params, jnp.asarray([tokens]),
                                            remat=False)[0]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    want_t = np.array([lp[p, tokens[p + 1]] for p in range(start, 74)])
    want_top = np.asarray(lp.max(-1))[start:]
    assert np.abs(np.asarray(ref["target"])[:-1] - want_t).max() < 5e-5
    assert np.abs(np.asarray(ref["top"]) - want_top).max() < 5e-5
    assert np.isnan(ref["target"][-1])


def test_the_reference_is_plain_and_lists_its_perturbations():
    import tolerance

    path = os.path.join(KBENCH, "reference", "lfm2_moe.py")
    assert tolerance.perturbations(path) == lfm2_moe.PERTURBATIONS
    assert set(lfm2_moe.PERTURBATIONS) == {
        "weights_fp8", "conv_state_dropped", "conv_gate_dropped",
        "no_qk_norm", "rope_interleaved", "softmax_router", "no_expert_bias",
        "one_expert_dropped", "experts_dropped", "last_layer_dropped"}
    with open(path) as f:
        imports = [line.split()[1] for line in f
                   if line.startswith(("import ", "from "))]
    # no module of the program, no kernel library
    assert imports == ["jax", "jax.numpy"]


@pytest.mark.parametrize("perturb", lfm2_moe.PERTURBATIONS)
def test_a_cruder_computation_moves_the_reference(perturb):
    params = _params(TINY)
    tokens = _tokens(120, seed=1)
    clean = lfm2_moe.forward(TINY, params, tokens, 0)
    crude = lfm2_moe.forward(TINY, params, tokens, 0, perturb=perturb)
    diff = np.abs(np.asarray(clean["top"]) - np.asarray(crude["top"])).max()
    assert diff > 1e-3, diff


@pytest.mark.parametrize("change", [
    {"model_type": "mimo_v2"}, {"rope_scaling": {"rope_type": "yarn"}},
    {"conv_bias": True}, {"norm_topk_prob": False},
    {"use_expert_bias": False}, {"layer_types": ["conv"] * 3},
    {"layer_types": ["conv"] * 6 + ["sliding_attention"]}])
def test_the_reference_refuses_what_it_does_not_implement(change):
    config = dict(TINY, **change)
    with pytest.raises(ValueError):
        lfm2_moe.forward(config, _params(TINY), [1, 2, 3], 0)
    with pytest.raises(ValueError):
        lfm2_moe.forward(TINY, _params(TINY), [1, 2, 3], 0,
                         perturb="head_int8")


def test_the_dense_sibling_runs_through_the_same_reference():
    """``lfm2``: the same layers with a dense FFN everywhere."""
    dense = {k: v for k, v in TINY.items()
             if k not in ("num_experts", "num_experts_per_tok",
                          "num_dense_layers", "moe_intermediate_size",
                          "use_expert_bias", "norm_topk_prob",
                          "routed_scaling_factor")}
    dense.update(model_type="lfm2", architectures=["Lfm2ForCausalLM"])
    assert [n for n, _ in lfm2_moe.layer_names(dense)] == [
        "conv_dense", "conv_dense", "full_dense", "conv_dense", "conv_dense",
        "conv_dense", "full_dense"]
    params = _params(dense)
    tokens = _tokens(40, seed=2)
    ref = lfm2_moe.forward(dense, params, tokens, 0)
    with jax.default_matmul_precision("highest"):
        logits = _model(dense).forward_train(params, jnp.asarray([tokens]),
                                             remat=False)[0]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    assert np.abs(np.asarray(ref["top"]) - np.asarray(lp.max(-1))).max() \
        < 5e-5


def test_the_configuration_is_the_published_one_but_for_its_cut():
    cfg = Manifest().config(CONFIG)
    entry = next(c for c in Manifest().data["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "layer_types"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    c = cfg["config"]
    assert (c["num_hidden_layers"], c["num_dense_layers"]) == (14, 2)
    assert c["layer_types"] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 3
    assert c["layer_types"] == cfg["published"]["layer_types"][:14]
    assert cfg["published"]["num_hidden_layers"] == 24 \
        == len(cfg["published"]["layer_types"])
    # no width, no expert count and no vocabulary is cut
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["vocab_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["num_experts"], c["num_experts_per_tok"], c["conv_L_cache"]) \
        == (2048, 7168, 1792, 65536, 32, 8, 32, 4, 3)
    assert "expert_shards" not in c
    assert "two pipeline stages" in cfg["deployment"]
    for key in ("head_dim", "tie_word_embeddings", "short_conv", "qk_norm",
                "conv_taps_draw", "qk_norm_draw", "stored_lanes"):
        assert key in cfg["assumed"]
    assert cfg["server"]["config_file"] == {"max_model_len": 5120,
                                            "max_num_seqs": 32,
                                            "max-num-batched-tokens": 4096}
    assert cfg["server"]["args"] == {"enable-prefix-caching": True,
                                     "prefill-pack": 1}
    # what only a tree that has the conv mixer says: the parent maps
    # the unknown model_type to a dense decoder and says "pallas"
    assert cfg["server"]["expect"] == {"attention": "pallas+conv",
                                       "prefix_cache": "off",
                                       "hbm_sizing_source": "measured"}
    assert cfg["server"]["expect_cpu"]["attention"] == "jax+conv"
    assert cfg["reference"] == "kbench/reference/lfm2_moe.py"
    assert float(cfg["tolerance"]["logprob_abs"]) > 0 \
        and len(cfg["tolerance"]["reason"]) > 200
    cell = Manifest().cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "batch-long", 1)
    mix = Manifest().traffic("batch-long")
    assert mix["check"] == {"prompt_lens": [150, 1100, 4500],
                            "decode_tokens": 24}
    assert (mix["loop"], mix["concurrency_per_slot"], mix["distinct"],
            mix["mix_seed"]) == ("closed", 2, 48, 606)


def test_the_top_level_keys_are_the_catalog_rows_but_for_reduced():
    """The driver compares the file's top level with the catalog's row;
    the harness serves the ``config`` group.  One model, written twice:
    the two may not drift, and only the reduced keys may differ from
    the row."""
    cfg = load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
    served = dict(cfg["config"])
    assert served.pop("architectures") == ["Lfm2MoeForCausalLM"]
    assert {k: cfg[k] for k in served} == served
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert row["source_url"] == cfg["source"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def test_the_cell_reports_what_the_issue_lists():
    m = Manifest()
    assert validate(m) == []
    names = [x["name"] for x in m.data["per_layer"]]
    # appended behind PR 42's last entry, in ISSUE 44's order (whatever
    # later PRs append stands behind them)
    at = names.index("cache.latent_pool_used_pct") + 1
    assert names[at:at + 5] == NEW
    table = {
        "kernel.moe_all_experts_roofline": (
            "%", "higher", "device_trace", "Kernels (engine/ops/)",
            "trace_moe_all_experts_roofline_pct"),
        "kernel.moe_prefill_experts_roofline": (
            "%", "higher", "device_trace", "Kernels (engine/ops/)",
            "trace_moe_prefill_experts_roofline_pct"),
        "kernel.decode_attn_d64_roofline": (
            "%", "higher", "device_trace", "Kernels (engine/ops/)",
            "trace_decode_attn_d64_roofline_pct"),
        "moe.all_experts_touched_pct": (
            "%", "higher", "program_counter",
            "Step programs (engine/model.py)", "counter_ratio_pct"),
        "cache.conv_state_pool_bytes": (
            "bytes", "lower", "program_counter",
            "Cache manager (engine/engine.py, native/)", "gauge_mean")}
    for name, (unit, better, source, layer, reader) in table.items():
        entry = next(x for x in m.data["per_layer"] if x["name"] == name)
        spec = m.layer_metric(name)
        assert entry["workloads"][0] == CELL
        assert entry["moves"] == spec["moves"] == "out_tok_s"
        assert entry["layer"] == spec["layer"] == layer
        assert (entry["unit"], entry["better"], entry["source"]) == (
            unit, better, source)
        assert spec["unit"] == unit and spec["reader"] == reader
        assert os.path.exists(os.path.join(KBENCH, "readers",
                                           reader + ".py"))
    assert m.layer_metric("kernel.moe_all_experts_roofline")["args"] == {
        "pattern": "^jit_decode[^/]*/%gmm"}
    assert m.layer_metric("kernel.moe_prefill_experts_roofline")["args"] == {
        "pattern": "^jit_prefill[^/]*/%gmm"}
    assert m.layer_metric("kernel.decode_attn_d64_roofline")["args"] == {
        "pattern": "^jit_decode[^/]*/%attention"}
    assert m.layer_metric("moe.all_experts_touched_pct")["args"] == {
        "part": "kaito:engine_moe_experts_touched_total",
        "whole": "kaito:engine_moe_expert_calls_total"}
    assert m.layer_metric("cache.conv_state_pool_bytes")["args"] == {
        "name": "kaito:engine_conv_state_pool_bytes"}
    # every metric all four older cells report
    got = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    shared = set.intersection(*({x["name"] for x in
                                 m.metrics_for(c, "per_layer")}
                                for c in OLD_CELLS))
    assert len(shared) == 30 and shared <= got
    # held pairs read 100 by construction here and are not reported; the
    # share-held readers count n_routed_experts and stay MiMo's
    assert not got & {"moe.held_pairs_pct", "kernel.moe_experts_roofline",
                      "moe.experts_touched_pct", "sched.prefill_multi_pct"}
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == \
        {"out_tok_s", "setup_s"}
    out = next(x for x in m.data["end_to_end"] if x["name"] == "out_tok_s")
    assert out["workloads"][:5] == OLD_CELLS + [CELL]
    # five configurations, five cells, none on four chips
    assert [w["chips"] for w in m.data["workloads"]][:5] == [1] * 5


def test_pr_42s_three_entries_stand_where_they_stood():
    """What test_kbench_joyai_llm_flash.py::
    test_the_cell_reports_what_the_issue_lists holds, every assertion of
    it, with the three found by name (tests/conftest.py marks that test
    as expected to fail, for the tail's length alone)."""
    m = Manifest()
    assert validate(m) == []
    joyai = "joyai-flash-ep16-long-out"
    got = {x["name"] for x in m.metrics_for(joyai, "per_layer")}
    new = ["kernel.mla_decode_attn_roofline",
           "kernel.mla_prefill_attn_roofline", "cache.latent_pool_used_pct"]
    names = [x["name"] for x in m.data["per_layer"]]
    at = names.index(new[0])
    assert names[at:at + 3] == new
    # behind PR 40's eleven, and this PR's five behind them as a prefix
    assert names[at - 1] == "device.idle_in.resolve"
    assert names[at + 3:at + 8] == NEW
    for name in new:
        entry = next(x for x in m.data["per_layer"] if x["name"] == name)
        spec = m.layer_metric(name)
        assert entry["workloads"] == [joyai]
        assert entry["moves"] == spec["moves"] == "out_tok_s"
        assert entry["layer"] == spec["layer"]
        assert (entry["unit"], entry["better"]) == ("%", "higher")
        assert os.path.exists(os.path.join(KBENCH, "readers",
                                           spec["reader"] + ".py"))
    shared = set.intersection(*({x["name"] for x in
                                 m.metrics_for(c, "per_layer")}
                                for c in OLD_CELLS[:3]))
    assert shared - got == {"sched.prefill_multi_pct"}
    assert not got & {"kernel.moe_experts_roofline",
                      "moe.experts_touched_pct", "moe.held_pairs_pct"}
    assert {x["name"] for x in m.metrics_for(joyai, "end_to_end")} == \
        {"out_tok_s", "setup_s"}
    spec = m.layer_metric("cache.latent_pool_used_pct")
    assert spec["reader"] == "gauge_mean" and spec["args"] == {
        "name": "kaito:kv_cache_usage_perc", "scale": 100}


def test_pr_40s_eleven_entries_stand_where_they_stood():
    """What test_kbench_joyai_llm_flash.py::
    test_pr_40s_eleven_entries_stand_where_they_stood holds, every
    assertion of it, with each of the eleven's cells compared as a
    prefix of four (tests/conftest.py marks that test as expected to
    fail, for the lists' lengths alone)."""
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
        assert entry["workloads"][:4] == OLD_CELLS
        assert entry["workloads"][4:5] == [CELL]
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


def test_lfm2_rooflines_on_planted_shapes():
    import rooflines_lfm2 as rl
    import rooflines_moe

    config = Manifest().config(CONFIG)["config"]
    assert rl.is_lfm2_moe(config)
    assert not rl.is_lfm2_moe({"num_hidden_layers": 32})
    assert not rl.is_lfm2_moe(Manifest().config("mimo-v2.5-d7-ep16")
                              ["config"])
    assert (rl.attention_layers(config), rl.expert_layers(config)) == (3, 12)
    # 2,048 B a token and attention layer, whatever is stored
    assert rl.decode_attention_bytes(config, [1000, 50]) == 3 * 2048 * 1050
    assert rl.decode_attention_bytes(config, []) == 0
    assert rl.moe_prefill_ops(config, 2560) == \
        2560 * 4 * 12 * 6 * 2048 * 1792
    one = 3 * 2048 * 1792 * 2
    assert rooflines_moe.expert_matrix_bytes(config) == one
    assert rl.moe_decode_bytes(config, 10, 0) == 10 * one
    assert rl.moe_decode_bytes(config, 10, 7) == \
        rooflines_moe.moe_decode_bytes(config, 10, 7) > 10 * one
    # a step that touches every expert of every layer: 4,228M
    # parameters, 8.5 GB
    assert 8.45e9 < rl.moe_decode_bytes(config, 32 * 12, 128 * 12) < 8.55e9


def _ctx(**kw):
    whole = Manifest().config(CONFIG)
    ctx = {"trace": {"devices": 1, "window_s": 1.0, "ops": {},
                     "op_counts": {}},
           "traced_s": [2.0, 3.0], "requests": [], "config": whole,
           "peaks": PEAKS, "before": {}, "after": {}}
    ctx.update(kw)
    return ctx


def test_all_experts_roofline_reader_on_a_planted_trace():
    import rooflines_lfm2 as rl
    from readers import trace_moe_all_experts_roofline_pct as reader

    config = Manifest().config(CONFIG)["config"]
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics",
        "kernel.moe_all_experts_roofline.json"))["args"]
    # a window of 1,000 steps of 12 expert layers, a tenth of them in
    # the span: 100 x 12 x 3 calls of the kernel; a prefill program's
    # calls are not read
    calls = 1000 * 12 * 32.0
    ctx = _ctx(
        trace={"devices": 1, "window_s": 1.0,
               "ops": {"jit_decode_multi/%gmm.3": 0.9,
                       "jit_decode_multi/%gmm.7": 0.3,
                       "jit_prefill_step/%gmm.3": 4.0},
               "op_counts": {"jit_decode_multi/%gmm.3": 2400.0,
                             "jit_decode_multi/%gmm.7": 1200.0,
                             "jit_prefill_step/%gmm.3": 99.0}},
        before={reader.CALLS: 5.0, reader.TOUCHED: 1.0, reader.PAIRS: 2.0},
        after={reader.CALLS: 5.0 + calls, reader.TOUCHED: 1.0 + 0.98 * calls,
               reader.PAIRS: 2.0 + 1000 * 12 * 128.0})
    got = reader.read(ctx, **pattern)
    need = rl.moe_decode_bytes(config, 0.98 * calls * 0.1,
                               1000 * 12 * 128.0 * 0.1)
    assert got == pytest.approx(100.0 * (need / 819e9) / 1.2)
    assert 0 < got < 100
    # the parent's program has no such counters, a CPU run no trace,
    # another configuration no num_experts: nothing to read
    assert reader.read(dict(ctx, after={}), **pattern) is None
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, trace=dict(ctx["trace"], ops={},
                                            op_counts={})), **pattern) is None
    mimo = dict(ctx, config=Manifest().config("mimo-v2.5-d7-ep16"))
    assert reader.read(mimo, **pattern) is None


def test_prefill_experts_roofline_reader_on_a_planted_trace():
    import rooflines_lfm2 as rl
    from readers import trace_moe_prefill_experts_roofline_pct as reader

    config = Manifest().config(CONFIG)["config"]
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics",
        "kernel.moe_prefill_experts_roofline.json"))["args"]
    reqs = [{"prompt_tokens": 4096, "chunk_s": [2.1, 4.0]},
            {"prompt_tokens": 1024, "chunk_s": [2.9]},
            {"prompt_tokens": 3000, "chunk_s": [1.9, 2.5]},   # before
            {"prompt_tokens": 3000, "chunk_s": []}]           # no token
    ctx = _ctx(requests=reqs,
               trace={"devices": 1, "window_s": 1.0,
                      "ops": {"jit_prefill_step/%gmm.7": 0.08,
                              "jit_prefill_step/%gmm.9": 0.04,
                              "jit_decode_multi/%gmm.2": 0.7},
                      "op_counts": {}})
    got = reader.read(ctx, **pattern)
    ops = rl.moe_prefill_ops(config, 4096 + 1024)
    assert got == pytest.approx(100.0 * (ops / 197e12) / 0.12)
    assert 0 < got < 100
    assert reader.read(dict(ctx, requests=reqs[2:]), **pattern) is None
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, traced_s=[]), **pattern) is None
    assert reader.read(dict(ctx, trace=dict(ctx["trace"], ops={})),
                       **pattern) is None
    dense = dict(ctx, config={"config": {"num_hidden_layers": 32}})
    assert reader.read(dense, **pattern) is None


def test_decode_attn_d64_roofline_reader_on_a_planted_trace():
    import rooflines_lfm2 as rl
    from readers import trace_decode_attn_d64_roofline_pct as reader

    config = Manifest().config(CONFIG)["config"]
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics",
        "kernel.decode_attn_d64_roofline.json"))["args"]
    reqs = [{"prompt_tokens": 2999, "chunk_s": [1.0, 4.0]},
            {"prompt_tokens": 1499, "chunk_s": [1.5, 3.5]},
            {"prompt_tokens": 10, "chunk_s": [0.1, 0.2]}]
    step = rl.decode_attention_bytes(config, [3000, 1500])
    assert reader.mean_step_bytes(config, reqs, 2.0, 3.0) == \
        pytest.approx(step)
    # 100 steps of 3 attention layers: 300 calls in the span
    ctx = _ctx(requests=reqs,
               trace={"devices": 1, "window_s": 1.0,
                      "ops": {"jit_decode_multi/%attention.2": 0.004,
                              "jit_decode_multi/%attention.5": 0.002,
                              "jit_prefill_step/%attention.7": 0.5},
                      "op_counts": {"jit_decode_multi/%attention.2": 200.0,
                                    "jit_decode_multi/%attention.5": 100.0,
                                    "jit_prefill_step/%attention.7": 9.0}})
    got = reader.read(ctx, **pattern)
    assert got == pytest.approx(100.0 * (100 * step / 819e9) / 0.006)
    assert 0 < got < 100
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, traced_s=[]), **pattern) is None
    assert reader.read(dict(ctx, trace=dict(ctx["trace"], ops={},
                                            op_counts={})), **pattern) is None
    dense = dict(ctx, config={"config": {"num_hidden_layers": 32}})
    assert reader.read(dense, **pattern) is None


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """ONE run of the tiny copy of the cell, traced flag on (a CPU takes
    no trace, the counters' readers still read), for every test below."""
    root = str(tmp_path_factory.mktemp("lfm2") / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    real = load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
    tiny = dict(real, config=TINY, deployment="CPU rehearsal only",
                tolerance={"logprob_abs": 0.002, "reason": "float32 on the "
                           "CPU against the float32 reference"})
    tiny["server"] = dict(
        real["server"],
        config_file={"max_model_len": 512, "max_num_seqs": 8,
                     "page_size": 16, "max-num-batched-tokens": 128})
    with open(os.path.join(root, "kbench", "configs", "tiny-lfm2.json"),
              "w") as f:
        json.dump(tiny, f)
    mix = load_json(os.path.join(root, "kbench", "traffic", "batch.json"))
    # the longest check prompt is two chunks of the 128-token budget:
    # the second starts from the row of conv state the first left and
    # attends the lane-packed pages
    mix["check"] = {"prompt_lens": [20, 70, 150], "decode_tokens": 24}
    with open(os.path.join(root, "kbench", "traffic", "batch-long.json"),
              "w") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    data["configs"].append({
        "name": "tiny-lfm2", "source": real["source"],
        "file": "kbench/configs/tiny-lfm2.json", "reduced": real["reduced"],
        "why": "CPU rehearsal of conv-state rows beside attention pages"})
    data["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-lfm2", "traffic": "batch-long",
         "chips": 1, "why": "rehearsal of the closed-loop mix on a conv "
         "and attention hybrid"})
    for m in data["end_to_end"]:
        if "workloads" in m and "tiny-batch" in m["workloads"]:
            m["workloads"].append(TINY_CELL)
    ours = {m["name"]: m for m in load_json(MANIFEST)["per_layer"]}
    data["per_layer"] += [dict(ours[name], workloads=[TINY_CELL])
                          for name in NEW + ["cache.preemptions"]]
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
    for name in NEW[:3]:
        assert name not in got
    # 5 conv layers x 8 slots x 2 inputs x 256 channels, float32
    assert got["cache.conv_state_pool_bytes"]["value"] == 5 * 8 * 2 * 256 * 4
    # 8 experts, 2 a token: a step of a few rows leaves some untouched
    assert 0.0 < got["moe.all_experts_touched_pct"]["value"] <= 100.0
    assert got["cache.preemptions"]["value"] == 0
