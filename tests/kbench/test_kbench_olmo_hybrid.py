"""The sixth configuration: Olmo-Hybrid-7B's plain reference against
the program's own CPU float32 path at a tiny size, the configuration's
keys against the catalog row, the roofline module on planted shapes and
the two readers on planted traces, the benchmark tests an appended
entry breaks held here by name, and ONE CPU rehearsal of a tiny copy of
``olmo-hybrid-7b-d8-long`` in a temporary manifest, which every test of
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
import olmo_hybrid  # noqa: E402

CELL = "olmo-hybrid-7b-d8-long"
CONFIG = "olmo-hybrid-7b-d8"
TINY_CELL = "tiny-olmo-hybrid-long"     # no other test file runs this cell
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OLD_CELLS = ["phi4mini-batch", "falconh1-d6-batch", "mimo-v25-d7-ep16-long",
             "joyai-flash-ep16-long-out", "lfm2-8b-a1b-d14-long"]
NEW = ["kernel.gdn_decode_roofline", "kernel.decode_attn_mha_roofline",
       "cache.delta_state_pool_bytes", "cache.delta_state_recomputes"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

# every key of the real configuration, the widths cut to a CPU's size:
# both periods of the pattern, 2 attention heads of 128 (one query head
# a KV head), 4 delta-rule heads with keys of 16 and values of 64 (two
# pairs of 128 lanes in the pool, as the chip's kernel takes them)
TINY = dict(load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
            ["config"],
            vocab_size=2048, hidden_size=256, intermediate_size=128,
            num_attention_heads=2, num_key_value_heads=2,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=64,
            max_position_embeddings=2048)


def _model(config):
    from kaito_tpu.engine.model import TransformerLM
    from kaito_tpu.models.autogen import arch_from_hf_config

    return TransformerLM(arch_from_hf_config(config), dtype=jnp.float32)


def _params(config, seed=3):
    return _model(config).init_params(jax.random.PRNGKey(seed))


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], size=n)]


@pytest.mark.parametrize("start", [0, 60])
def test_reference_agrees_with_the_program_on_the_cpu(start):
    """The reference's token-by-token recurrence against the program's
    chunked scan (75 tokens: a whole chunk of 64 and a part)."""
    params = _params(TINY)
    tokens = _tokens(75)
    ref = olmo_hybrid.forward(TINY, params, tokens, start)
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

    path = os.path.join(KBENCH, "reference", "olmo_hybrid.py")
    assert tolerance.perturbations(path) == olmo_hybrid.PERTURBATIONS
    assert set(olmo_hybrid.PERTURBATIONS) == {
        "weights_fp8", "delta_term_dropped", "decay_dropped",
        "beta_not_doubled", "conv_state_dropped", "no_l2_norm",
        "out_gate_dropped", "no_qk_norm", "qk_norm_per_head", "rope_added",
        "pre_norm", "last_layer_dropped"}
    with open(path) as f:
        source = f.read()
    imports = [line.split()[1] for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    # no module of the program, no kernel library; the recurrence token
    # by token
    assert imports == ["jax", "jax.numpy"]
    assert "kaito_tpu" not in source.replace("``kaito_tpu``", "")
    assert "jax.lax.scan(token" in source


@pytest.mark.parametrize("perturb", olmo_hybrid.PERTURBATIONS)
def test_a_cruder_computation_moves_the_reference(perturb):
    params = _params(TINY)
    tokens = _tokens(120, seed=1)
    clean = olmo_hybrid.forward(TINY, params, tokens, 0)
    crude = olmo_hybrid.forward(TINY, params, tokens, 0, perturb=perturb)
    diff = np.abs(np.asarray(clean["top"]) - np.asarray(crude["top"]))
    # (without the L2 norm a step of beta |k|^2 > 2 diverges)
    assert not np.all(np.nan_to_num(diff, nan=1.0) <= 1e-3)


@pytest.mark.parametrize("change", [
    {"model_type": "lfm2_moe"}, {"rope_parameters": {"rope_theta": 500000}},
    {"attention_bias": True}, {"linear_num_key_heads": 2},
    {"layer_types": ["linear_attention"] * 3},
    {"layer_types": ["linear_attention"] * 7 + ["sliding_attention"]},
    {"hidden_act": "gelu"}])
def test_the_reference_refuses_what_it_does_not_implement(change):
    config = dict(TINY, **change)
    with pytest.raises(ValueError):
        olmo_hybrid.forward(config, _params(TINY), [1, 2, 3], 0)
    with pytest.raises(ValueError):
        olmo_hybrid.forward(TINY, _params(TINY), [1, 2, 3], 0,
                            perturb="head_int8")


def test_the_configuration_is_the_published_one_but_for_its_cut():
    cfg = Manifest().config(CONFIG)
    entry = next(c for c in Manifest().data["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "layer_types"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    c = cfg["config"]
    assert c["num_hidden_layers"] == 8
    assert c["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"] + ["linear_attention"] * 3 + ["full_attention"]
    assert c["layer_types"] == cfg["published"]["layer_types"][:8]
    assert cfg["published"]["num_hidden_layers"] == 32 \
        == len(cfg["published"]["layer_types"])
    # no width, no head count and no vocabulary is cut
    assert (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"], c["linear_allow_neg_eigval"]) \
        == (3840, 11008, 100352, 30, 30, 30, 30, 96, 192, 4, True)
    assert c["rope_parameters"] == {"rope_theta": None}
    assert "four pipeline stages" in cfg["deployment"]
    for key in ("architectures", "head_dim", "rotary", "block_norm",
                "qk_norm", "delta_rule", "weights", "decay_draw",
                "conv_taps_draw", "norm_gain_draws", "state_dtype",
                "stored_lanes"):
        assert key in cfg["assumed"]
    assert cfg["assumed"]["state_dtype"].split(":")[0] in ("bfloat16",
                                                           "float32")
    assert cfg["server"]["config_file"] == {"max_model_len": 5120,
                                            "max_num_seqs": 32,
                                            "max-num-batched-tokens": 4096}
    assert cfg["server"]["args"] == {"enable-prefix-caching": True}
    # what only a tree that has the delta rule says: the parent maps the
    # unknown model_type to a dense decoder and says "pallas"
    assert cfg["server"]["expect"] == {"attention": "pallas+delta",
                                       "prefix_cache": "off",
                                       "hbm_sizing_source": "measured"}
    assert cfg["server"]["expect_cpu"]["attention"] == "jax+delta"
    assert cfg["reference"] == "kbench/reference/olmo_hybrid.py"
    assert float(cfg["tolerance"]["logprob_abs"]) > 0 \
        and len(cfg["tolerance"]["reason"]) > 200
    cell = Manifest().cell(CELL)
    # batch-long in every parameter but the traced span's length (the
    # cell reads under the 1,229 tokens/s at which 10 s hold a cycle)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "batch-long-t14", 1)
    mix = Manifest().traffic("batch-long-t14")
    plain = Manifest().traffic("batch-long")
    assert {k for k in mix if mix[k] != plain[k]} == {
        "trace_seconds", "trace_seconds_why",
        # what a traced second costs each mix's largest cell (PR 55)
        "trace_mb_per_s", "trace_mb_per_s_from"}
    assert (plain["trace_seconds"], mix["trace_seconds"]) == (10, 14)
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
    assert served.pop("architectures") == ["OlmoHybridForCausalLM"]
    assert {k: cfg[k] for k in served} == served
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
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
    # appended behind PR 44's last entry, in ISSUE 49's order (whatever
    # later PRs append stands behind them)
    at = names.index("cache.conv_state_pool_bytes") + 1
    assert names[at:at + 4] == NEW
    kernels, cache = "Kernels (engine/ops/)", \
        "Cache manager (engine/engine.py, native/)"
    table = {
        "kernel.gdn_decode_roofline": (
            "%", "higher", "device_trace", kernels,
            "trace_gdn_decode_roofline_pct"),
        "kernel.decode_attn_mha_roofline": (
            "%", "higher", "device_trace", kernels,
            "trace_decode_attn_mha_roofline_pct"),
        "cache.delta_state_pool_bytes": (
            "bytes", "lower", "program_counter", cache, "gauge_mean"),
        "cache.delta_state_recomputes": (
            "count", "lower", "program_counter", cache, "counter_delta")}
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
    assert m.layer_metric("kernel.gdn_decode_roofline")["args"] == {
        "pattern": "^jit_decode[^/]*/%gdn_state_update"}
    assert m.layer_metric("kernel.decode_attn_mha_roofline")["args"] == {
        "pattern": "^jit_decode[^/]*/%attention"}
    assert m.layer_metric("cache.delta_state_pool_bytes")["args"] == {
        "name": "kaito:engine_state_pool_bytes"}
    assert m.layer_metric("cache.delta_state_recomputes")["args"] == {
        "name": "kaito:engine_state_recomputes_total"}
    # every metric all five older cells report
    got = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    shared = set.intersection(*({x["name"] for x in
                                 m.metrics_for(c, "per_layer")}
                                for c in OLD_CELLS))
    assert len(shared) == 30 and shared <= got
    assert got == shared | set(NEW)
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == \
        {"out_tok_s", "setup_s"}
    out = next(x for x in m.data["end_to_end"] if x["name"] == "out_tok_s")
    assert out["workloads"][:6] == OLD_CELLS + [CELL]
    # six configurations, six cells, none on four chips
    assert [c["name"] for c in m.data["configs"]][5] == CONFIG
    assert [w["chips"] for w in m.data["workloads"]][:6] == [1] * 6
    # the accepted benchmark's rooflines and readers are not this PR's
    for name in ("rooflines_ssm", "rooflines_lfm2"):
        assert "gdn" not in open(os.path.join(KBENCH, name + ".py")).read()


def test_gdn_rooflines_on_planted_shapes():
    import rooflines_gdn as rg
    import rooflines_lfm2

    config = Manifest().config(CONFIG)["config"]
    assert rg.is_delta_rule(config)
    assert not rg.is_delta_rule({"num_hidden_layers": 32})
    assert not rg.is_delta_rule(Manifest().config("lfm2-8b-a1b-d14")
                                ["config"])
    assert rg.gdn_dims(config) == (30, 96, 192)
    assert (rg.linear_layers(config),
            rooflines_lfm2.attention_layers(config)) == (6, 2)
    # 552,960 numbers a row and layer at the logical lanes
    assert rg.gdn_state_bytes_per_row(config, 2) == 552960 * 2
    assert rg.gdn_state_bytes_per_row(config, 4) == 552960 * 4
    operands = 4 * (2 * 30 * 96 + 2 * 30 * 192 + 60)
    assert rg.gdn_decode_update_bytes(config, 10, 2) == \
        10 * (2 * 552960 * 2 + operands)
    assert rg.gdn_decode_update_bytes(config, 0, 2) == 0
    # 15,360 B a token and attention layer through the accepted function
    assert rooflines_lfm2.decode_attention_bytes(config, [1000, 50]) == \
        2 * 15360 * 1050
    # a step of 32 rows at 2,700 tokens: the two attention layers read
    # six times what the six linear layers read and write
    pages = rooflines_lfm2.decode_attention_bytes(config, [2700] * 32)
    state = 6 * rg.gdn_decode_update_bytes(config, 32, 2)
    assert 2.6e9 < pages < 2.7e9 and 0.42e9 < state < 0.45e9


def _ctx(**kw):
    whole = Manifest().config(CONFIG)
    ctx = {"trace": {"devices": 1, "window_s": 1.0, "ops": {},
                     "op_counts": {}},
           "traced_s": [2.0, 3.0], "requests": [], "config": whole,
           "peaks": PEAKS, "before": {}, "after": {}}
    ctx.update(kw)
    return ctx


def test_gdn_decode_roofline_reader_on_a_planted_trace():
    import rooflines_gdn as rg
    from readers import trace_gdn_decode_roofline_pct as reader

    config = Manifest().config(CONFIG)["config"]
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics", "kernel.gdn_decode_roofline.json"))["args"]
    # 3 + 2 chunks after the first inside the span
    reqs = [{"prompt_tokens": 2000, "chunk_s": [1.9, 2.1, 2.5, 2.9, 3.5]},
            {"prompt_tokens": 1000, "chunk_s": [2.2, 2.4, 2.6]},
            {"prompt_tokens": 50, "chunk_s": [2.7]}]
    assert reader.decoded_tokens(reqs, 2.0, 3.0) == 5
    ctx = _ctx(requests=reqs,
               trace={"devices": 1, "window_s": 1.0,
                      "ops": {"jit_decode_multi/%gdn_state_update.3": 1e-4,
                              "jit_decode_step/%gdn_state_update.1": 2e-5,
                              "jit_decode_multi/%attention.2": 0.5},
                      "op_counts": {}})
    got = reader.read(ctx, **pattern)
    need = 6 * rg.gdn_decode_update_bytes(config, 5, 2)
    assert got == pytest.approx(100.0 * (need / 819e9) / 1.2e-4)
    assert 0 < got < 100
    # a float32 state bills twice the state's bytes
    wide = dict(ctx, config=dict(ctx["config"], assumed={
        "state_dtype": "float32: held wide"}))
    assert reader.read(wide, **pattern) == pytest.approx(
        100.0 * (6 * rg.gdn_decode_update_bytes(config, 5, 4) / 819e9)
        / 1.2e-4)
    # the parent's program has no such kernel, a CPU run no trace,
    # another configuration no linear_num_value_heads: nothing to read
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, traced_s=[]), **pattern) is None
    assert reader.read(dict(ctx, trace=dict(ctx["trace"], ops={})),
                       **pattern) is None
    falcon = dict(ctx, config=Manifest().config("falcon-h1-34b-instruct-d6"))
    assert reader.read(falcon, **pattern) is None
    assert reader.read(dict(ctx, config=dict(ctx["config"], assumed={})),
                       **pattern) is None


def test_decode_attn_mha_roofline_reader_on_a_planted_trace():
    import rooflines_lfm2 as rl
    from readers import trace_decode_attn_mha_roofline_pct as reader

    config = Manifest().config(CONFIG)["config"]
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics",
        "kernel.decode_attn_mha_roofline.json"))["args"]
    reqs = [{"prompt_tokens": 2999, "chunk_s": [1.0, 4.0]},
            {"prompt_tokens": 1499, "chunk_s": [1.5, 3.5]},
            {"prompt_tokens": 10, "chunk_s": [0.1, 0.2]}]
    step = rl.decode_attention_bytes(config, [3000, 1500])
    assert step == 2 * 15360 * 4500
    assert reader.mean_step_bytes(config, reqs, 2.0, 3.0) == \
        pytest.approx(step)
    # 100 steps of 2 attention layers: 200 calls in the span
    ctx = _ctx(requests=reqs,
               trace={"devices": 1, "window_s": 1.0,
                      "ops": {"jit_decode_multi/%attention.2": 0.03,
                              "jit_decode_multi/%attention.5": 0.02,
                              "jit_prefill_step/%attention.7": 0.5},
                      "op_counts": {"jit_decode_multi/%attention.2": 120.0,
                                    "jit_decode_multi/%attention.5": 80.0,
                                    "jit_prefill_step/%attention.7": 9.0}})
    got = reader.read(ctx, **pattern)
    assert got == pytest.approx(100.0 * (100 * step / 819e9) / 0.05)
    assert 0 < got < 100
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, traced_s=[]), **pattern) is None
    assert reader.read(dict(ctx, trace=dict(ctx["trace"], ops={},
                                            op_counts={})), **pattern) is None
    # the accepted reader's configurations are not this one's
    lfm2 = dict(ctx, config=Manifest().config("lfm2-8b-a1b-d14"))
    assert reader.read(lfm2, **pattern) is None
    dense = dict(ctx, config={"config": {"num_hidden_layers": 32}})
    assert reader.read(dense, **pattern) is None


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """ONE run of the tiny copy of the cell, traced flag on (a CPU takes
    no trace, the counters' readers still read), for every test below."""
    root = str(tmp_path_factory.mktemp("olmo") / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    real = load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
    tiny = dict(real, config=TINY, deployment="CPU rehearsal only",
                tolerance={"logprob_abs": 0.002, "reason": "float32 on the "
                           "CPU against the float32 reference"})
    tiny["server"] = dict(
        real["server"],
        config_file={"max_model_len": 512, "max_num_seqs": 8,
                     "page_size": 16, "max-num-batched-tokens": 128})
    with open(os.path.join(root, "kbench", "configs",
                           "tiny-olmo-hybrid.json"), "w") as f:
        json.dump(tiny, f)
    mix = load_json(os.path.join(root, "kbench", "traffic", "batch.json"))
    # the longest check prompt is two chunks of the 128-token budget:
    # the second starts from the row of matrix state and the
    # convolutions' tail the first left and attends the pages of 2 KV
    # heads under 2 query heads
    mix["check"] = {"prompt_lens": [20, 70, 150], "decode_tokens": 24}
    with open(os.path.join(root, "kbench", "traffic",
                           "batch-long-t14.json"), "w") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    data["configs"].append({
        "name": "tiny-olmo-hybrid", "source": real["source"],
        "file": "kbench/configs/tiny-olmo-hybrid.json",
        "reduced": real["reduced"],
        "why": "CPU rehearsal of matrix-state rows beside attention pages"})
    data["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-olmo-hybrid",
         "traffic": "batch-long-t14", "chips": 1,
         "why": "rehearsal of the closed-loop mix on a delta-rule and "
         "attention hybrid"})
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
         "--workload", TINY_CELL, "--seed", str(2 ** 31 + 149), "--seconds",
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
    for name in NEW[:2]:
        assert name not in got
    # 6 delta-rule layers x 8 slots x (16 x 4 x 64 numbers of matrix
    # state + 3 inputs of 4 x (2 x 16 + 64) channels), float32
    assert got["cache.delta_state_pool_bytes"]["value"] == \
        6 * 8 * (16 * 256 + 3 * 384) * 4
    assert got["cache.delta_state_recomputes"]["value"] == 0
    assert got["cache.preemptions"]["value"] == 0
