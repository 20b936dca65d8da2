"""The third configuration: MiMo-V2.5's plain reference against the
program's own CPU float32 path at a tiny size, the shares of an expert
layer adding up to the whole, the router against a hand-written top-k,
the new readers on planted numbers, and ONE CPU rehearsal of a tiny
copy of ``mimo-v25-d7-ep16-long`` in a temporary manifest, which every
test of the cell reads (the rehearsal manifest is a benchmark file and
stays as it is)."""

import json
import os
import shutil
import subprocess
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from manifest import Manifest, load_json, validate
from paths import KBENCH, MANIFEST, ROOT
from test_kbench_rehearsal import REHEARSAL, _last_line

sys.path.insert(0, os.path.join(KBENCH, "reference"))
import mimo_v2  # noqa: E402

CELL = "mimo-v25-d7-ep16-long"
CONFIG = "mimo-v2.5-d7-ep16"
TINY_CELL = "tiny-mimo-long"            # no other test file runs this cell
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# every key of the real configuration, the widths cut to a CPU's size:
# 16 experts of which a quarter is held, a window of two 16-token pages
TINY = dict(load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
            ["config"],
            vocab_size=2048, hidden_size=64, intermediate_size=128,
            num_attention_heads=8, num_key_value_heads=2, head_dim=24,
            v_head_dim=16, swa_num_attention_heads=8,
            swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
            sliding_window=32, sliding_window_size=32,
            moe_intermediate_size=32, n_routed_experts=4, expert_shards=4,
            num_experts_per_tok=4, max_position_embeddings=2048)


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
    ref = mimo_v2.forward(TINY, params, tokens, start)
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

    path = os.path.join(KBENCH, "reference", "mimo_v2.py")
    assert tolerance.perturbations(path) == mimo_v2.PERTURBATIONS
    assert set(mimo_v2.PERTURBATIONS) == {
        "no_sink", "window_as_full", "theta_swapped", "no_value_scale",
        "no_correction_bias", "softmax_router", "experts_dropped",
        "one_expert_dropped", "last_layer_dropped", "weights_fp8"}


@pytest.mark.parametrize("perturb", mimo_v2.PERTURBATIONS)
def test_a_cruder_computation_moves_the_reference(perturb):
    """With the draws of the sink, the correction bias and the held
    experts' down projections every new part moves the logits: each
    perturbation shows, one dropped expert among them."""
    params = _params(TINY)
    tokens = _tokens(120, seed=1)
    clean = mimo_v2.forward(TINY, params, tokens, 0)
    crude = mimo_v2.forward(TINY, params, tokens, 0, perturb=perturb)
    diff = np.abs(np.asarray(clean["top"]) - np.asarray(crude["top"])).max()
    assert diff > 1e-2, diff


@pytest.mark.parametrize("change", [
    {"model_type": "llama"}, {"rope_scaling": {"rope_type": "yarn"}},
    {"n_group": 4}, {"n_shared_experts": 1}, {"scoring_func": "softmax"},
    {"attention_bias": True}, {"tie_word_embeddings": True},
    {"hidden_act": "gelu"}, {"add_full_attention_sink_bias": True},
    {"moe_layer_freq": [0, 1]}])
def test_the_reference_refuses_what_it_does_not_implement(change):
    config = dict(TINY, **change)
    with pytest.raises(ValueError):
        mimo_v2.forward(config, _params(TINY), [1, 2, 3], 0)
    with pytest.raises(ValueError):
        mimo_v2.forward(TINY, _params(TINY), [1, 2, 3], 0,
                        perturb="head_int8")


def _one_layer_arch(**kw):
    from kaito_tpu.models.autogen import arch_from_hf_config

    return replace(arch_from_hf_config(TINY), **kw)


def _expert_params(arch, seed=0):
    rng = np.random.default_rng(seed)
    E, Im, X = arch.hidden_size, arch.moe_intermediate_size, arch.num_experts

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) / 5

    return {"router": draw(E, X) * 5, "router_bias": draw(X) / 2,
            "experts_gate": draw(X, E, Im), "experts_up": draw(X, E, Im),
            "experts_down": draw(X, Im, E)}


def test_the_shares_add_up():
    """16 experts cut four ways: the four shares' expert outputs sum to
    the uncut layer's, by the grouped path and by the dense one, and
    each share's counters say what it held."""
    from kaito_tpu.engine import nn

    whole = _one_layer_arch(expert_shards=1, expert_shard=0)
    p = _expert_params(whole)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((300, 64)),
                    jnp.float32)
    uncut = nn.moe_mlp(x, p, whole)
    assert np.abs(np.asarray(nn.moe_mlp_ragged(x, p, whole) - uncut)).max() \
        < 1e-5
    total, pairs = 0.0, 0
    for shard in range(4):
        share = replace(whole, expert_shards=4, expert_shard=shard)
        held = {k: (v[4 * shard:4 * shard + 4] if k.startswith("experts")
                    else v) for k, v in p.items()}
        y, stats = nn.moe_mlp_ragged(x, held, share, with_stats=True)
        total = total + y
        calls, touched, here, routed = np.asarray(stats).tolist()
        assert (calls, routed) == (4, 300 * 4) and touched <= calls
        pairs += here
    assert pairs == 300 * 4
    assert np.abs(np.asarray(total - uncut)).max() < 1e-5
    with pytest.raises(NotImplementedError, match="share"):
        nn.moe_mlp(x, p, replace(whole, expert_shards=4))


def test_the_router_against_a_hand_written_top_k():
    """Sigmoid scores, the correction bias added to choose and never to
    weigh, weights normalized over the chosen: against numpy, with a
    bias that changes the choice for some tokens."""
    from kaito_tpu.engine import nn

    arch = _one_layer_arch(expert_shards=1)
    rng = np.random.default_rng(2)
    logits = rng.standard_normal((200, 16)).astype(np.float32)
    bias = (0.5 * rng.standard_normal(16)).astype(np.float32)
    idx, w = nn.route_tokens(jnp.asarray(logits), arch, jnp.asarray(bias))
    s = 1.0 / (1.0 + np.exp(-logits))
    want = np.argsort(-(s + bias), axis=1, kind="stable")[:, :4]
    plain = np.argsort(-s, axis=1, kind="stable")[:, :4]
    assert (np.sort(np.asarray(idx), 1) == np.sort(want, 1)).all()
    assert (np.sort(want, 1) != np.sort(plain, 1)).any(1).mean() > 0.2
    picked = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(w),
                               picked / picked.sum(1, keepdims=True),
                               rtol=1e-5)
    # the softmax scoring the other models use: the softmax over the
    # chosen logits
    soft = replace(arch, router_scoring="softmax", router_bias=False)
    idx2, w2 = nn.route_tokens(jnp.asarray(logits), soft)
    top = np.sort(logits, 1)[:, ::-1][:, :4]
    e = np.exp(top - top.max(1, keepdims=True))
    np.testing.assert_allclose(np.sort(np.asarray(w2), 1)[:, ::-1],
                               e / e.sum(1, keepdims=True), rtol=1e-5)


def test_the_configuration_is_the_published_one_but_for_its_cut():
    cfg = Manifest().config(CONFIG)
    entry = next(c for c in Manifest().data["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
    c = cfg["config"]
    assert (c["num_hidden_layers"], c["hybrid_layer_pattern"],
            c["moe_layer_freq"]) == (7, [0, 1, 1, 1, 1, 0, 1],
                                     [0, 1, 1, 1, 1, 1, 1])
    assert (c["n_routed_experts"], c["expert_shards"], c["expert_shard"],
            c["num_experts_per_tok"]) == (16, 16, 0, 8)
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["vocab_size"], c["head_dim"],
            c["v_head_dim"], c["num_key_value_heads"],
            c["swa_num_key_value_heads"]) == (4096, 16384, 2048, 152576,
                                              192, 128, 4, 8)
    assert cfg["published"]["n_routed_experts"] == 256
    assert cfg["published"]["num_hidden_layers"] == 48 == \
        len(cfg["published"]["hybrid_layer_pattern"])
    # one fresh prefill chunk of 4,096 tokens, under vLLM's name for
    # the budget, through the configuration file's alias
    assert cfg["server"]["config_file"] == {"max_model_len": 5120,
                                            "max_num_seqs": 32,
                                            "max-num-batched-tokens": 4096}
    assert cfg["server"]["args"] == {"enable-prefix-caching": True,
                                     "prefill-pack": 1}
    assert cfg["server"]["expect"]["prefix_cache"] == "off"
    cell = Manifest().cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "batch-long", 1)
    mix = Manifest().traffic("batch-long")
    assert mix["check"] == {"prompt_lens": [150, 1100, 4500],
                            "decode_tokens": 24}
    assert (mix["loop"], mix["concurrency_per_slot"], mix["distinct"],
            mix["mix_seed"], mix["count"]) == ("closed", 2, 48, 606, 3072)
    assert mix["prompt"]["unique"] == {"dist": "uniform", "min": 1024,
                                       "max": 4096}


def test_the_top_level_keys_are_the_catalog_rows_but_for_reduced():
    """The driver compares the file's top level with the catalog's row;
    the harness serves the ``config`` group.  One model, written twice:
    the two may not drift, and only the reduced keys may differ from
    the row."""
    cfg = load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
    served = dict(cfg["config"])
    assert served.pop("architectures") == ["MiMoV2ForCausalLM"]
    assert {k: cfg[k] for k in served} == served
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert row["source_url"] == cfg["source"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


@pytest.mark.parametrize("key", ["max-num-batched-tokens",
                                 "max_num_batched_tokens"])
def test_the_config_file_alias_sets_the_prefill_budget(tmp_path, key):
    from kaito_tpu.engine.config import EngineConfig
    from kaito_tpu.engine.server import load_config_file

    path = tmp_path / "kaito.yaml"
    path.write_text(json.dumps({"engine": {key: 4096}}))
    assert load_config_file(EngineConfig(), str(path)) \
        .max_prefill_tokens == 4096


def test_the_cell_reports_what_the_issue_lists():
    m = Manifest()
    got = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    new = {"kernel.moe_experts_roofline", "kernel.decode_attn_kinds_roofline",
           "moe.experts_touched_pct", "moe.held_pairs_pct",
           "cache.window_pages_per_seq"}
    assert new <= got
    both = {x["name"] for x in m.metrics_for("phi4mini-batch", "per_layer")} \
        & {x["name"] for x in m.metrics_for("falconh1-d6-batch", "per_layer")}
    assert both - got == {"kernel.decode_attn_roofline"}
    assert "sched.first_token_deferred_pct" not in got
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == \
        {"out_tok_s", "setup_s"}
    for name in new:
        entry = next(x for x in m.data["per_layer"] if x["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "out_tok_s"


def test_pr_34s_entry_stands_where_it_stood():
    """What test_kbench_prefill_multi_metric.py's first test holds, with
    the entry found by name: that test finds it as ``per_layer[-1]``
    with two cells; this PR's five entries are appended behind it and
    its cell to the entry's cells, as the benchmark's contract has a
    later PR do (tests/conftest.py marks that one test as expected to
    fail, for that reason alone)."""
    from readers import counter_share_pct

    metric = "sched.prefill_multi_pct"
    spec = load_json(os.path.join(KBENCH, "layer_metrics", metric + ".json"))
    assert spec["reader"] == "counter_share_pct"
    assert spec["layer"] == "Scheduler (engine/engine.py)"
    assert spec["moves"] == "out_tok_s" and spec["unit"] == "%"
    assert spec["args"] == {
        "part": "kaito:engine_prefill_turns_multi_total",
        "rest": "kaito:engine_prefill_turns_single_total"}
    per_layer = Manifest().data["per_layer"]
    names = [x["name"] for x in per_layer]
    at = names.index(metric)
    assert per_layer[at] == {
        "name": metric, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "Scheduler (engine/engine.py)", "moves": "out_tok_s",
        # the new cell appended to its cells, as to every metric the
        # cell reports (the serial scheduler's counters are there)
        "workloads": ["phi4mini-batch", "falconh1-d6-batch", CELL]}
    # nothing stands before it that did not, and behind it only what
    # this PR appended, in the order ISSUE 38 lists them
    assert at == 24 and names[at + 1:] == [
        "kernel.moe_experts_roofline", "kernel.decode_attn_kinds_roofline",
        "moe.experts_touched_pct", "moe.held_pairs_pct",
        "cache.window_pages_per_seq"]
    other = {"kaito:generation_tokens_total": 5.0}
    assert counter_share_pct.read({"before": {}, "after": other},
                                  **spec["args"]) is None
    still = {spec["args"]["part"]: 4.0, spec["args"]["rest"]: 7.0}
    assert counter_share_pct.read({"before": still, "after": still},
                                  **spec["args"]) is None
    grown = {spec["args"]["part"]: 9.0, spec["args"]["rest"]: 3.0}
    assert counter_share_pct.read({"before": other, "after": grown},
                                  **spec["args"]) == 75.0


def test_expert_roofline_bills_only_the_touched_experts():
    import rooflines_moe
    from readers import trace_moe_experts_roofline_pct as reader

    whole = Manifest().config(CONFIG)
    config = whole["config"]
    one = rooflines_moe.expert_matrix_bytes(config)
    assert one == 3 * 4096 * 2048 * 2 and \
        rooflines_moe.expert_layers(config) == 6
    pair = 2 * (2 * 4096 + 2048) + 4 * (2 * 2048 + 4096)
    assert rooflines_moe.moe_decode_bytes(config, 10, 16) == \
        10 * one + 16 * pair
    # a window of 100 decode steps over 6 expert layers: 9,600 calls of
    # the 16 held experts, 6,000 touched, 9,000 pairs here; a quarter of
    # the steps ran inside the traced span (3 kernel calls a layer-step)
    before = {reader.CALLS: 0.0, reader.TOUCHED: 0.0, reader.PAIRS: 0.0}
    after = {reader.CALLS: 9600.0, reader.TOUCHED: 6000.0,
             reader.PAIRS: 9000.0}
    ctx = {"trace": {"devices": 1, "window_s": 1.0,
                     "ops": {"jit_decode_multi/%gmm.3": 0.08,
                             "jit_decode_multi/%gmm.4": 0.12,
                             "jit_prefill_step/%gmm.9": 0.5},
                     "op_counts": {"jit_decode_multi/%gmm.3": 150.0,
                                   "jit_decode_multi/%gmm.4": 300.0,
                                   "jit_prefill_step/%gmm.9": 18.0}},
           "before": before, "after": after, "config": whole,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics", "kernel.moe_experts_roofline.json"))["args"]
    got = reader.read(ctx, **pattern)
    need = rooflines_moe.moe_decode_bytes(config, 6000 * 0.25, 9000 * 0.25)
    assert got == pytest.approx(100.0 * (need / 819e9) / 0.2)
    assert 0 < got < 100
    # a skipped expert is not billed: with every call counted as
    # touched the same time would read 1.6 times the share
    all_billed = dict(ctx, after=dict(after, **{reader.TOUCHED: 9600.0}))
    assert reader.read(all_billed, **pattern) > 1.5 * got
    # no kernel, no trace, no counters, another architecture: nothing
    # to read, and no exception
    no_kernel = dict(ctx, trace=dict(ctx["trace"], ops={}, op_counts={}))
    assert reader.read(no_kernel, **pattern) is None
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, after={}), **pattern) is None
    dense = dict(ctx, config={"config": {"num_hidden_layers": 32}})
    assert reader.read(dense, **pattern) is None


def test_attention_roofline_by_kind_from_contexts():
    import rooflines_attn_kinds as rk
    from readers import trace_decode_attn_kinds_roofline_pct as reader

    whole = Manifest().config(CONFIG)
    config = whole["config"]
    assert rk.kinds(config) == {"full": (2, 2560.0), "window": (5, 5120.0)}
    # a row of 1,000 tokens and one of 50 (inside the window)
    step = rk.decode_attention_bytes_by_kind(config, [1000, 50])
    assert step == 2 * 2560 * 1050 + 5 * 5120 * (128 + 50)
    reqs = [{"prompt_tokens": 999, "chunk_s": [1.0, 4.0]},
            {"prompt_tokens": 49, "chunk_s": [1.5, 3.5]},
            {"prompt_tokens": 10, "chunk_s": [0.1, 0.2]}]
    assert reader.mean_step_bytes(config, reqs, 2.0, 3.0) == \
        pytest.approx(step)
    ctx = {"trace": {"devices": 1, "window_s": 1.0,
                     "ops": {"jit_decode_multi/%attention.2": 0.004,
                             "jit_decode_multi/%attention.5": 0.002,
                             "jit_prefill_step/%attention.7": 0.5},
                     "op_counts": {"jit_decode_multi/%attention.2": 500.0,
                                   "jit_decode_multi/%attention.5": 200.0}},
           "traced_s": [2.0, 3.0], "requests": reqs, "config": whole,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    pattern = load_json(os.path.join(
        KBENCH, "layer_metrics",
        "kernel.decode_attn_kinds_roofline.json"))["args"]
    got = reader.read(ctx, **pattern)
    assert got == pytest.approx(100.0 * (100 * step / 819e9) / 0.006)
    assert 0 < got < 100
    one_kind = dict(ctx, config={"config": {"num_hidden_layers": 32}})
    assert reader.read(one_kind, **pattern) is None
    assert reader.read(dict(ctx, trace=None), **pattern) is None
    assert reader.read(dict(ctx, traced_s=[]), **pattern) is None


def test_ratio_readers_on_planted_numbers():
    from readers import counter_ratio_pct, gauge_ratio_mean

    ctx = {"before": {"a": 10.0, "b": 100.0}, "after": {"a": 20.0,
                                                        "b": 260.0}}
    assert counter_ratio_pct.read(ctx, part="a", whole="b") == 6.25
    assert counter_ratio_pct.read(ctx, part="a", whole="c") is None
    assert counter_ratio_pct.read(
        dict(ctx, after={"a": 20.0, "b": 100.0}), part="a", whole="b") is None
    polls = [{"p": 30.0, "s": 10.0}, {"p": 8.0, "s": 2.0}, {"p": 0.0,
                                                           "s": 0.0}, {}]
    assert gauge_ratio_mean.read({"polls": polls}, name="p", over="s") == 3.5
    assert gauge_ratio_mean.read({"polls": [{}]}, name="p", over="s") is None


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """ONE run of the tiny copy of the cell, traced flag on (a CPU takes
    no trace, the counters' readers still read), for every test below."""
    root = str(tmp_path_factory.mktemp("mimo") / "rehearsal")
    shutil.copytree(os.path.dirname(REHEARSAL), root)
    real = load_json(os.path.join(KBENCH, "configs", CONFIG + ".json"))
    tiny = dict(real, config=TINY, deployment="CPU rehearsal only",
                tolerance={"logprob_abs": 0.002, "reason": "float32 on the "
                           "CPU against the float32 reference"})
    tiny["server"] = dict(
        real["server"],
        config_file={"max_model_len": 512, "max_num_seqs": 8,
                     "page_size": 16, "max-num-batched-tokens": 128})
    with open(os.path.join(root, "kbench", "configs", "tiny-mimo.json"),
              "w") as f:
        json.dump(tiny, f)
    mix = load_json(os.path.join(root, "kbench", "traffic", "batch.json"))
    # the longest check prompt is two chunks of the 128-token budget:
    # the second goes down context prefill across freed window pages;
    # 40 decoded tokens pass the 32-position window
    mix["check"] = {"prompt_lens": [20, 70, 150], "decode_tokens": 40}
    mix["output"] = {"dist": "uniform", "min": 24, "max": 48}
    with open(os.path.join(root, "kbench", "traffic", "batch-long.json"),
              "w") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    data = load_json(path)
    data["configs"].append({
        "name": "tiny-mimo", "source": real["source"],
        "file": "kbench/configs/tiny-mimo.json", "reduced": real["reduced"],
        "why": "CPU rehearsal of the two-kind cache and the held experts"})
    data["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-mimo", "traffic": "batch-long",
         "chips": 1, "why": "rehearsal of the closed-loop mix on two kinds "
         "of page"})
    for m in data["end_to_end"]:
        if "workloads" in m and "tiny-batch" in m["workloads"]:
            m["workloads"].append(TINY_CELL)
    ours = {m["name"]: m for m in load_json(MANIFEST)["per_layer"]}
    data["per_layer"] += [dict(ours[name], workloads=[TINY_CELL])
                          for name in ("kernel.moe_experts_roofline",
                                       "kernel.decode_attn_kinds_roofline",
                                       "moe.experts_touched_pct",
                                       "moe.held_pairs_pct",
                                       "cache.window_pages_per_seq")]
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    assert validate(Manifest(path)) == []
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    res = subprocess.run(
        [sys.executable, os.path.join(KBENCH, "run.py"), "--manifest", path,
         "--workload", TINY_CELL, "--seed", str(2 ** 31 + 83), "--seconds",
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
    assert "kernel.moe_experts_roofline" not in got
    assert "kernel.decode_attn_kinds_roofline" not in got
    # a quarter of the experts is held: a quarter of the pairs, near
    # enough, lands here, and not every held expert gets one every step
    assert 10.0 < got["moe.held_pairs_pct"]["value"] < 45.0
    assert 0.0 < got["moe.experts_touched_pct"]["value"] <= 100.0


def test_the_rehearsal_holds_the_window_tables_bound(rehearsal):
    _, out = rehearsal
    # window 32 over pages of 16: 4 pages at most while a row decodes
    assert 0.0 < out["metrics"]["cache.window_pages_per_seq"]["value"] <= 4.0
