"""Small pieces: the peaks table, the readers of counters, the
numerical comparison, the word-level tokenizer."""

import json
import os

import pytest

import check
import run as bench
from kserver import BenchError, parse_metrics
from paths import KBENCH
from readers import (client_minus_hist_ms, counter_delta, counter_share_pct,
                     gauge_mean, hist_mean_ms)


def test_peaks_are_keyed_by_device_kind_and_sourced():
    with open(os.path.join(KBENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert "cloud.google.com" in peaks["_source"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_is_an_error():
    assert bench.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(BenchError):
        bench.peaks_for("TPU v9")


def test_parse_metrics_keeps_unlabelled_samples():
    text = ("# HELP x\nkaito:a_total 3\nkaito:b{l=\"1\"} 4\n"
            "kaito:h_seconds_sum 1.5\nkaito:h_seconds_count 3\n")
    assert parse_metrics(text) == {"kaito:a_total": 3.0,
                                   "kaito:h_seconds_sum": 1.5,
                                   "kaito:h_seconds_count": 3.0}


def test_counter_and_histogram_readers():
    ctx = {"before": {"h_sum": 1.0, "h_count": 2.0, "hits": 1.0, "miss": 1.0,
                      "c": 5.0},
           "after": {"h_sum": 2.0, "h_count": 6.0, "hits": 4.0, "miss": 2.0,
                     "c": 7.0},
           "polls": [{"g": 0.5}, {"g": 1.0}, {}],
           "client": {"ttft_mean_ms": 400.0}}
    assert hist_mean_ms.read(ctx, name="h") == 250.0
    assert counter_delta.read(ctx, name="c") == 2.0
    assert counter_delta.read(ctx, name="absent") is None
    assert counter_share_pct.read(ctx, part="hits", rest="miss") == 75.0
    assert gauge_mean.read(ctx, name="g", scale=100.0) == 75.0
    assert gauge_mean.read(ctx, name="absent") is None
    assert client_minus_hist_ms.read(ctx, client="ttft_mean_ms",
                                     name="h") == 150.0
    ctx["after"]["h_count"] = 2.0
    assert hist_mean_ms.read(ctx, name="h") is None


def _served_and_ref(shift=0.0):
    prompts = [[1, 2, 3], [4, 5, 6, 7]]
    n = 2
    ref = []
    for k, ids in enumerate(prompts):
        start = 0 if k == 0 else len(ids) - 1
        length = len(ids) + n - start
        ref.append({"top": [-1.0] * length, "target": [-1.0] * length})
    served = {"first": [-1.0 + shift, -1.0], "repeat": -1.0,
              "decode": [{"ids": [9, 9], "lps": [-1.0, -1.0]},
                         {"ids": [8, 8], "lps": [-1.0, -1.0 - shift]}],
              "score": [None, -1.0, -1.0]}
    return prompts, served, ref


def test_compare_names_the_clause_that_broke():
    prompts, served, ref = _served_and_ref()
    assert check.compare(prompts, served, ref, 0.05)["failed"] == []
    prompts, served, ref = _served_and_ref(shift=0.2)
    verdict = check.compare(prompts, served, ref, 0.05)
    assert verdict["failed"] == ["decode", "prefill"]
    assert verdict["worst"]["prefill"] == pytest.approx(0.2)
    # greedy must have chosen a near-argmax of the reference
    prompts, served, ref = _served_and_ref()
    ref[1]["top"][len(prompts[1]) - 1 - (len(prompts[1]) - 1) + 1] = -0.5
    assert "decode_near_argmax" in check.compare(
        prompts, served, ref, 0.05)["failed"]


def test_reference_requests_pair_each_prompt_with_its_own_ids():
    prompts, served, _ = _served_and_ref()
    reqs = check.reference_requests(prompts, served)
    assert reqs == [{"tokens": [1, 2, 3, 9, 9], "start": 0},
                    {"tokens": [4, 5, 6, 7, 8, 8], "start": 3}]


def test_word_level_tokenizer_gives_every_id_a_word():
    from tokenizer_gen import tokenizer_dir
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(tokenizer_dir(2048),
                                        local_files_only=True)
    ids = [5, 200, 2000, 0, 20, 2]
    assert tok.encode("w5 w200 w2000 w0 w20 w2") == ids
    assert tok.decode(ids) == "w5 w200 w2000 w0 w20 w2"
    assert all(tok.decode([i]) for i in ids)       # never the empty string
    assert tok.vocab_size == 2048


def test_decode_attention_roofline_from_counts_and_bytes():
    import rooflines
    from readers import trace_decode_attn_roofline_pct as reader

    config = {"num_attention_heads": 24, "num_key_value_heads": 8,
              "hidden_size": 3072}
    assert rooflines.kv_bytes_per_token_per_layer(config) == 2 * 8 * 128 * 2
    assert rooflines.decode_attention_bytes(config, 1000, tensor_parallel=4) \
        == 1000 * 4096 / 4
    # two requests live through the whole traced span: 100 + 10 and
    # 200 + 10 tokens of context when it is sampled
    reqs = [{"prompt_tokens": 100, "chunk_s": [0.1 * i for i in range(10)] + [9.0]},
            {"prompt_tokens": 200, "chunk_s": [0.1 * i for i in range(10)] + [9.0]},
            {"prompt_tokens": 999, "chunk_s": []}]
    assert reader.live_context_tokens(reqs, 2.0, 3.0) == 320
    ctx = {"trace": {"devices": 1, "window_s": 1.0,
                     "ops": {"jit_decode_multi/%attention.11": 0.002,
                             "jit_prefill_ctx/%fusion.1": 0.5},
                     "op_counts": {"jit_decode_multi/%attention.11": 64.0,
                                   "jit_prefill_ctx/%fusion.1": 3.0}},
           "traced_s": [2.0, 3.0], "requests": reqs,
           "config": {"config": config, "server": {}},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    want = 100.0 * (64 * 320 * 4096 / 819e9) / 0.002
    got = reader.read(ctx, pattern="^jit_decode[^/]*/%attention")
    assert got == pytest.approx(want) and 0 < got < 100
    ctx["traced_s"] = []
    assert reader.read(ctx, pattern="attention") is None
