"""Draft-model speculative decoding (docs/speculative.md).

Covers the whole ladder: the windowed rejection sampler's exactness
properties (greedy reduction, distribution preservation), the adaptive
depth controller's AIMD + fallback behavior, the n-gram index vs the
brute-force trailing scan it replaced, engine end-to-end greedy
equivalence (synthetic self-draft AND the committed real checkpoint
against its pinned goldens), the adversarial low-acceptance fallback,
and the workspace/preset plumbing.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.engine.sampler import spec_verify_sample
from kaito_tpu.engine.spec import DepthController, NgramIndex

REPO = __file__.rsplit("/tests/", 1)[0]
TESTDATA = os.path.join(os.path.dirname(__file__), "testdata")
REAL_CKPT = os.path.join(REPO, "checkpoints", "tiny-llama-real")
HAS_REAL = os.path.exists(os.path.join(REAL_CKPT, "model.safetensors")) \
    and os.path.exists(os.path.join(TESTDATA,
                                    "goldens_tiny-llama-real.json"))

BASE = dict(model="tiny-llama-test", max_model_len=256, page_size=16,
            max_num_seqs=4, dtype="float32", kv_dtype="float32",
            prefill_buckets=(32, 64, 128), seed=0,
            enable_prefix_caching=False)


def _greedy(n, **kw):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True,
                          **kw)


def _drive(eng, reqs, max_steps=800):
    for _ in range(max_steps):
        eng.step()
        if all(r.finish_reason for r in reqs):
            break
    return [list(r.output_tokens) for r in reqs]


def _mk(draft="", **kw):
    return InferenceEngine(EngineConfig(**{**BASE, **kw},
                                        speculative_draft=draft))


# ---------------------------------------------------------------------------
# DepthController: AIMD + the draft -> ngram -> probation ladder
# ---------------------------------------------------------------------------

def test_controller_raises_depth_on_high_acceptance():
    ctl = DepthController(1, k_max=6, k_init=2)
    for _ in range(10):
        ctl.observe(0, 4, 4)          # perfect acceptance
    assert ctl.depth(0) == 6          # additive increase to the cap
    assert ctl.mode(0) == "draft"
    assert ctl.accept_ewma(0) > 0.9


def test_controller_decays_depth_on_poor_acceptance():
    ctl = DepthController(1, k_max=8, k_init=8)
    ctl.observe(0, 8, 2)              # 25% < lower_at
    assert ctl.depth(0) == 4          # multiplicative decrease
    ctl.observe(0, 4, 1)
    assert ctl.depth(0) == 2


def test_controller_falls_back_to_ngram_under_adversarial_acceptance():
    ctl = DepthController(1, k_max=4, k_init=4,
                          fallback_patience=4)
    rounds = 0
    while ctl.mode(0) == "draft":
        ctl.observe(0, ctl.depth(0), 0)   # nothing ever accepted
        rounds += 1
        assert rounds < 50
    assert ctl.mode(0) == "ngram"
    assert ctl.depth(0) == 0          # depth reads 0 while fallen back


def test_controller_probation_retries_draft_at_depth_one():
    ctl = DepthController(1, k_max=4, k_init=4,
                          fallback_patience=2, probation_rounds=3)
    for _ in range(20):
        ctl.observe(0, 4, 0)
        if ctl.mode(0) == "ngram":
            break
    assert ctl.mode(0) == "ngram"
    for _ in range(3):
        assert ctl.mode(0) == "ngram"
        ctl.note_fallback_round(0)
    assert ctl.mode(0) == "draft" and ctl.depth(0) == 1


def test_controller_reset_restores_slot_state():
    ctl = DepthController(2, k_max=4, k_init=2, fallback_patience=1)
    for _ in range(5):
        ctl.observe(0, 4, 0)
    assert ctl.mode(0) == "ngram"
    ctl.reset(0)
    assert ctl.mode(0) == "draft" and ctl.depth(0) == 2
    # slot 1 untouched throughout
    assert ctl.mode(1) == "draft" and ctl.depth(1) == 2


def test_controller_mean_depth_over_slots():
    ctl = DepthController(3, k_max=8, k_init=2)
    for _ in range(10):
        ctl.observe(0, 4, 4)
    assert ctl.mean_depth([0, 1]) == pytest.approx((8 + 2) / 2)
    assert ctl.mean_depth([]) == 0.0


# ---------------------------------------------------------------------------
# NgramIndex vs the brute-force trailing scan it replaced
# ---------------------------------------------------------------------------

def _scan_propose(tokens, k, max_tokens):
    """Reference: newest earlier occurrence of the trailing k-gram."""
    if len(tokens) < k + 1 or max_tokens <= 0:
        return []
    tail = tuple(tokens[-k:])
    for start in range(len(tokens) - k - 1, -1, -1):
        if tuple(tokens[start:start + k]) == tail:
            return tokens[start + k:start + k + max_tokens]
    return []


@pytest.mark.parametrize("k", [2, 3])
def test_ngram_index_matches_brute_force_scan(k):
    rng = np.random.RandomState(k)
    toks = rng.randint(0, 6, 40).tolist()   # small alphabet: many hits
    idx = NgramIndex(k, toks[:10])
    cur = toks[:10]
    for t in toks[10:]:
        idx.append(t)
        cur.append(t)
        for m in (1, 4, 8):
            assert idx.propose(m) == _scan_propose(cur, k, m), \
                f"diverged at len={len(cur)} max_tokens={m}"


def test_ngram_index_never_matches_own_tail():
    # [1,2,3,1,2]: the trailing [1,2] matches offset 0 and proposes
    # its continuation [3,1,2] — never the tail occurrence itself
    idx = NgramIndex(2, [1, 2, 3, 1, 2])
    assert idx.propose(4) == [3, 1, 2]
    assert idx.propose(1) == [3]
    # a gram only present as the tail itself finds nothing
    idx2 = NgramIndex(2, [1, 2, 3, 4, 5])
    assert idx2.propose(4) == []


def test_ngram_index_match_falls_out_of_window():
    # single early occurrence of the tail gram: in-window it proposes,
    # once older than `window` it is a miss — the scan's old bound
    idx = NgramIndex(2, [5, 6, 9], window=8)
    idx.append(5)
    idx.append(6)
    assert idx.propose(3) == [9, 5, 6]
    idx2 = NgramIndex(2, [5, 6, 9], window=8)
    for t in range(20, 27):
        idx2.append(t)
    idx2.append(5)
    idx2.append(6)       # start 0 < n - window: stale
    assert idx2.propose(3) == []


def test_ngram_index_windowed_matches_windowed_scan():
    rng = np.random.RandomState(7)
    toks = rng.randint(0, 4, 300).tolist()   # tiny alphabet: many hits
    W = 32
    idx = NgramIndex(2, toks[:5], window=W)
    cur = toks[:5]
    for t in toks[5:]:
        idx.append(t)
        cur.append(t)
        for m in (1, 6):
            assert idx.propose(m) == _scan_propose(cur[-W:], 2, m), \
                f"diverged at len={len(cur)} max_tokens={m}"
    # memory stays O(window): buffer trimmed, stale entries swept
    assert len(idx.tokens) <= 2 * W
    assert all(s >= idx.n - 2 * W for s in idx.last.values())


# ---------------------------------------------------------------------------
# spec_verify_sample: exactness properties
# ---------------------------------------------------------------------------

def _keys(n, seed=0):
    return jnp.asarray(jax.random.split(jax.random.PRNGKey(seed), n),
                       jnp.uint32)


def test_verify_sample_greedy_accepts_matching_prefix():
    V, K = 7, 3
    rng = np.random.RandomState(0)
    tl = jnp.asarray(rng.randn(1, K + 1, V), jnp.float32)
    argmax = np.argmax(np.asarray(tl[0]), axis=-1)
    # proposal agrees at positions 0,1 and diverges at 2
    prop = np.array([[argmax[0], argmax[1], (argmax[2] + 1) % V]])
    out, n_emit, lps, _ = spec_verify_sample(
        tl, jnp.zeros((1, K, V), jnp.float32), jnp.asarray(prop),
        jnp.asarray([K]), jnp.asarray([0.0]),
        jnp.asarray([False]), _keys(1))
    assert int(n_emit[0]) == 3        # 2 accepted + the correction
    assert np.asarray(out)[0, :3].tolist() == argmax[:3].tolist()
    # logprobs are the UNMODIFIED target distribution's
    ref = jax.nn.log_softmax(tl[0], axis=-1)
    for j in range(3):
        assert float(lps[0, j]) == pytest.approx(
            float(ref[j, argmax[j]]), abs=1e-5)


def test_verify_sample_greedy_full_accept_emits_bonus():
    V, K = 5, 2
    rng = np.random.RandomState(1)
    tl = jnp.asarray(rng.randn(1, K + 1, V), jnp.float32)
    argmax = np.argmax(np.asarray(tl[0]), axis=-1)
    prop = np.array([argmax[:K]])
    out, n_emit, _, _ = spec_verify_sample(
        tl, jnp.zeros((1, K, V), jnp.float32), jnp.asarray(prop),
        jnp.asarray([K]), jnp.asarray([0.0]),
        jnp.asarray([False]), _keys(1))
    assert int(n_emit[0]) == K + 1    # whole window + bonus
    assert np.asarray(out)[0].tolist() == argmax.tolist()


def test_verify_sample_prop_len_zero_is_plain_step():
    V = 5
    rng = np.random.RandomState(2)
    tl = jnp.asarray(rng.randn(2, 3, V), jnp.float32)
    out, n_emit, _, _ = spec_verify_sample(
        tl, jnp.zeros((2, 2, V), jnp.float32),
        jnp.zeros((2, 2), jnp.int32), jnp.asarray([0, 0]),
        jnp.asarray([0.0, 0.0]), jnp.asarray([False, False]), _keys(2))
    assert np.asarray(n_emit).tolist() == [1, 1]
    assert np.asarray(out)[:, 0].tolist() == \
        np.argmax(np.asarray(tl)[:, 0], axis=-1).tolist()


def test_verify_sample_first_token_marginal_is_target_distribution():
    """Leviathan's theorem, tested not assumed: accept-or-residual on
    draft proposals emits x ~ p exactly, for an ARBITRARY q."""
    V, N = 5, 6000
    rng = np.random.RandomState(3)
    tlog = rng.randn(V).astype(np.float32) * 1.5
    dlog = rng.randn(V).astype(np.float32) * 1.5   # deliberately off-p
    p = np.exp(tlog - tlog.max()); p /= p.sum()

    tl = jnp.broadcast_to(jnp.asarray(tlog), (N, 2, V))
    dl = jnp.broadcast_to(jnp.asarray(dlog), (N, 1, V))
    # proposals drawn from q so the accept test faces q's true draws
    q = np.exp(dlog - dlog.max()); q /= q.sum()
    prop = rng.choice(V, size=(N, 1), p=q).astype(np.int32)
    out, n_emit, _, _ = spec_verify_sample(
        tl, dl, jnp.asarray(prop), jnp.full((N,), 1),
        jnp.full((N,), 1.0), jnp.zeros((N,), bool), _keys(N, seed=9))
    assert int(jnp.min(n_emit)) >= 1
    first = np.asarray(out)[:, 0]
    freq = np.bincount(first, minlength=V) / N
    # ~3 sigma of a multinomial at N=6000
    assert np.abs(freq - p).max() < 3.5 * np.sqrt(p.max() / N) + 0.01, \
        f"marginal {freq} != target {p}"


def test_verify_sample_onehot_q_accept_prob_is_target_prob():
    """A deterministic proposer (n-gram) is the one-hot-q limit: the
    proposal token is accepted with probability exactly p(token)."""
    V, N, tok = 5, 6000, 2
    rng = np.random.RandomState(4)
    tlog = rng.randn(V).astype(np.float32)
    p = np.exp(tlog - tlog.max()); p /= p.sum()
    tl = jnp.broadcast_to(jnp.asarray(tlog), (N, 2, V))
    prop = jnp.full((N, 1), tok, jnp.int32)
    out, n_emit, _, _ = spec_verify_sample(
        tl, jnp.zeros((N, 1, V), jnp.float32), prop, jnp.full((N,), 1),
        jnp.full((N,), 1.0), jnp.ones((N,), bool), _keys(N, seed=11))
    accept_rate = float(np.mean(np.asarray(n_emit) == 2))
    assert accept_rate == pytest.approx(float(p[tok]), abs=0.03)
    # rejected rows resampled from the residual: never the proposal
    rej = np.asarray(out)[np.asarray(n_emit) == 1, 0]
    assert not np.any(rej == tok)


# ---------------------------------------------------------------------------
# Engine end-to-end: the draft path against the plain engine
# ---------------------------------------------------------------------------

REPEAT_PROMPT = [7, 11, 13, 7, 11, 13, 7, 11, 13, 7, 11]


# slow: 33 s alone under the check's command
@pytest.mark.slow
def test_draft_greedy_equivalence_and_fewer_steps():
    ref = _mk()
    out_ref = _drive(ref, [ref.submit(REPEAT_PROMPT, _greedy(32))])
    eng = _mk(draft="tiny-llama-test")   # self-draft: same synth weights
    req = eng.submit(REPEAT_PROMPT, _greedy(32))
    out = _drive(eng, [req])
    assert out == out_ref
    # speculation engaged and paid: strictly fewer target dispatches
    # than tokens emitted
    assert eng.counters["spec_draft_steps_total"] >= 1
    assert eng.counters["decode_steps_total"] < 32
    assert eng.counters["spec_draft_accepted_tokens_total"] > 0


def test_non_pow2_draft_k_clamps_to_verify_window():
    """speculative_draft_k=3: once the controller reaches full depth
    the pow2 program bucket (4) must clamp to W-1=3 — regression for a
    shape mismatch inside the fused verify that killed the decode
    step."""
    ref = _mk()
    out_ref = _drive(ref, [ref.submit(REPEAT_PROMPT, _greedy(32))])
    eng = _mk(draft="tiny-llama-test", speculative_draft_k=3)
    out = _drive(eng, [eng.submit(REPEAT_PROMPT, _greedy(32))])
    assert out == out_ref
    assert eng.counters["spec_draft_steps_total"] >= 1
    assert eng.counters["spec_draft_accepted_tokens_total"] > 0


def test_full_accept_rounds_keep_draft_kv_exact():
    """Self-draft greedy full-accept steady state: identical weights
    mean nothing is ever rejected — IF the draft KV stays exact.
    Regression for the full-accept hole: commit() claimed one position
    past what the proposal scan wrote, so the next round attended over
    garbage and acceptance collapsed to ~0.5 in exactly the
    high-acceptance steady state."""
    ref = _mk()
    out_ref = _drive(ref, [ref.submit(REPEAT_PROMPT, _greedy(32))])
    eng = _mk(draft="tiny-llama-test")
    out = _drive(eng, [eng.submit(REPEAT_PROMPT, _greedy(32))])
    assert out == out_ref
    prop = eng.counters["spec_draft_proposed_tokens_total"]
    acc = eng.counters["spec_draft_accepted_tokens_total"]
    assert prop > 0 and acc == prop


def test_probation_ticks_without_ngram_proposer():
    """A demoted slot must tick probation (and re-arm the draft) even
    with speculative_ngram=0, the default — regression for a permanent
    draft disable when the n-gram proposer is off."""
    eng = _mk(draft="tiny-llama-test")
    assert eng.cfg.speculative_ngram == 0
    req = eng.submit(REPEAT_PROMPT, _greedy(24))
    eng.step()                  # prefill; slot 0 now decoding
    ctl = eng.spec_ctl
    ctl._mode[0] = "ngram"      # as sustained-poor acceptance would
    ctl._probation[0] = 2
    steps = 0
    while ctl.mode(0) == "ngram":
        assert not req.finish_reason and steps < 10
        eng.step()
        steps += 1
    assert ctl.mode(0) == "draft" and ctl.depth(0) == 1
    _drive(eng, [req])          # and the request still completes
    assert len(req.output_tokens) == 24


def test_draft_metrics_exposition():
    from kaito_tpu.engine.metrics import EngineMetrics

    eng = _mk(draft="tiny-llama-test")
    m = EngineMetrics(eng)
    _drive(eng, [eng.submit(REPEAT_PROMPT, _greedy(24))])
    text = m.registry.expose()
    assert 'kaito:spec_proposed_tokens_total{mode="draft"}' in text
    assert 'kaito:spec_accepted_tokens_total{mode="draft"}' in text
    assert 'kaito:spec_proposed_tokens_total{mode="ngram"}' in text
    assert "kaito:spec_depth" in text
    for line in text.splitlines():
        if line.startswith('kaito:spec_proposed_tokens_total{mode="draft"}'):
            assert float(line.split()[-1]) > 0


def test_draft_sampled_traffic_speculates_and_completes():
    eng = _mk(draft="tiny-llama-test")
    req = eng.submit(REPEAT_PROMPT, SamplingParams(
        max_tokens=24, temperature=0.8, ignore_eos=True))
    out = _drive(eng, [req])[0]
    assert len(out) == 24
    assert eng.counters["spec_draft_steps_total"] >= 1
    assert eng.counters["spec_draft_proposed_tokens_total"] > 0


def test_draft_batch_mixed_sampling_matches_plain_greedy_rows():
    """Greedy rows stay bit-exact even sharing a verify batch with
    sampled rows."""
    ref = _mk()
    out_ref = _drive(ref, [ref.submit(REPEAT_PROMPT, _greedy(20))])[0]
    eng = _mk(draft="tiny-llama-test")
    g = eng.submit(REPEAT_PROMPT, _greedy(20))
    s = eng.submit([3, 5, 9, 3, 5, 9], SamplingParams(
        max_tokens=20, temperature=0.9, ignore_eos=True))
    outs = _drive(eng, [g, s])
    assert outs[0] == out_ref
    assert len(outs[1]) == 20


@pytest.mark.skipif(not HAS_REAL, reason="no committed real checkpoint")
def test_real_checkpoint_draft_greedy_matches_goldens():
    """The acceptance bar: draft-spec greedy output is token-identical
    to the PINNED golden continuations of the trained checkpoint, with
    fewer target forwards than tokens emitted."""
    golden = json.load(open(os.path.join(
        TESTDATA, "goldens_tiny-llama-real.json")))
    cfg = EngineConfig(model="tiny-llama-real", weights_dir=REAL_CKPT,
                       dtype="float32", kv_dtype="float32",
                       max_model_len=512, max_num_seqs=2,
                       prefill_buckets=(64, 128),
                       enable_prefix_caching=False, seed=0,
                       speculative_draft="tiny-llama-real",
                       speculative_draft_k=4,
                       speculative_draft_weights_dir=REAL_CKPT)
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        total = 0
        for p in golden["prompts"]:
            want = p["fp32"]["greedy_tokens"]
            req = eng.submit(list(p["prompt_tokens"]),
                             _greedy(len(want)))
            got = [t for t in req.stream()]
            assert got == want
            total += len(want)
        assert eng.counters["decode_steps_total"] < total
        assert eng.counters["spec_draft_accepted_tokens_total"] > 0
    finally:
        eng.stop()


@pytest.mark.skipif(not HAS_REAL, reason="no committed real checkpoint")
def test_adversarial_draft_falls_back_and_output_stays_exact():
    """Trained target + UNTRAINED (synthetic) draft: acceptance is
    adversarially low, the controller must walk depth down / flip
    slots to the fallback, and greedy output must STILL match the
    goldens (correctness never rides on acceptance)."""
    golden = json.load(open(os.path.join(
        TESTDATA, "goldens_tiny-llama-real.json")))
    p = golden["prompts"][0]
    want = p["fp32"]["greedy_tokens"]
    cfg = EngineConfig(model="tiny-llama-real", weights_dir=REAL_CKPT,
                       dtype="float32", kv_dtype="float32",
                       max_model_len=512, max_num_seqs=2,
                       prefill_buckets=(64, 128),
                       enable_prefix_caching=False, seed=0,
                       speculative_draft="tiny-llama-real",
                       speculative_draft_k=4,
                       speculative_draft_weights_dir="")  # synthetic!
    eng = InferenceEngine(cfg)
    eng.start()
    try:
        req = eng.submit(list(p["prompt_tokens"]), _greedy(len(want)))
        got = [t for t in req.stream()]
        assert got == want
        prop = eng.counters["spec_draft_proposed_tokens_total"]
        acc = eng.counters["spec_draft_accepted_tokens_total"]
        if prop:
            assert acc / prop < 0.9   # the draft really is bad
        # the controller reacted: depth off the initial value or the
        # slot rode the fallback ladder (depth 0 in ngram mode)
        ctl = eng.spec_ctl
        assert ctl.depth(0) != ctl.k_init or ctl.mode(0) == "ngram" \
            or ctl.accept_ewma(0) < 0.8
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Plumbing: registry validation, manifests, preset generator
# ---------------------------------------------------------------------------

def test_resolve_speculative_draft_auto_and_errors():
    from kaito_tpu.models.registry import (get_model_by_name,
                                           resolve_speculative_draft)

    target = get_model_by_name("llama-3.3-70b-instruct")
    assert resolve_speculative_draft(target, "") == ""
    assert resolve_speculative_draft(target, "auto") == \
        "llama-3.1-8b-instruct"
    assert resolve_speculative_draft(
        target, "llama-3.1-8b-instruct") == "llama-3.1-8b-instruct"
    with pytest.raises(ValueError, match="not in the model catalog"):
        resolve_speculative_draft(target, "no-such-preset")
    with pytest.raises(ValueError, match="vocab_size"):
        resolve_speculative_draft(target, "phi-4")
    # a target with no curated pairing: auto quietly disables
    unpaired = get_model_by_name("tiny-llama-test")
    assert resolve_speculative_draft(unpaired, "auto") == ""


def test_manifest_annotation_renders_engine_flag():
    from kaito_tpu.api import InferenceSpec, ObjectMeta, ResourceSpec, Workspace
    from kaito_tpu.manifests.inference import build_engine_command
    from kaito_tpu.models.registry import get_model_by_name
    from kaito_tpu.parallel.plan import plan_parallelism
    from kaito_tpu.sku.catalog import CHIP_CATALOG

    md = get_model_by_name("llama-3.3-70b-instruct")
    plan = plan_parallelism(md, CHIP_CATALOG["v5e"], workload="serve",
                            max_model_len=2048)
    ws = Workspace(
        ObjectMeta(name="spec", annotations={
            "kaito-tpu.io/speculative-draft": "auto"}),
        resource=ResourceSpec(instance_type="ct5lp-hightpu-4t"),
        inference=InferenceSpec(preset="llama-3.3-70b-instruct"))
    cmd = build_engine_command(ws, md, plan)
    i = cmd.index("--speculative-draft")
    assert cmd[i + 1] == "llama-3.1-8b-instruct"   # auto resolved
    # no annotation -> no flag
    ws.metadata.annotations = {}
    assert "--speculative-draft" not in build_engine_command(ws, md, plan)


def test_workspace_plan_fails_on_bad_draft_annotation():
    from kaito_tpu.api import InferenceSpec, ObjectMeta, ResourceSpec, Workspace
    from kaito_tpu.api.workspace import COND_RESOURCE_READY
    from kaito_tpu.controllers.runtime import Store
    from kaito_tpu.controllers.workspace import WorkspaceReconciler
    from kaito_tpu.provision import FakeCloud, KarpenterTPUProvisioner

    store = Store()
    cloud = FakeCloud(store)
    rec = WorkspaceReconciler(store, KarpenterTPUProvisioner(store))
    store.create(Workspace(
        ObjectMeta(name="bad-draft", annotations={
            "kaito-tpu.io/speculative-draft": "phi-4"}),  # vocab clash
        resource=ResourceSpec(instance_type="ct5lp-hightpu-1t"),
        inference=InferenceSpec(preset="llama-3.1-8b-instruct")))
    for _ in range(3):
        rec.reconcile_key("default", "bad-draft")
        cloud.tick()
    ws = store.get("Workspace", "default", "bad-draft")
    cond = next((c for c in ws.status.conditions
                 if c.type == COND_RESOURCE_READY), None)
    assert cond is not None and cond.status == "False"
    assert cond.reason == "PlanFailed"
    assert "vocab_size" in cond.message
    evs = store.events.events(name="bad-draft")
    assert any(e.reason == "PlanFailed" for e in evs)


def test_preset_generator_validates_draft_flag(tmp_path, capsys):
    from kaito_tpu.models import preset_generator

    cfg = {"architectures": ["LlamaForCausalLM"], "model_type": "llama",
           "vocab_size": 128256, "hidden_size": 8192,
           "num_hidden_layers": 80, "num_attention_heads": 64,
           "num_key_value_heads": 8, "intermediate_size": 28672,
           "max_position_embeddings": 131072, "rope_theta": 500000.0}
    cf = tmp_path / "cfg.json"
    cf.write_text(json.dumps(cfg))
    argv = ["--model", "meta-llama/Llama-3.3-70B-Instruct",
            "--config-file", str(cf), "--json"]
    assert preset_generator.main(argv + ["--speculative-draft",
                                         "auto"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["speculative_draft"] == "llama-3.1-8b-instruct"
    assert preset_generator.main(argv + ["--speculative-draft",
                                         "no-such"]) == 1
    assert "not in the model catalog" in capsys.readouterr().err


def test_draft_runner_rejects_incompatible_preset():
    with pytest.raises(ValueError, match="vocab_size"):
        _mk(draft="tiny-llama-real")   # 2048 vs 258
