"""Packed multi-sequence prefill (docs/prefill.md): the token-budget
pack scheduler must produce greedy output BIT-IDENTICAL to the serial
round-robin scheduler, while spending strictly fewer prefill
dispatches on concurrent traffic.

Covers the matrix the scheduler actually branches on: mixed prompt
lengths (segment packing + batch-axis grouping), a chunked long prompt
straddling pack rounds, int8 KV (packed scale-fold path), a
grammar-constrained slot inside a pack (fused first-token sampling),
QoS priority ordering of the pack pick, and abort mid-pack.
"""

import json

import pytest

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams

BASE = dict(model="tiny-llama-test", max_model_len=512, page_size=16,
            max_num_seqs=4, dtype="float32", kv_dtype="float32",
            prefill_buckets=(32, 64, 128, 256), seed=0,
            enable_prefix_caching=False)

# mixed lengths: two short (batch/segment-packable), one mid, one just
# over a bucket boundary
PROMPTS = [
    [(3 * i) % 1900 + 2 for i in range(9)],
    [(5 * i) % 1900 + 2 for i in range(21)],
    [(7 * i) % 1900 + 2 for i in range(34)],
    [(11 * i) % 1900 + 2 for i in range(65)],
]


def _greedy(n, **kw):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True,
                          **kw)


def _drive(eng, reqs, max_steps=3000):
    for _ in range(max_steps):
        eng.step()
        if all(r.finish_reason for r in reqs):
            break
    return [list(r.output_tokens) for r in reqs]


def _mk(pack, **kw):
    return InferenceEngine(EngineConfig(**{**BASE, **kw},
                                        prefill_pack=pack))


def _run_concurrent(eng, prompts, n=8):
    reqs = [eng.submit(list(p), _greedy(n)) for p in prompts]
    return _drive(eng, reqs)


# ---------------------------------------------------------------------------
# bit-equivalence: packed vs serial
# ---------------------------------------------------------------------------

def test_pack_matches_serial_mixed_lengths():
    serial = _mk(1)
    ref = _run_concurrent(serial, PROMPTS)
    packed = _mk(0)
    out = _run_concurrent(packed, PROMPTS)
    assert out == ref
    # packing actually engaged: fewer prefill dispatches for the same
    # prompt tokens, and the histogram saw a pack of >= 2
    assert (packed.counters["prefill_steps_total"]
            < serial.counters["prefill_steps_total"])
    assert (packed.counters["prefill_tokens_total"]
            == serial.counters["prefill_tokens_total"])
    assert packed.prefill_pack_hist._total > 0
    assert packed.prefill_pack_hist._sum > packed.prefill_pack_hist._total


@pytest.mark.parametrize("chunk,turns", [(512, (1, 0)), (16, (0, 3))])
def test_pack_one_is_the_serial_scheduler(chunk, turns):
    """prefill_pack=1 is the serial scheduler: one one-row dispatch a
    prompt, the default packing's outputs, and as many prompts a turn
    as its chunk budget holds whole (docs/prefill.md) — both at 512,
    one at 16, where the second prompt (21 tokens) is chunked."""
    a = _mk(1, max_prefill_tokens=chunk)
    ra = _run_concurrent(a, PROMPTS[:2])
    b = _mk(0, max_prefill_tokens=512)
    rb = _run_concurrent(b, PROMPTS[:2])
    assert ra == rb
    assert b.counters["prefill_steps_total"] == 1
    assert a.counters["prefill_steps_total"] == (2 if chunk == 512 else 3)
    assert (a.counters["prefill_turns_multi_total"],
            a.counters["prefill_turns_single_total"]) == turns
    assert a.prefill_pack_hist._sum == a.counters["prefill_steps_total"]
    # the pack path counts no turns: the two counters are the serial
    # scheduler's
    assert b.counters["prefill_turns_multi_total"] \
        + b.counters["prefill_turns_single_total"] == 0


def test_long_prompt_straddles_pack_rounds():
    """A chunked long prompt shares the budget with short prompts: its
    chunks land in different pack rounds and the joint output still
    matches serial exactly."""
    prompts = [[(13 * i) % 1800 + 2 for i in range(200)]] + PROMPTS[:2]
    serial = _mk(1, max_prefill_tokens=48)
    ref = _run_concurrent(serial, prompts)
    packed = _mk(0, max_prefill_tokens=48)
    out = _run_concurrent(packed, prompts)
    assert out == ref
    # really chunked: the 200-token prompt needs >= 5 rounds at 48
    assert packed.counters["prefill_steps_total"] >= 5


def test_pack_matches_serial_int8_kv():
    serial = _mk(1, kv_dtype="int8")
    ref = _run_concurrent(serial, PROMPTS)
    packed = _mk(0, kv_dtype="int8")
    out = _run_concurrent(packed, PROMPTS)
    assert out == ref
    assert (packed.counters["prefill_steps_total"]
            < serial.counters["prefill_steps_total"])


def test_grammar_slot_in_pack():
    """A grammar-constrained request packed with unconstrained ones:
    the fused first-token sampler applies the mask row only to the
    constrained slot and the constrained stream stays valid JSON."""
    from kaito_tpu.engine.grammar import GrammarSpec, canonical_schema

    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "tag": {"type": "string", "maxLength": 4}},
              "required": ["ok", "tag"],
              "additionalProperties": False}

    def run(pack):
        eng = _mk(pack)
        g = eng.grammar_cache.get(
            GrammarSpec("json_schema", canonical_schema(schema)),
            eng.tokenizer)
        rc = eng.submit([10, 20, 30], SamplingParams(
            max_tokens=60, temperature=0.0, grammar=g))
        others = [eng.submit(list(p), _greedy(8)) for p in PROMPTS[:2]]
        outs = _drive(eng, [rc] + others)
        text = eng.tokenizer.decode(outs[0])
        obj = json.loads(text)
        assert set(obj) == {"ok", "tag"}
        return outs

    assert run(0) == run(1)


def test_qos_priority_orders_the_pack():
    """With a budget that fits ONE prompt per round, the guaranteed
    tenant's prompt dispatches first even when submitted last."""
    qos = json.dumps({
        "classes": {"guaranteed": {"priority": 100, "weight": 8},
                    "best-effort": {"priority": 0, "weight": 1}},
        "tenants": {"acme": "guaranteed"},
        "default_class": "best-effort",
    })
    eng = _mk(0, qos_config=qos, max_prefill_tokens=32)
    be = eng.submit([(3 * i) % 900 + 2 for i in range(30)], _greedy(4),
                    tenant="free")
    gt = eng.submit([(5 * i) % 900 + 2 for i in range(30)], _greedy(4),
                    tenant="acme")
    _drive(eng, [be, gt])
    assert be.finish_reason and gt.finish_reason
    assert gt.first_token_time <= be.first_token_time


def test_abort_mid_pack():
    """Aborting one request between pack rounds must not disturb the
    survivors' output."""
    prompts = [[(13 * i) % 1800 + 2 for i in range(200)]] + PROMPTS[:2]
    serial = _mk(1, max_prefill_tokens=48)
    sref = [serial.submit(list(p), _greedy(8)) for p in prompts]
    serial.abort(sref[0])
    ref = _drive(serial, sref[1:])

    packed = _mk(0, max_prefill_tokens=48)
    reqs = [packed.submit(list(p), _greedy(8)) for p in prompts]
    packed.step()                       # first pack round dispatched
    packed.abort(reqs[0])               # long prompt dies mid-prefill
    out = _drive(packed, reqs[1:])
    assert out == ref
    # the aborted request retired at its first post-abort emit instead
    # of running its full budget (same contract as the serial path)
    assert reqs[0].finish_reason is not None
    assert len(reqs[0].output_tokens) < 8


# ---------------------------------------------------------------------------
# observability: histogram exposition round-trips through promtext
# ---------------------------------------------------------------------------

def test_pack_metrics_promtext_roundtrip():
    eng = _mk(0)
    _run_concurrent(eng, PROMPTS[:3], n=4)
    for hist, name in ((eng.prefill_pack_hist,
                        "kaito:engine_prefill_pack_size"),
                       (eng.prefill_wait_hist,
                        "kaito:prefill_queue_wait_seconds")):
        lines = list(hist.collect())
        assert f"# TYPE {name} histogram" in lines
        count = sum_ = None
        for ln in lines:
            if ln.startswith(f"{name}_count"):
                count = float(ln.split()[-1])
            elif ln.startswith(f"{name}_sum"):
                sum_ = float(ln.split()[-1])
        assert count is not None and count > 0
        assert sum_ is not None and sum_ >= 0.0
    # the step timeline annotated the packed rounds
    packs = [e for e in eng.timeline.records()
             if e.get("prefill_pack")]
    assert packs and max(e["prefill_pack"] for e in packs) >= 2
