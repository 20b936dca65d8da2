"""The traffic generator: every seed gets the same set of work in
another order, and the same seed gets the same requests."""

import os

import pytest

import trafficgen
from manifest import Manifest
from paths import KBENCH

REHEARSAL = os.path.join(KBENCH, "testdata", "rehearsal", "BENCHMARK.json")


def _mix(name):
    """``batch`` is the benchmark's own mix; the open-loop mixes are
    the rehearsal's until a cell proves them on the chip."""
    if name == "batch":
        return Manifest().traffic(name)
    return Manifest(REHEARSAL).traffic(name)


@pytest.mark.parametrize("name", ["chat", "rag"])
def test_open_loop_seeds_share_the_set_of_sizes_and_gaps(name):
    mix = _mix(name)
    runs = [trafficgen.schedule(mix, seed=s, vocab=5000, seconds=30,
                                rate_rps=2.0) for s in (1, 2, 2 ** 31 + 12345)]
    shapes = [sorted((len(r["prompt_ids"]), r["max_tokens"]) for r in reqs)
              for reqs in runs]
    assert shapes[0] == shapes[1] == shapes[2]
    assert all(len(reqs) == 60 for reqs in runs)
    for reqs in runs:
        due = [r["due_s"] for r in reqs]
        assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30
    assert [r["prompt_ids"] for r in runs[0]] != [r["prompt_ids"] for r in runs[1]]


def test_same_seed_same_requests():
    mix = _mix("chat")
    a = trafficgen.schedule(mix, seed=9, vocab=5000, seconds=10, rate_rps=3)
    b = trafficgen.schedule(mix, seed=9, vocab=5000, seconds=10, rate_rps=3)
    assert a == b


def test_chat_prompts_share_system_prompts():
    mix = _mix("chat")
    reqs = trafficgen.schedule(mix, seed=4, vocab=5000, seconds=30, rate_rps=4)
    n = mix["prompt"]["shared_prefix"]["tokens"]
    heads = {tuple(r["prompt_ids"][:n]) for r in reqs}
    assert 1 < len(heads) <= mix["prompt"]["shared_prefix"]["count"]
    lo = n + mix["prompt"]["unique"]["min"]
    hi = n + mix["prompt"]["unique"]["max"]
    assert all(lo <= len(r["prompt_ids"]) <= hi for r in reqs)


def test_rag_documents_are_asked_three_times():
    mix = _mix("rag")
    reqs = trafficgen.schedule(mix, seed=4, vocab=50000, seconds=30, rate_rps=1)
    q = mix["repeat"]["question_tokens"]
    lo, hi = mix["prompt"]["unique"]["min"], mix["prompt"]["unique"]["max"]
    docs = {}
    for r in reqs:
        docs.setdefault(tuple(r["prompt_ids"][:-q]), []).append(r)
    assert sorted(len(v) for v in docs.values()) == [3] * 10
    assert all(lo <= len(r["prompt_ids"]) <= hi for r in reqs)
    tails = {tuple(r["prompt_ids"][-q:]) for r in reqs}
    assert len(tails) == len(reqs)


def test_closed_loop_has_no_due_times_and_no_sharing():
    mix = _mix("batch")
    reqs = trafficgen.schedule(mix, seed=4, vocab=50000, seconds=30,
                               count=mix["count"])
    assert len(reqs) == mix["count"]
    assert all(r["due_s"] == 0.0 for r in reqs)
    assert all(128 <= len(r["prompt_ids"]) <= 512
               and 128 <= r["max_tokens"] <= 384 for r in reqs)


def test_closed_loop_seeds_give_every_stretch_the_same_work():
    """A window starts only the first part of the list: every copy of
    the mix's ``distinct`` shapes is the same set, in the seed's order."""
    mix = _mix("batch")
    k = mix["distinct"]
    a, b = ([(len(r["prompt_ids"]), r["max_tokens"]) for r in
             trafficgen.schedule(mix, seed=s, vocab=50000, seconds=30,
                                 count=mix["count"])]
            for s in (3, 2 ** 31 + 5))
    assert mix["count"] % k == 0 and len(set(a[:k])) > k // 2
    for i in range(0, mix["count"], k):
        assert sorted(a[i:i + k]) == sorted(a[:k]) == sorted(b[i:i + k])
    assert a[:k] != b[:k] and a[:k] != a[k:2 * k]


def test_words_and_ids_round_trip():
    ids = [0, 17, 200063]
    assert trafficgen.ids_of(trafficgen.words(ids)) == ids
    with pytest.raises(ValueError):
        trafficgen.ids_of("w1 hello")
