"""The traffic generators: seeded only by the seed, sizes inside their
clips, and every seed given the same work."""

import json
import os
from collections import Counter

import pytest

import chipbench_setup  # noqa: F401  (the benchmark and src on the path)
import registry

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG_SEED = 2**31 + 12345


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def source(name, seed, seconds=30.0, max_batch=16, vocab=32064):
    p = mix(name)
    return registry.generator(p["kind"]).make(
        p, seed=seed, vocab=vocab, seconds=seconds, max_batch=max_batch)


def first_turns(src):
    return [(t, s.id, s.prompt, s.max_new) for t, s in src.arrivals()]


@pytest.mark.parametrize("name", ["chat-shared", "chat-unshared",
                                  "longgen-closed"])
def test_same_seed_same_requests(name):
    a, b = source(name, BIG_SEED), source(name, BIG_SEED)
    assert first_turns(a) == first_turns(b)
    assert [(s.id, s.prompt, s.max_new) for s in a.setup_requests()] == \
        [(s.id, s.prompt, s.max_new) for s in b.setup_requests()]
    assert a.prompt_lengths() == b.prompt_lengths()
    c = source(name, BIG_SEED + 1)
    assert (first_turns(a) != first_turns(c)
            or a.setup_requests()[0].prompt != c.setup_requests()[0].prompt)


def _turn_sizes(src):
    """(user length, output length) of every turn the draws hold."""
    return Counter(zip(src.user_lens, src.out_lens)), \
        Counter(src.user_lens), Counter(src.out_lens)


def test_chat_lengths_inside_clips_and_same_work_every_seed():
    p = mix("chat-shared")
    for seed in (1, BIG_SEED):
        src = source("chat-shared", seed)
        u, o = p["user_len"], p["output_len"]
        assert all(u["min"] <= x <= u["max"] for x in src.user_lens)
        assert all(o["min"] <= x <= o["max"] for x in src.out_lens)
        assert all(p["turns"][0] <= t <= p["turns"][1] for t in src.turns)
        for t, s in src.arrivals():
            assert len(s.prompt) + s.out_len <= p["max_context"]
            assert len(s.prompt) >= min(p["system_prompts"]) + u["min"]
            assert 0 <= t < 30.0
    a, b = source("chat-shared", 1), source("chat-shared", BIG_SEED)
    assert Counter(a.user_lens) == Counter(b.user_lens)
    assert Counter(a.out_lens) == Counter(b.out_lens)
    assert Counter(a.turns) == Counter(b.turns)
    assert Counter(a.system_of) == Counter(b.system_of)
    assert sorted(a.thinks) == sorted(b.thinks)
    # the same sessions at the same times; only the tokens differ
    assert a.starts == b.starts and a.turns == b.turns
    assert a.arrivals()[0][1].prompt != b.arrivals()[0][1].prompt
    # the same prompt lengths for every seed: their operations compile
    # once, in the first run of a checkout
    assert a.prompt_lengths() == b.prompt_lengths()


def test_chat_turns_build_on_history():
    src = source("chat-shared", 7)
    _, first = src.arrivals()[0]
    sys_len = len(src.system[src.system_of[first.meta["session"]]])
    assert first.prompt[:sys_len] == src.system[
        src.system_of[first.meta["session"]]]
    served = list(range(first.out_len))
    nxt = src.on_finish(first, served, 5.0)
    if src.turns[first.meta["session"]] > 1 and nxt:
        (due, spec), = nxt
        assert due > 5.0
        assert spec.prompt[:len(first.prompt)] == first.prompt
        assert list(spec.prompt[len(first.prompt):][:len(served)]) == served
    assert set(src.prompt_lengths()) >= {len(s.prompt)
                                         for _, s in src.arrivals()}


def test_unshared_is_fresh_tokens_of_the_same_lengths():
    a, b = source("chat-shared", 3), source("chat-unshared", 3)
    ta, tb = a.arrivals(), b.arrivals()
    assert [(t, len(s.prompt), s.max_new) for t, s in ta] == \
        [(t, len(s.prompt), s.max_new) for t, s in tb]
    assert b.prefixes() == [] and a.prefixes()
    sys0 = a.system[0]
    assert not any(s.prompt[:len(sys0)] == sys0 for _, s in tb)


def test_longgen_clients_staggered_and_inside_clips():
    p = mix("longgen-closed")
    src = source("longgen-closed", BIG_SEED, max_batch=16)
    first = src.setup_requests()
    assert len(first) == 16 and src.arrivals() == []
    left = sorted(s.max_new for s in first)
    assert left[0] < left[-1] / 4, "first requests are not staggered"
    for s in first:
        assert len(s.prompt) + s.out_len <= p["max_total"]
        assert s.max_new >= 1
    (due, nxt), = src.on_finish(first[0], [0] * first[0].out_len, 2.5)
    assert due == 2.5
    assert p["prompt_len"][0] <= len(nxt.prompt) <= p["prompt_len"][1]
    assert p["output_len"][0] <= nxt.out_len <= p["output_len"][1]
    other = source("longgen-closed", 99, max_batch=16)
    assert sorted(src.prompt_lens) == sorted(other.prompt_lens)
    assert sorted(src.out_lens) == sorted(other.out_lens)
    assert src.prompt_lengths() == other.prompt_lengths()
    assert [len(s.prompt) for s in first] == \
        [len(s.prompt) for s in other.setup_requests()]


def test_longgen_prompt_lengths_cover_every_request():
    p = mix("longgen-closed")
    src = source("longgen-closed", 5, max_batch=16)
    sent = []
    for spec in src.setup_requests():
        chain = 1
        while True:
            nxt = src.on_finish(spec, [0] * spec.out_len, 0.0)
            if not nxt:
                break
            (_, spec), = nxt
            sent.append(len(spec.prompt))
            chain += 1
        assert chain == p["requests_per_client"]
    assert set(src.prompt_lengths()) == set(sent)
