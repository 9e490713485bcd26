"""The generator: deterministic per seed, bounded, the same work for every
seed in another order."""
import os
from collections import Counter

import numpy as np
import pytest

import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = ["conv", "decode"]
HERE = os.path.dirname(os.path.abspath(__file__))


def mix(name):
    path = os.path.join(BENCH, "traffic", name + ".json")
    if not os.path.exists(path):               # a test-only mix
        path = os.path.join(HERE, name + ".json")
    return traffic.load(path)


def plan(name, seed, seconds=30.0, split=None, vocab=64000):
    return traffic.schedule(mix(name), split or {"A": 0.5, "B": 0.5}, vocab,
                            seed, seconds)


def key(p):
    return (p.due_s, p.rung, p.max_new, p.prefix, p.prompt.tobytes())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a, b = plan(name, 2**31 + 77), plan(name, 2**31 + 77)
    assert [key(p) for p in a] == [key(p) for p in b]


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_schedule(name):
    a, b = plan(name, 5), plan(name, 2**33 + 5)   # differ above 32 bits only
    assert [key(p) for p in a] != [key(p) for p in b]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    """Arrivals, and the set of sizes, prefix picks and rungs within each
    shuffle block, do not depend on the seed; their order and the tokens
    do."""
    def shapes(ps):
        return [(len(p.prompt), p.max_new, p.prefix, p.rung) for p in ps]
    a, b = plan(name, 1), plan(name, 2**31 + 99991)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    k = mix(name).get("shuffle_block", len(a))
    for i in range(0, len(a), k):
        assert sorted(shapes(a[i:i + k])) == sorted(shapes(b[i:i + k]))
    assert shapes(a) != shapes(b)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_bounds_hold(name):
    m = mix(name)
    for p in plan(name, 3):
        lo = m["prompt"]["min"]
        hi = max(m["prompt"]["max"], m.get("prefix", {}).get("length", 0)
                 + m.get("prefix", {}).get("min_tail", 0))
        assert lo <= len(p.prompt) <= hi
        assert m["output"]["min"] <= p.max_new <= m["output"]["max"]
        assert 0 <= p.prompt.min() and p.prompt.max() < 64000
        assert p.due_s <= 30.0


def test_shared_prefixes_and_rung_split():
    m = mix("tiny_chat")
    ps = plan("tiny_chat", 11, seconds=100.0)
    assert len(ps) == round(m["arrival"]["rate_per_s"] * 100)
    shared = [p for p in ps if p.prefix >= 0]
    assert abs(len(shared) / len(ps) - m["prefix"]["share"]) < 0.15
    heads = {}
    for p in shared:
        h = heads.setdefault(p.prefix, p.prompt[:m["prefix"]["length"]])
        assert np.array_equal(h, p.prompt[:m["prefix"]["length"]])
    c = Counter(p.rung for p in ps)
    assert abs(c["A"] - c["B"]) <= 1
    # Zipf: the first system prompt is the most used
    use = Counter(p.prefix for p in shared)
    assert use[0] == max(use.values())


def test_backlog_is_due_at_once():
    ps = plan("decode", 4, split={"S": 1.0}, vocab=102400)
    assert all(p.due_s == 0.0 for p in ps)
    assert len(ps) == mix("decode")["arrival"]["count"]
    assert max(p.prompt.max() for p in ps) < 102400
