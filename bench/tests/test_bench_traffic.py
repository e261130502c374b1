"""The traffic generators: the same seed gives the same traffic, and every
seed gives the stated mix in another order."""
import collections
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import decks
from bench.traffic.prompts import requests as prompts

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("weights,n,expected", [
    ([1, 1, 1], 30, [10, 10, 10]),
    ([0.30, 0.25, 0.20, 0.12, 0.08, 0.05], 100, [30, 25, 20, 12, 8, 5]),
    ([0.5, 0.5], 3, [2, 1]),
])
def test_apportion(weights, n, expected):
    assert decks.apportion(weights, n) == expected


def test_apportion_rejects_bad_input():
    with pytest.raises(ValueError):
        decks.apportion([0, 0], 4)


@pytest.mark.parametrize("name", ["scale_from_zero"])
def test_prompts_follow_the_length_set(name):
    m = mix(name)
    want = dict(zip(m["prompt_lengths"],
                    decks.apportion(m["length_weights"], m["deck"])))
    for seed in (1, 2**35):
        ps = list(itertools.islice(prompts(m, seed, 151936), m["deck"]))
        assert dict(collections.Counter(len(p) for p in ps)) == want
        assert all(p.dtype == np.int32 and p.min() >= 0 and p.max() < 151936
                   for p in ps)
        assert max(m["prompt_lengths"]) + m["max_new_tokens"] <= m["max_seq_len"]


def test_prompts_deterministic_per_seed():
    m = mix("scale_from_zero")
    a = list(itertools.islice(prompts(m, 99, 1000), 30))
    b = list(itertools.islice(prompts(m, 99, 1000), 30))
    c = list(itertools.islice(prompts(m, 98, 1000), 30))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(len(x) == len(y) and np.array_equal(x, y) for x, y in zip(a, c))


def test_every_burst_sends_the_same_lengths():
    m = mix("scale_from_zero")
    assert m["deck"] == m["burst"]
    want = sorted(len(p) for p in itertools.islice(prompts(m, 1, 1000), m["burst"]))
    for seed in (5, 2**40 + 5):
        gen = prompts(m, seed, 1000)
        for _ in range(4):
            got = sorted(len(next(gen)) for _ in range(m["burst"]))
            assert got == want
    assert set(want) == set(m["prompt_lengths"])
