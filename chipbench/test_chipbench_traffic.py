"""The traffic generator: deterministic per seed, the stated shapes."""

import os
import statistics

import numpy as np
import pytest

from chipbench import harness, traffic


def mix(name):
    return harness.load_json(os.path.join(harness.HERE, "traffic",
                                          f"{name}.json"))


CHAT = mix("chat-poisson")
PRETRAIN = mix("pretrain-4k")
BIG = 2**33 + 12345  # seeds may exceed 32 bits


def test_open_loop_is_deterministic_per_seed():
    a = traffic.open_loop(CHAT, BIG, 30.0, 151936)
    b = traffic.open_loop(CHAT, BIG, 30.0, 151936)
    c = traffic.open_loop(CHAT, BIG - 2**33, 30.0, 151936)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["prompt"], c[0]["prompt"])


def test_open_loop_rate_and_window():
    seconds = 40.0
    reqs = traffic.open_loop(CHAT, 7, seconds, 151936)
    assert len(reqs) == round(CHAT["rate_per_s"] * seconds)
    due = [r["due"] for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < seconds
    # Poisson arrivals: gaps are exponential (coefficient of variation ~1).
    gaps = np.diff([0.0] + due)
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_open_loop_lengths_follow_the_mix():
    reqs = traffic.open_loop(CHAT, 11, 200.0, 151936)
    p = [len(r["prompt"]) for r in reqs]
    o = [r["max_new_tokens"] for r in reqs]
    assert min(p) >= CHAT["prompt"]["min"] and max(p) <= CHAT["prompt"]["max"]
    assert min(o) >= CHAT["output"]["min"] and max(o) <= CHAT["output"]["max"]
    assert abs(statistics.median(p) - CHAT["prompt"]["median"]) <= 0.05 * 1024
    assert abs(statistics.median(o) - CHAT["output"]["median"]) <= 0.05 * 192
    assert 1250 < statistics.mean(p) < 1500  # log-normal, sigma 0.8, clipped
    assert 200 < statistics.mean(o) < 260
    greedy = [r["temperature"] == 0 for r in reqs]
    assert greedy[::2] == [True] * len(greedy[::2])
    assert not any(greedy[1::2])
    assert all(1 <= t < 151936 for r in reqs for t in r["prompt"][:8])


def test_every_seed_gets_the_same_work():
    """Lengths and arrival times are the same for every seed; the token
    ids are the seed's own."""
    a = traffic.open_loop(CHAT, 1, 30.0, 151936)
    b = traffic.open_loop(CHAT, BIG, 30.0, 151936)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and len(x["prompt"]) == len(y["prompt"])
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert not np.array_equal(x["prompt"], y["prompt"])
    # Shuffled, not sorted by length.
    o = [r["max_new_tokens"] for r in a]
    assert o != sorted(o) and o != sorted(o, reverse=True)


@pytest.mark.parametrize("rows", [1, 2])
def test_token_rows(rows):
    it = traffic.token_rows(PRETRAIN, BIG, rows, 151936)
    b0, b1 = next(it), next(it)
    again = next(traffic.token_rows(PRETRAIN, BIG, rows, 151936))
    assert b0["input_ids"].shape == (rows, PRETRAIN["seq_len"])
    assert np.array_equal(b0["input_ids"], again["input_ids"])
    assert np.array_equal(b0["input_ids"][:, 1:], b0["labels"][:, :-1])
    assert not np.array_equal(b0["input_ids"], b1["input_ids"])
    if rows > 1:
        assert not np.array_equal(b0["input_ids"][0], b0["input_ids"][1])
