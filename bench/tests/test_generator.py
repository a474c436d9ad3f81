"""The generator gives every seed the same work in another order."""

import numpy as np
import pytest

from bench import generator


def plan(traffic, seed, n):
    planner = generator.Planner(traffic, seed, 64000)
    return [planner.get(i) for i in range(n)]

SEEDS = [0, 7, 2**31 + 12345, 2**33 + 1]


@pytest.mark.parametrize("name", ["doc8k_closed16", "chat_short_open"])
def test_same_seed_same_schedule(name):
    t = generator.load(name)
    a = plan(t, SEEDS[2], 25)
    b = plan(t, SEEDS[2], 25)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.new_tokens, x.gap_s) == (y.new_tokens, y.gap_s)


@pytest.mark.parametrize("name", ["doc8k_closed16", "chat_short_open"])
def test_every_seed_the_same_work(name):
    t = generator.load(name)
    runs = [plan(t, seed, 45) for seed in SEEDS]
    for reqs in runs[1:]:
        assert [(len(r.prompt), r.new_tokens, r.gap_s) for r in reqs] == \
            [(len(r.prompt), r.new_tokens, r.gap_s) for r in runs[0]]
    # the seed draws the tokens
    assert len({reqs[0].prompt.tobytes() for reqs in runs}) == len(SEEDS)


@pytest.mark.parametrize("name", ["doc8k_closed16", "chat_short_open"])
def test_every_block_holds_the_same_sizes(name):
    t = generator.load(name)
    block = t["block"]
    lens, new, gaps = generator.sizes(t, 3)
    first = (sorted(lens[:block]), sorted(new[:block]), sorted(gaps[:block]))
    for i in range(block, 3 * block, block):
        assert (sorted(lens[i:i + block]), sorted(new[i:i + block]),
                sorted(gaps[i:i + block])) == first
    assert list(lens[:block]) != list(lens[block:2 * block])  # shuffled


def test_block_follows_the_weights():
    t = generator.load("chat_short_open")
    lens, new, gaps = generator.sizes(t, 1)
    counts = {v: int(np.sum(lens == v)) for v in t["prompt_tokens"]["values"]}
    assert counts == {32: 7, 64: 6, 128: 4, 256: 3}
    assert new.min() >= 16 and new.max() <= 256
    assert abs(np.mean(gaps) - 1.0 / t["rate_per_s"]) < 0.1 / t["rate_per_s"]


def test_shared_document_starts_every_prompt():
    t = generator.load("doc8k_closed16")
    reqs = plan(t, 5, 4)
    doc = reqs[0].prompt[:8192]
    assert all(np.array_equal(r.prompt[:8192], doc) for r in reqs)
    assert len({r.prompt[8192:].tobytes() for r in reqs}) == 4
