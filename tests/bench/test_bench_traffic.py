"""The traffic generator: a fixed set of lengths from the mix's lognormal,
dealt into balanced groups, ordered and filled with tokens by the seed."""
import json
import os

import pytest

from bench.traffic import Traffic, deal, quantile_lengths, spread_order

MIXES = os.path.join(os.path.dirname(__file__), "..", "..", "bench", "mixes")


def mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def test_quantiles_of_the_chat_lengths():
    m = mix("handoff")
    assert quantile_lengths(m["prompt"], 8) == [55, 105, 157, 219, 300, 417,
                                                622, 1187]
    assert quantile_lengths(m["answer"], 8) == [38, 63, 87, 113, 145, 189,
                                                260, 437]
    assert quantile_lengths(dict(m["prompt"], max=600), 8)[-2:] == [600, 600]


@pytest.mark.parametrize("n, group, want", [
    (8, 2, [[0, 7], [1, 6], [2, 5], [3, 4]]),
    (6, 3, [[0, 3, 4], [1, 2, 5]]),
    (4, 4, [[0, 1, 2, 3]]),
])
def test_deal(n, group, want):
    assert deal(n, group) == want


def test_the_block_reaches_both_clips():
    t = Traffic(mix("handoff"), 49152, 1)
    assert (min(t.prompts), max(t.prompts)) == (30, 1536)
    assert (min(t.answers), max(t.answers)) == (23, 512)
    assert t.max_total() == 2048


@pytest.mark.parametrize("n, want", [
    (1, [0]),
    (8, [0, 4, 2, 6, 1, 5, 3, 7]),
    (6, [0, 4, 2, 1, 5, 3]),
])
def test_spread_order(n, want):
    assert spread_order(n) == want


def test_every_prefix_of_the_cycle_spans_the_ranks():
    t = Traffic(mix("serve"), 49152, 1)
    heavy = [sum(t.prompts[r] for r in t.ranks[i]) for i in t.order]
    mean = sum(heavy) / len(heavy)
    for k in (4, 8):
        assert abs(sum(heavy[:k]) / k - mean) < 0.2 * mean


def test_deal_refuses_a_partial_group():
    with pytest.raises(ValueError):
        deal(7, 2)


def lengths(seed, n_groups=8):
    t = Traffic(mix("serve"), 49152, seed)
    gs = t.groups()
    return [[(len(m.payload["prompt"]), m.payload["max_new_tokens"])
             for m in next(gs)] for _ in range(n_groups)]


@pytest.mark.parametrize("seed", [0, 2**31 + 3, 2**32 + 12345])
def test_every_seed_serves_the_same_set(seed):
    base = lengths(1)
    got = lengths(seed)
    assert [sorted(p for p, _ in g) for g in got] == [
        sorted(p for p, _ in g) for g in base]
    assert [sorted(a for _, a in g) for g in got] == [
        sorted(a for _, a in g) for g in base]


def test_a_seed_repeats_and_ids_count_up():
    a, b = Traffic(mix("serve"), 100, 7), Traffic(mix("serve"), 100, 7)
    ma = a.warmup() + next(a.groups())
    mb = b.warmup() + next(b.groups())
    assert [m.payload for m in ma] == [m.payload for m in mb]
    assert [m.msg_id for m in ma] == [0, 1, 2, 3]
    assert all(0 <= t < 100 for m in ma for t in m.payload["prompt"])
    assert a.prompt_len == {m.msg_id: len(m.payload["prompt"]) for m in ma}
