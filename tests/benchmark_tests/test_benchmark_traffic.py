"""The stratified generator, the token-crediting rule and the percentiles:
pure Python, no jax."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.lib import traffic as tg  # noqa: E402

SEEDS = [0, 1, 7, 2 ** 31 + 12345]
MIXES = ["chat_steady", "code_batch"]


def mix(name):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_every_block_carries_the_same_lengths(name, seed):
    pairs = tg.length_pairs(mix(name))
    assert len(pairs) == tg.K
    gen = tg.blocks(pairs, seed, "lengths")
    drawn = [next(gen) for _ in range(5 * tg.K)]
    for b in range(5):
        assert sorted(drawn[b * tg.K:(b + 1) * tg.K]) == sorted(pairs)
    # the seed decides the order only
    other = tg.blocks(pairs, seed + 1, "lengths")
    assert [next(other) for _ in range(tg.K)] != drawn[:tg.K]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_the_file_limits(name):
    m = mix(name)
    for p, o in tg.length_pairs(m):
        assert m["prompt_tokens"]["min"] <= p <= m["prompt_tokens"]["max"]
        assert m["output_tokens"]["min"] <= o <= m["output_tokens"]["max"]
        assert p + o <= m["max_total_tokens"]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_arrival_count_for_every_seed(seed):
    rate, window = mix("chat_steady")["rate_rps"], 48.0
    due = tg.arrival_times(rate, seed, window)
    assert due == sorted(due) and due[-1] < window
    # a block of K gaps spans exactly K / rate seconds, so seeds differ
    # by less than one block's worth at any cut and agree at block ends
    assert abs(len(due) - rate * window) <= tg.K / 2
    whole = tg.arrival_times(rate, seed, 10 * tg.K / rate + 1e-9)
    assert len(whole) == 10 * tg.K
    assert sum(tg.exponential_gaps(rate)) == pytest.approx(tg.K / rate)


def test_same_seed_same_tokens():
    a = tg.token_ids(2 ** 31 + 5, 3, 50, 1000)
    assert a == tg.token_ids(2 ** 31 + 5, 3, 50, 1000)
    assert a != tg.token_ids(2 ** 31 + 5, 4, 50, 1000)
    assert all(1 <= t < 1000 for t in a)


def test_tokens_are_credited_when_they_arrive():
    recs = [
        # started before the window: prompt credited outside, two of its
        # three output tokens inside
        {"prompt_tokens": 100, "arrivals": [(0.5, 1), (1.5, 1), (2.5, 1)]},
        # first token inside the window: its prompt counts; the frame of
        # two tokens after the close does not
        {"prompt_tokens": 40, "arrivals": [(3.0, 1), (11.0, 2)]},
        # never answered
        {"prompt_tokens": 999, "arrivals": []},
    ]
    assert tg.credited_tokens(recs, 1.0, 10.0) == (40, 3)
    assert tg.credited_tokens(recs, 0.0, 20.0) == (140, 6)
    assert sum(tg.credited_by_fifth(recs, 0.0, 20.0)) == 146
    assert tg.credited_by_fifth(recs, 0.0, 20.0)[0] == 144
    assert tg.token_gaps_ms(recs, 1.0, 10.0) == pytest.approx(
        [1000.0, 1000.0])


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([5, 1, 4, 2, 3], 90, 4.6),
    (list(range(1, 101)), 95, 95.05),
    ([7], 95, 7.0),
    ([], 50, None),
])
def test_percentile_against_known_lists(values, q, want):
    got = tg.percentile(values, q)
    assert got is None if want is None else got == pytest.approx(want)
