"""The traffic generator: determinism by seed, stratified sizes, ranges,
conversations that fit max_len."""
import collections

import pytest

import chipbench_tiny  # noqa: F401
from bench import spec, traffic

BIG_SEED = 2 ** 31 + 12345


def _sizes(items):
    return sorted((len(i.prompt), i.max_new) for i in items)


@pytest.mark.parametrize("mix_name", ["chat", "longprompt"])
def test_same_seed_same_traffic(mix_name):
    mix = spec.traffic(mix_name)
    if mix["loop"] == "open":
        a = traffic.open_loop(mix, BIG_SEED, 30, 32000)
        b = traffic.open_loop(mix, BIG_SEED, 30, 32000)
    else:
        a = sum(traffic.closed_loop(mix, BIG_SEED, 32000, 8), [])
        b = sum(traffic.closed_loop(mix, BIG_SEED, 32000, 8), [])
    assert [(i.user, i.conv, i.prompt, i.max_new, i.send, i.sampled)
            for i in a] == [(i.user, i.conv, i.prompt, i.max_new, i.send,
                             i.sampled) for i in b]


def test_open_loop_seeds_offer_the_same_work():
    mix = spec.traffic("chat")
    a = traffic.open_loop(mix, 1, 30, 32000)
    b = traffic.open_loop(mix, 2, 30, 32000)
    n = round(mix["rate_per_s"] * 30)
    # Every seed draws the same stratified sizes; the last arrivals may
    # fall past the window's end.
    assert abs(len(a) - n) <= 2 and abs(len(b) - n) <= 2
    full = traffic.stratified(mix["prompt"], n)
    for items in (a, b):
        got = collections.Counter(len(i.prompt) for i in items)
        assert not got - collections.Counter(full.tolist())
        assert all(0 <= i.send < 30 for i in items)
        assert [i.send for i in items] == sorted(i.send for i in items)
    assert [i.prompt for i in a] != [i.prompt for i in b]


def test_closed_loop_blocks_hold_the_same_sizes():
    mix = spec.traffic("longprompt")
    s = mix["strata"]
    lists = traffic.closed_loop(mix, 5, 32000, 8) + \
        traffic.closed_loop(mix, 6, 32000, 8)
    first = _sizes(lists[0][:s])
    for items in lists:
        for k in range(0, len(items) - s + 1, s):
            assert sorted(len(i.prompt) for i in items[k:k + s]) == \
                sorted(p for p, _ in first)
            assert sorted(i.max_new for i in items[k:k + s]) == \
                sorted(o for _, o in first)


@pytest.mark.parametrize("mix_name", ["chat", "longprompt"])
def test_sizes_in_range_and_conversations_fit(mix_name):
    mix = spec.traffic(mix_name)
    if mix["loop"] == "open":
        items = traffic.open_loop(mix, 9, 60, 32000)
    else:
        items = sum(traffic.closed_loop(mix, 9, 32000, 8), [])
    for it in items:
        assert mix["prompt"]["min"] <= len(it.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= it.max_new <= mix["output"]["max"]
        assert it.start_pos + it.positions <= mix["max_len"]
        assert all(1 <= t < 32000 for t in it.prompt)
    by_conv = collections.defaultdict(list)
    for it in sorted(items, key=lambda i: (i.client or 0, i.index)):
        by_conv[it.conv].append(it)
    for conv, turns in by_conv.items():
        pos = 0
        for t in turns:
            assert t.start_pos == pos
            pos += t.positions
        assert not any(t.last_turn for t in turns[:-1])
    if not mix["returning"]:
        assert all(len(t) == 1 and t[0].last_turn
                   for t in by_conv.values())


def test_chat_users_return_with_zipf_skew():
    mix = spec.traffic("chat")
    items = traffic.open_loop(mix, 3, 200, 32000)
    counts = collections.Counter(i.user for i in items)
    assert counts["u0"] > counts["u1"] > counts[f"u{mix['users'] - 1}"]
    convs = collections.Counter(i.conv for i in items)
    assert max(convs.values()) > 1           # users continue a session


def test_stratified_quantiles():
    d = {"dist": "uniform", "min": 10, "max": 20}
    assert traffic.stratified(d, 5).tolist() == [11, 13, 15, 17, 19]
    d = {"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 8,
         "max": 128}
    v = traffic.stratified(d, 101)
    assert v[50] == 24 and v.min() >= 8 and v.max() <= 128
