"""The one traffic generator: seeded, same work for every seed, stated
ranges and shares."""
from __future__ import annotations

import collections

import numpy as np
import pytest

import bc_tiny  # noqa: F401  (puts the repository root on the path)
from benchmarks.chip import common, generate

BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("mix", ["chat", "shared-prefix"])
def test_open_loop_is_a_function_of_the_seed(mix):
    t = common.traffic(mix)
    a = generate.requests(t, 1000, BIG_SEED, 30.0, 2048)
    b = generate.requests(t, 1000, BIG_SEED, 30.0, 2048)
    c = generate.requests(t, 1000, BIG_SEED + 1, 30.0, 2048)
    assert [(x.arrival, x.max_new, x.prompt.tolist()) for x in a] == \
        [(x.arrival, x.max_new, x.prompt.tolist()) for x in b]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    # the same work for every seed: lengths and arrivals, in one order
    assert [(x.arrival, x.max_new, len(x.prompt), x.prefix) for x in a] == \
        [(x.arrival, x.max_new, len(x.prompt), x.prefix) for x in c]


@pytest.mark.parametrize("seconds", [10.0, 30.0, 51.0])
def test_chat_lengths_rate_and_window(seconds):
    t = common.traffic("chat")
    items = generate.requests(t, 1000, 7, seconds, 2048)
    assert len(items) == round(t["rate_per_s"] * seconds)
    arr = [x.arrival for x in items]
    assert arr[0] == 0.0 and max(arr) < seconds
    assert all(b >= a for a, b in zip(arr, arr[1:]))
    plens = [len(x.prompt) for x in items]
    assert min(plens) >= t["prompt"]["min"]
    assert max(plens) <= t["prompt"]["max"]
    assert all(t["output"]["min"] <= x.max_new <= t["output"]["max"]
               for x in items)
    assert abs(np.median(plens) - t["prompt"]["median"]) \
        < 0.15 * t["prompt"]["median"]
    assert all(0 <= v < 1000 for x in items for v in x.prompt)


def test_shared_prefix_zipf_shares():
    t = common.traffic("shared-prefix")
    items = generate.requests(t, 1000, 3, 40.0, 2048)
    sp = t["shared_prefix"]
    counts = collections.Counter(x.prefix for x in items)
    assert list(generate.zipf_counts(len(items), sp["prompts"],
                                     sp["zipf_s"])) == \
        [counts[k] for k in range(sp["prompts"])]
    firsts = {}
    for x in items:
        head = tuple(x.prompt[:sp["tokens"]].tolist())
        assert firsts.setdefault(x.prefix, head) == head
        suffix = len(x.prompt) - sp["tokens"]
        assert t["suffix"]["min"] <= suffix <= t["suffix"]["max"]
    assert len(set(firsts.values())) == sp["prompts"]


def test_zipf_counts_add_up_and_fall():
    c = generate.zipf_counts(101, 4, 1.2)
    assert c.sum() == 101 and list(c) == sorted(c, reverse=True)
    w = 1 / np.arange(1, 5) ** 1.2
    assert np.all(np.abs(c - 101 * w / w.sum()) < 1)


def test_closed_backlog_fills_the_cache():
    t = common.traffic("long-decode")
    items = generate.requests(t, 64000, BIG_SEED, 30.0, 4096)
    assert len(items) == t["initial"] + t["queued"]
    assert all(x.arrival == 0.0 for x in items)
    for x in items:
        p = len(x.prompt)
        assert t["prompt"]["min"] <= p <= t["prompt"]["max"]
        assert min(t["output_min"], 4096 - p) <= x.max_new <= 4096 - p


def test_train_mix_sends_no_requests():
    with pytest.raises(ValueError):
        generate.requests(common.traffic("b4x1024"), 100, 1, 10.0, 128)
