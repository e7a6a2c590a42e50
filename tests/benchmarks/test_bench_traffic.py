"""Traffic files give the same requests for the same seed, different ones
for another, and the same work for every seed."""

import glob
import json
import os

import numpy as np
import pytest

from zkbench import cells, traffic

MIXES = sorted(
    p for p in glob.glob(os.path.join(cells.BENCH_DIR, "traffic", "*.json"))
    if json.load(open(p)).get("kind") == "requests"
)
BIG = 2**31 + 12345  # the driver's seeds pass 32 signed bits


def schedule(mix, seed):
    if mix["arrivals"]["process"] == "closed":
        per_client = traffic.closed_loop(mix, seed, 3, 50257)
        return [r for client in per_client for r in client]
    return traffic.open_loop(mix, seed, 20.0, 50257)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_same_seed_same_requests_other_seed_other_order(path):
    mix = json.load(open(path))
    a, b, c = schedule(mix, BIG), schedule(mix, BIG), schedule(mix, BIG + 1)
    assert len(a) == len(b) == len(c) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)
    assert any(
        len(x.prompt) != len(z.prompt) or not np.array_equal(x.prompt, z.prompt)
        for x, z in zip(a, c)
    )
    # every seed draws the same work, in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in c)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_lengths_keep_to_the_mix(path):
    mix = json.load(open(path))
    prefix = mix["prompt"]["shared_prefix_tokens"]
    body = mix["prompt"]["body"]
    reqs = schedule(mix, 3)
    for r in reqs:
        assert prefix + body["min"] <= len(r.prompt) <= prefix + body["max"]
        assert mix["output"]["min"] <= r.max_new_tokens <= mix["output"]["max"]
        assert r.prompt.dtype == np.int32 and r.prompt.min() >= 0 and r.prompt.max() < 50257
    if prefix:
        assert all(np.array_equal(r.prompt[:prefix], reqs[0].prompt[:prefix]) for r in reqs)
        assert not np.array_equal(reqs[0].prompt[prefix:prefix + 16], reqs[1].prompt[prefix:prefix + 16])


def test_open_loop_count_and_span():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 12.5},
           "prompt": {"shared_prefix_tokens": 4, "body": {"dist": "fixed", "value": 8}},
           "output": {"dist": "uniform", "min": 2, "max": 6}}
    reqs = traffic.open_loop(mix, 9, 20.0, 100)
    assert len(reqs) == 250
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 20.0


def test_pareto_quantiles_by_hand():
    # F(x) = (1 - (lo/x)^a) / (1 - (lo/hi)^a); a = 1, lo = 10, hi = 40:
    # u = 0.5 -> x = 10 / (1 - 0.5 * 0.75) = 16
    q = traffic.dist_quantiles({"dist": "pareto", "min": 10, "max": 40, "alpha": 1.0}, 1)
    assert q.tolist() == [16]
    q = traffic.dist_quantiles({"dist": "uniform", "min": 0, "max": 10}, 5)
    assert q.tolist() == [1, 3, 5, 7, 9]
    gaps = traffic.exponential_gaps(1000)
    assert abs(gaps.mean() - 1.0) < 0.01


def test_warmup_requests_cover_short_and_long():
    mix = json.load(open(MIXES[0]))
    warm = traffic.warmup_requests(mix, BIG, 50257)
    lens = sorted(len(r.prompt) for r in warm)
    prefix = mix["prompt"]["shared_prefix_tokens"]
    assert 1 <= len(warm) <= max(1, mix.get("warmup_requests", 3))
    assert lens[0] >= prefix + mix["prompt"]["body"]["min"]


@pytest.mark.parametrize("path", [p for p in MIXES if json.load(open(p))["arrivals"]["process"] != "closed"], ids=os.path.basename)
def test_open_loop_gaps_are_one_set_in_the_seeds_order(path):
    """The arrivals follow ``--seed``: every seed gets the same set of
    gaps between arrivals (so as many short ones), in another order."""
    mix = json.load(open(path))
    seconds = 20.0

    def gaps(seed):
        due = [r.due_s for r in traffic.open_loop(mix, seed, seconds, 50257)]
        return np.diff([0.0] + due + [seconds])

    a, b, c = gaps(BIG), gaps(BIG), gaps(BIG + 1)
    assert np.array_equal(a, b)
    assert not np.allclose(a, c)
    assert np.allclose(np.sort(a), np.sort(c))
    assert "schedule_seed" not in mix["arrivals"]
