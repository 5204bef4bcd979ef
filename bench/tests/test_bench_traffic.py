"""The request generator: deterministic per seed, the same work for every
seed, and the stated medians, clips, rates and burst sizes."""

import numpy as np
import pytest

from bench import harness, traffic

CHAT = {"arrivals": {"kind": "poisson", "rate": 5.0},
        "prompt": {"median": 192, "sigma": 1.0, "min": 16, "max": 1024},
        "output": {"median": 96, "sigma": 0.8, "min": 8, "max": 512}}
DOCQA = {"arrivals": {"kind": "bursts", "rate": 2.0, "burst_mean": 3,
                      "burst_cap": 8},
         "prompt": {"median": 1024, "sigma": 0.5, "min": 256, "max": 2048},
         "output": {"median": 32, "sigma": 0.6, "min": 8, "max": 128}}
BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("mix", [CHAT, DOCQA], ids=["poisson", "bursts"])
def test_same_seed_same_requests(mix):
    a = traffic.requests(mix, BIG_SEED, 60.0, 1000)
    b = traffic.requests(mix, BIG_SEED, 60.0, 1000)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    c = traffic.requests(mix, BIG_SEED + 1, 60.0, 1000)
    assert [r["due"] for r in a] != [r["due"] for r in c]


@pytest.mark.parametrize("mix", [CHAT, DOCQA], ids=["poisson", "bursts"])
def test_every_seed_gets_the_same_work(mix):
    a = traffic.requests(mix, 1, 60.0, 1000)
    b = traffic.requests(mix, 2, 60.0, 1000)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new_tokens"] for r in a) == \
        sorted(r["max_new_tokens"] for r in b)
    assert a[-1]["due"] == pytest.approx(b[-1]["due"])


@pytest.mark.parametrize("mix", [CHAT, DOCQA], ids=["poisson", "bursts"])
def test_every_stretch_of_requests_holds_the_same_work(mix):
    """Seeds differ only in the order within blocks of BLOCK requests, so
    a window holds the same work whatever the seed."""
    a = traffic.requests(mix, 11, 60.0, 1000)
    b = traffic.requests(mix, 12, 60.0, 1000)
    for lo in range(0, len(a) - traffic.BLOCK, traffic.BLOCK):
        blk = slice(lo, lo + traffic.BLOCK)
        for key in ("prompt", "max_new_tokens"):
            def size(r):
                return len(r["prompt"]) if key == "prompt" else r[key]
            assert sorted(map(size, a[blk])) == sorted(map(size, b[blk]))
    # and an even spread: the long prompts are not bunched in one stretch
    half = len(a) // 2
    long_ = np.percentile([len(r["prompt"]) for r in a], 80)
    first = sum(len(r["prompt"]) > long_ for r in a[:half])
    second = sum(len(r["prompt"]) > long_ for r in a[half:])
    assert abs(first - second) <= 2


@pytest.mark.parametrize("which,spec", [
    ("prompt", CHAT["prompt"]), ("output", CHAT["output"]),
    ("prompt", DOCQA["prompt"]), ("output", DOCQA["output"])])
def test_lengths_have_the_stated_median_and_clips(which, spec):
    v = traffic.lognormal_lengths(1001, spec)
    assert np.median(v) == spec["median"]
    assert v.min() == spec["min"] or v.min() > spec["min"]
    assert v.min() >= spec["min"] and v.max() <= spec["max"]
    # the tails are really clipped at these sample sizes
    assert (v == spec["max"]).any() or spec["sigma"] < 0.7


def test_poisson_rate_is_over_the_whole_horizon():
    due = traffic.arrival_times(CHAT["arrivals"], 40.0,
                                np.random.default_rng(3))
    assert len(due) == 200
    assert due[-1] == pytest.approx(40.0)
    assert np.all(np.diff(due) >= 0)


def test_bursts_are_geometric_with_mean_three_capped_at_eight():
    sizes = traffic.geometric_sizes(1000, 3.0, 8)
    assert sizes.min() == 1 and sizes.max() == 8
    assert 2.6 < sizes.mean() < 3.0          # the cap trims the tail
    due = traffic.arrival_times(DOCQA["arrivals"], 60.0,
                                np.random.default_rng(5))
    starts, counts = np.unique(due, return_counts=True)
    assert counts.max() <= 8
    assert len(due) / 60.0 == pytest.approx(2.0, rel=0.15)


def _pool_mixes():
    bench = harness.benchmark()
    names = sorted({w["traffic"] for w in bench["workloads"]})
    mixes = [(n, harness.read_json(f"{harness.BENCH}/traffic/{n}.json"))
             for n in names]
    return [(n, m) for n, m in mixes if "pool" in m]


@pytest.mark.parametrize("name,mix", _pool_mixes(),
                         ids=[n for n, _ in _pool_mixes()])
def test_pool_fits_the_mix_at_a_power_of_two_capacity(name, mix):
    """The TPU compiler aborts the bucketed prefill where the cache's
    capacity (max_prompt_len + max_new_tokens) is not a power of two
    (scatter_emitter check failure at 1152, 1536 and 2176 positions; 1024
    and 2048 compile), so a pool holds a power of two, and every request
    of the mix fits it."""
    pool = mix["pool"]
    cap = pool["max_prompt_len"] + pool["max_new_tokens"]
    assert cap & (cap - 1) == 0, f"{name}: capacity {cap}"
    assert mix["prompt"]["max"] <= pool["max_prompt_len"]
    assert mix["output"]["max"] <= pool["max_new_tokens"]


def test_sub_seed_takes_large_and_negative_seeds():
    s = {harness.sub_seed(x, 1) for x in (0, 1, 2 ** 31 + 5, 2 ** 40, -3)}
    assert len(s) == 5 and all(0 <= v < 2 ** 31 for v in s)
