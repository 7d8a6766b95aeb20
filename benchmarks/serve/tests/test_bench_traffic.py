"""Traffic generation: deterministic per seed, the same work on every seed,
and the distributions the mix files state."""
import json
import math
import os
import sys
from statistics import NormalDist

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from servebench import traffic  # noqa: E402

MIXES = os.path.join(os.path.dirname(HERE), "traffic")
BIG_SEED = 2 ** 33 + 12345      # past 32 bits, as the driver's are


def mix(name):
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-poisson", "short-closed",
                                  "longdoc-closed"])
def test_same_seed_same_requests(name, monkeypatch):
    a = traffic.make_plan(mix(name), BIG_SEED, 32000, 30)
    b = traffic.make_plan(mix(name), BIG_SEED, 32000, 30)
    c = traffic.make_plan(mix(name), BIG_SEED + 1, 32000, 30)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.output_lens, b.output_lens)
    assert np.array_equal(a.arrivals, b.arrivals)
    assert np.array_equal(a.prompt(5), b.prompt(5))
    # another seed: the same sizes at the same times, other token ids
    assert np.array_equal(a.prompt_lens, c.prompt_lens)
    assert np.array_equal(a.arrivals, c.arrivals)
    assert not np.array_equal(a.prompt(5), c.prompt(5))
    # another schedule: the same sizes in another order
    monkeypatch.setattr(traffic, "SCHEDULE_SEED", 1)
    d = traffic.make_plan(mix(name), BIG_SEED, 32000, 30)
    assert not np.array_equal(a.prompt_lens, d.prompt_lens)
    assert len(a.prompt(3)) == a.prompt_lens[3]
    assert a.prompt(3).min() >= 0 and a.prompt(3).max() < 32000


@pytest.mark.parametrize("name", ["chat-poisson", "short-closed",
                                  "longdoc-closed"])
def test_every_schedule_draws_the_same_sizes(name, monkeypatch):
    m = mix(name)
    strata = m["strata"]
    for schedule in (0, 1, 2, BIG_SEED):
        monkeypatch.setattr(traffic, "SCHEDULE_SEED", schedule)
        p = traffic.make_plan(m, 5, 1000, 200, max_requests=4 * strata)
        for block in range(2):
            s = slice(block * strata, (block + 1) * strata)
            assert sorted(p.prompt_lens[s]) == sorted(
                traffic.quantiles(m["prompt_len"], strata))
            assert sorted(p.output_lens[s]) == sorted(
                traffic.quantiles(m["output_len"], strata))


def test_lognormal_grid_matches_its_distribution():
    spec = {"dist": "lognormal", "median": 512, "sigma": 1.0, "min": 64,
            "max": 2048}
    q = traffic.quantiles(spec, 1000)
    assert q.min() == 64 and q.max() == 2048
    assert np.median(q) == pytest.approx(512, abs=2)
    # the unclipped middle follows the lognormal's quantiles
    z = NormalDist().inv_cdf(0.75)
    assert np.quantile(q, 0.75) == pytest.approx(512 * math.exp(z), rel=0.01)
    share_clipped_low = np.mean(q == 64)
    assert share_clipped_low == pytest.approx(
        NormalDist().cdf(math.log(64 / 512)), abs=0.002)


def test_poisson_arrivals():
    m = dict(mix("chat-poisson"), rate_per_s=5.0, strata=200)
    p = traffic.make_plan(m, BIG_SEED, 1000, 400)
    gaps = np.diff(p.arrivals)
    assert p.arrivals[0] == 0.0 and (gaps > 0).all()
    assert p.arrivals[-1] < 400
    # the midpoint grid leaves out the exponential's far tail: a little
    # under the nominal mean gap
    assert gaps.mean() == pytest.approx(1 / 5.0, rel=0.03)
    assert len(p) == pytest.approx(5.0 * 400, rel=0.03)
    # exponential: the coefficient of variation is 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.08)


def test_a_window_of_the_chat_mix_holds_one_whole_block(monkeypatch):
    m = mix("chat-poisson")
    for schedule in (0, 1, 2, BIG_SEED):
        monkeypatch.setattr(traffic, "SCHEDULE_SEED", schedule)
        p = traffic.make_plan(m, 9, 1000, 51)
        assert m["strata"] <= len(p) <= m["strata"] + 4
        head = slice(0, m["strata"])
        assert sorted(p.prompt_lens[head]) == sorted(
            traffic.quantiles(m["prompt_len"], m["strata"]))


def test_closed_loop_plan():
    m = mix("short-closed")
    p = traffic.make_plan(m, 7, 32000, 30)
    assert p.loop == "closed" and p.concurrency == 64
    assert len(p) >= 64 * 16
    assert (p.arrivals == 0).all()
    assert p.prompt_lens.min() >= 64 and p.prompt_lens.max() <= 512
    assert p.output_lens.min() >= 64 and p.output_lens.max() <= 256


def test_prompt_buckets_are_the_grid_padded():
    m = mix("chat-poisson")
    # the 25-quantile grid reaches 64..2048 and no prompt in 1537..1792
    assert traffic.prompt_buckets(m, 256) == [256, 512, 768, 1024, 1280,
                                              1536, 2048]
    # past a sliding window a prompt runs unpadded (a narrow lognormal:
    # every quantile rounds to 20 tokens)
    small = {"strata": 4, "prompt_len": {"dist": "lognormal", "median": 20,
                                         "sigma": 0.01, "min": 1,
                                         "max": 100}}
    assert traffic.prompt_buckets(small, 16, window=16) == [20]
    assert traffic.prompt_buckets(small, 16) == [32]


def test_unknown_shapes_are_refused():
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "zipf"}, 4)
    with pytest.raises(ValueError):
        traffic.make_plan(dict(mix("chat-poisson"), loop="burst"), 1, 10, 1)
