"""The general traffic generator: deterministic per seed, and faithful to
each mix's parameters."""

import json
import math
import os
from statistics import NormalDist

import numpy as np
import pytest

from bench_testlib import BENCH
from harness import traffic

MIXES = ("chat", "rag")


def load(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def flat(arrivals):
    return [(a.due, len(a.prompt), a.max_new_tokens) for a in arrivals]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_traffic(mix):
    a = traffic.generate(load(mix), 30, 2**33 + 5, 32064)
    b = traffic.generate(load(mix), 30, 2**33 + 5, 32064)
    assert flat(a) == flat(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_the_work_not_the_tokens(mix):
    a = traffic.generate(load(mix), 30, 1, 32064)
    b = traffic.generate(load(mix), 30, 2, 32064)
    assert flat(a) == flat(b)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))


def test_lengths_follow_median_sigma_and_clip():
    spec = {"median": 512, "sigma": 0.8, "min": 64, "max": 3072}
    x = traffic.lognormal_lengths(4001, spec, np.random.default_rng(0))
    assert x.min() >= 64 and x.max() <= 3072
    assert np.median(x) == 512
    # The quartiles of an unclipped lognormal: median * exp(+-0.674 sigma).
    q1, q3 = np.percentile(x, [25, 75])
    z = NormalDist().inv_cdf(0.75)
    assert q1 == pytest.approx(512 * math.exp(-0.8 * z), rel=0.01)
    assert q3 == pytest.approx(512 * math.exp(0.8 * z), rel=0.01)
    assert (x == 3072).sum() > 0 and (x == 64).sum() > 0


@pytest.mark.parametrize("mix", MIXES)
def test_mix_lengths_within_its_clips(mix):
    m = load(mix)
    a = traffic.generate(m, 400, 3, 32064)
    p = np.array([len(x.prompt) for x in a])
    o = np.array([x.max_new_tokens for x in a])
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    assert abs(np.median(p) - m["prompt"]["median"]) <= 1
    assert abs(np.median(o) - m["output"]["median"]) <= 1


def test_arrivals_fill_the_window_at_the_rate():
    mix = load("chat")
    s = traffic.generate(mix, 50, 3, 32064)
    assert len(s) == round(mix["rate_per_s"] * 50)
    arr = [x.due for x in s]
    assert arr == sorted(arr) and arr[0] == 0.0 and arr[-1] < 50
    gaps = np.diff(arr + [50.0])
    assert gaps.sum() == pytest.approx(50)
    # Exponential gaps: the spread is about the mean.
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.35)


def test_warm_start_residents():
    chat = load("chat")
    w = traffic.warm_start(dict(chat, warm_start=6), 9, 32064)
    assert len(w) == 6
    for x in w:
        assert x.due == 0.0
        assert 1 <= x.max_new_tokens <= chat["output"]["max"]
        assert chat["prompt"]["min"] <= len(x.prompt) <= chat["prompt"]["max"]
    assert len(traffic.warm_start(chat, 9, 32064)) == chat["warm_start"]
    assert traffic.warm_start(dict(chat, warm_start=0), 9, 32064) == []


@pytest.mark.parametrize("mix,longest", [("chat", 3072 + 1024),
                                         ("rag", 8192 + 256)])
def test_longest_context_fits_the_deployment(mix, longest):
    """The longest request of a mix, plus the prefill chunk it may round
    up to, fits the max_seq_len of each cell's configuration that runs
    it."""
    from harness import spec
    assert traffic.longest_context(load(mix)) == longest
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]
                 if w["traffic"] == mix]
    assert cells
    for cell in cells:
        dep = spec.load_cell(cell).config["deployment"]
        assert longest + dep["prefill_chunk"] <= dep["max_seq_len"]
