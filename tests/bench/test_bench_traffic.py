"""The traffic generator: deterministic per seed, the same work for every
seed, and an open-loop rate that matches its file."""

import json
import os

import numpy as np
import pytest

from bench.harness import traffic

TRAFFIC = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                       "traffic")


def mixes():
    return sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))


def load(name):
    return json.load(open(os.path.join(TRAFFIC, name + ".json")))


@pytest.mark.parametrize("name", [m for m in mixes()
                                  if "prompt" in load(m)])
def test_schedule_is_deterministic_per_seed(name):
    mix = load(name)
    a = traffic.schedule(mix, 2**31 + 11, 1000)
    b = traffic.schedule(mix, 2**31 + 11, 1000)
    c = traffic.schedule(mix, 7, 1000)
    assert a == b
    assert a != c


@pytest.mark.parametrize("name", [m for m in mixes()
                                  if "prompt" in load(m)])
def test_every_seed_gets_the_same_work(name):
    mix = load(name)
    a = traffic.schedule(mix, 1, 1000)
    b = traffic.schedule(mix, 2, 1000)
    for key in ("gen",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi for r in a)
    lo, hi = mix["answer"]["min"], mix["answer"]["max"]
    assert all(lo <= r["gen"] <= hi for r in a)
    assert all(0 <= t < 1000 for r in a for t in r["prompt"])


def test_open_loop_mean_rate_matches_the_file():
    mix = {"loop": "open", "rate": 12.5, "requests": 4000,
           "prompt": {"dist": "lognormal", "median": 128, "sigma": 0.9,
                      "min": 32, "max": 1024},
           "answer": {"dist": "uniform", "min": 16, "max": 32}}
    reqs = traffic.schedule(mix, 3, 100)
    assert traffic.mean_rate(reqs) == pytest.approx(12.5, rel=0.01)
    dues = [r["due"] for r in reqs]
    assert dues == sorted(dues)
    for name in mixes():
        m = load(name)
        if m.get("loop") == "open":
            got = traffic.mean_rate(traffic.schedule(m, 5, 100))
            assert got == pytest.approx(m["rate"], rel=0.02)


def test_lognormal_lengths_hit_the_median_and_clip():
    spec = {"dist": "lognormal", "median": 128, "sigma": 0.9, "min": 32,
            "max": 1024}
    x = traffic.quantile_lengths(spec, 1001)
    assert np.median(x) == 128
    assert x.min() >= 32 and x.max() <= 1024
