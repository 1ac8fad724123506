"""The traffic generator: determinism, the same work under every seed,
and the length and rate parameters of a mix file."""
import glob
import json
import os

import numpy as np
import pytest

from bench.arrivals import Mix, arrival_times, gamma_intervals, lengths

CHAT = {"kind": "open", "rate_per_s": 5.0, "cv": 1.0, "warm_in_s": 10.0,
        "base_seed": 11,
        "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                   "min": 32, "max": 1280},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 16, "max": 512}}
BACKLOG = {"kind": "backlog", "n_requests": 300, "base_seed": 12,
           "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                      "min": 32, "max": 512},
           "output": {"dist": "uniform", "min": 256, "max": 1024}}


def _gen(params, seed, seconds=40.0, vocab=1000):
    return Mix("m", params["kind"], params).generate(seed, seconds, vocab)


@pytest.mark.parametrize("params", [CHAT, BACKLOG])
def test_same_seed_same_requests(params):
    a, b = _gen(params, 2**31 + 77), _gen(params, 2**31 + 77)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["arrival"] == y["arrival"]
        assert x["prompt_len"] == y["prompt_len"]
        assert x["max_new"] == y["max_new"]
        assert np.array_equal(x["tokens"], y["tokens"])


def test_seeds_permute_one_backlog():
    a, b = _gen(BACKLOG, 1), _gen(BACKLOG, 2)
    assert len(a) == len(b) == 300
    key = lambda rs: sorted((r["prompt_len"], r["max_new"]) for r in rs)
    assert key(a) == key(b)
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    assert all(r["arrival"] == 0.0 for r in a)


def test_seeds_bring_the_same_requests_into_the_window():
    def window(seed):
        rs = _gen(CHAT, seed, seconds=40.0)
        w = [r for r in rs if CHAT["warm_in_s"] <= r["arrival"]
             < CHAT["warm_in_s"] + 40.0]
        gaps = np.diff([r["arrival"] for r in w])
        return (len(rs), sorted((r["prompt_len"], r["max_new"]) for r in w),
                sorted(np.round(gaps, 9)), w)
    a, b = window(2**31 + 1), window(2**31 + 2)
    assert a[0] == b[0] == 50 + 200
    assert len(a[1]) == 200 and a[1] == b[1]
    assert [r["prompt_len"] for r in a[3]] != [r["prompt_len"] for r in b[3]]
    assert a[3][0]["arrival"] == CHAT["warm_in_s"]


def test_open_mix_lengths_rate_and_cut():
    reqs = _gen(CHAT, 5, seconds=400.0)
    arr = np.array([r["arrival"] for r in reqs])
    assert arr[0] == 0.0 and np.all(np.diff(arr) >= 0)
    assert arr[-1] < CHAT["warm_in_s"] + 400.0
    rate = len(reqs) / (CHAT["warm_in_s"] + 400.0)
    assert abs(rate - 5.0) < 0.5
    p = np.array([r["prompt_len"] for r in reqs])
    o = np.array([r["max_new"] for r in reqs])
    assert p.min() >= 32 and p.max() <= 1280
    assert o.min() >= 16 and o.max() <= 512
    assert 300 < np.median(p) < 480 and 100 < np.median(o) < 160
    assert all(len(r["tokens"]) == r["prompt_len"] for r in reqs)
    assert all(r["tokens"].max() < 1000 for r in reqs)


def test_gamma_gaps_mean_and_cv():
    rng = np.random.default_rng(0)
    g = gamma_intervals(200_000, 0.25, 3.0, rng)
    assert abs(g.mean() - 0.25) < 0.01
    assert abs(g.std() / g.mean() - 3.0) < 0.1
    assert np.array_equal(arrival_times(np.array([1.0, 2.0, 3.0])),
                          np.array([0.0, 1.0, 3.0]))


def test_uniform_and_lognormal_lengths():
    rng = np.random.default_rng(0)
    u = lengths({"dist": "uniform", "min": 256, "max": 1024}, 50_000, rng)
    assert u.min() == 256 and u.max() == 1024
    ln = lengths({"dist": "lognormal", "median": 100, "sigma": 0.5,
                  "min": 1, "max": 10**6}, 50_000, rng)
    assert abs(np.median(ln) - 100) < 3
    with pytest.raises(ValueError):
        lengths({"dist": "pareto", "min": 1, "max": 2}, 3, rng)


MIXES = sorted(glob.glob(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic", "*.json")))


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_committed_mix_draws_its_stated_lengths(path):
    name = os.path.basename(path)[:-5]
    mix = Mix.load(path, name)
    reqs = mix.generate(2**31 + 5, 400.0, 1000)
    for key, field in (("prompt", "prompt_len"), ("output", "max_new")):
        spec = mix.params[key]
        x = np.array([r[field] for r in reqs])
        assert spec["min"] <= x.min() and x.max() <= spec["max"]
        if spec["dist"] == "lognormal" and spec["median"] < spec["max"]:
            assert abs(np.median(x) / spec["median"] - 1) < 0.15, key
    # a request fits the slot of every configuration its cells serve it on
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    files = {c["name"]: c["file"] for c in spec["configs"]}
    for w in spec["workloads"]:
        if w["traffic"] == name:
            with open(os.path.join(root, files[w["config"]])) as f:
                conf = json.load(f)
            s_max = max(conf["lut"]["table"].values())
            assert (mix.max_prompt + mix.max_output + s_max
                    <= conf["serving"]["cache_len"])
