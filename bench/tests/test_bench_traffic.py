"""The traffic generator (bench/traffic.py) and the mixes under
bench/traffic/."""
from __future__ import annotations

import glob
import json
import os

import numpy as np
import pytest

from bench import traffic as TR

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json")))


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_mix_is_deterministic_for_a_seed_and_keeps_its_clips(path):
    mix = load(path)
    a = TR.generate(mix, 2**31 + 5, 30, 1000)
    b = TR.generate(mix, 2**31 + 5, 30, 1000)
    c = TR.generate(mix, 7, 30, 1000)
    assert [(r.due_s, r.n_out, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.n_out, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    for r in a:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.n_out <= mix["output"]["max"]
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 1000
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_every_seed_offers_the_same_work_in_another_order(path):
    mix = load(path)
    block = mix["block"]
    a = TR.generate(mix, 1, 30, 1000)
    b = TR.generate(mix, 2, 30, 1000)
    n = len(a) // block * block
    assert n, "a window holds at least one whole block"
    assert sorted(len(r.prompt) for r in a[:n]) == \
        sorted(len(r.prompt) for r in b[:n])
    assert sorted(r.n_out for r in a[:n]) == sorted(r.n_out for r in b[:n])
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("rate,seconds", [(6.0, 30), (9.5, 40), (2.0, 10)])
def test_poisson_schedule_has_its_rate(rate, seconds):
    mix = {"arrivals": {"kind": "poisson", "rate_per_s": rate},
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "output": {"dist": "uniform", "min": 2, "max": 3}, "block": 32}
    reqs = TR.generate(mix, 3, seconds, 100)
    assert len(reqs) == round(rate * seconds)
    due = np.array([r.due_s for r in reqs])
    assert 0 < due[0] and due[-1] < seconds
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.05)
    # exponential gaps: the standard deviation is about the mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.25)


def test_unknown_arrival_kind_is_an_error():
    mix = {"arrivals": {"kind": "offline"},
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "output": {"dist": "uniform", "min": 2, "max": 3}}
    with pytest.raises(ValueError):
        TR.generate(mix, 3, 5, 100)


def test_quantiles_follow_the_distribution():
    q = TR.quantiles({"dist": "lognormal", "median": 100, "sigma": 0.5,
                      "min": 1, "max": 10_000}, 101)
    assert q[50] == 100 and q[0] < 100 < q[-1]
    u = TR.quantiles({"dist": "uniform", "min": 16, "max": 64}, 49)
    assert u.min() == 16 and u.max() == 64
    with pytest.raises(ValueError):
        TR.quantiles({"dist": "zipf", "min": 1, "max": 2}, 3)
