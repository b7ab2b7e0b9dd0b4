"""Every traffic generator gives the same schedule for the same seed, and
every seed the same work in another order."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

import loadgen
from conftest import BENCH

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")) if f.endswith(".json"))


def _mix(name):
    return json.load(open(os.path.join(BENCH, "traffic", name + ".json")))


@pytest.mark.parametrize("name", MIXES)
def test_requests_repeat_for_a_seed(name):
    mix = _mix(name)
    a = loadgen.requests(mix, 2**31 + 9, 51, 49155)
    b = loadgen.requests(mix, 2**31 + 9, 51, 49155)
    assert a == b
    c = loadgen.requests(mix, 2**31 + 10, 51, 49155)
    assert a != c
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    # The lead and the window each hold one multiset of sizes and of gaps
    # between arrivals, in another order, all of the window's inside it.
    for part in (lambda r: r.due_s < 0, lambda r: r.due_s >= 0):
        pa, pc = [r for r in a if part(r)], [r for r in c if part(r)]
        assert len(pa) == len(pc)
        for field in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
            assert sorted(map(field, pa)) == sorted(map(field, pc))
        gaps_a, gaps_c = np.diff([r.due_s for r in pa]), np.diff([r.due_s for r in pc])
        assert len(set(np.round(gaps_a, 9)) - set(np.round(gaps_c, 9))) <= 1
    assert max(r.due_s for r in a + c) < 51


def test_quantiles_stay_in_range():
    dist = {"dist": "lognormal", "median": 384, "sigma": 0.6, "min": 128, "max": 1024}
    q = loadgen.quantiles(dist, 200)
    assert q.min() >= 128 and q.max() <= 1024 and abs(np.median(q) - 384) <= 2
