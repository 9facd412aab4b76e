"""The traffic generator: seeded, the same work for every seed, and the
stated moments."""
import collections
import json
import math
import os

import numpy as np
import pytest

from harness import traffic

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")


def mix(name):
    return traffic.load(os.path.join(BENCH, "traffic", f"{name}.json"))


@pytest.mark.parametrize("name", ["chat", "prompt"])
def test_same_seed_same_schedule(name):
    a = traffic.schedule(mix(name), 30, 2**31 + 12345, 4096)
    b = traffic.schedule(mix(name), 30, 2**31 + 12345, 4096)
    assert [(x.due_s, x.gen, x.prompt.tolist()) for x in a] == \
        [(x.due_s, x.gen, x.prompt.tolist()) for x in b]


@pytest.mark.parametrize("name", ["chat", "prompt"])
def test_seeds_share_the_work_in_another_order(name):
    """Every seed offers the same lengths and gaps; the order differs, and
    so do the high bits of a seed beyond 32."""
    m = mix(name)
    a = traffic.schedule(m, 30, 5, 4096)
    b = traffic.schedule(m, 30, 5 + 2**33, 4096)
    assert sorted(x.prompt.size for x in a) == sorted(x.prompt.size for x in b)
    assert sorted(x.gen for x in a) == sorted(x.gen for x in b)
    assert [x.prompt.size for x in a] != [x.prompt.size for x in b]
    assert len(a) == len(b) == round(m["arrivals"]["rate_per_s"] * 30)
    assert all(0 <= x.due_s < 30 for x in a + b)
    # the gaps between due times are drawn from one multiset of 30 s
    pool = collections.Counter(
        np.round(traffic.gaps(m["arrivals"], len(a), 30), 6))
    for s in (a, b):
        got = collections.Counter(np.round(np.diff([x.due_s for x in s]), 6))
        assert not got - pool


@pytest.mark.parametrize("name", ["chat", "prompt"])
def test_moments_are_as_stated(name):
    m = mix(name)
    n = 4000
    for key in ("prompt_len", "gen_len"):
        spec = m[key]
        ls = traffic.lengths(spec, n)
        assert ls.min() >= spec["min"] and ls.max() <= spec["max"]
        assert abs(np.median(ls) - spec["median"]) <= 1
        inside = ls[(ls > spec["min"]) & (ls < spec["max"])]
        # log-space spread of the unclipped middle: sigma within rounding
        q16, q84 = np.quantile(np.log(ls), [0.1587, 0.8413])
        assert abs((q84 - q16) / 2 - spec["sigma"]) < 0.05, (q16, q84)
        assert inside.size > 0.9 * n
    g = traffic.gaps(m["arrivals"], n, 100.0)
    assert math.isclose(g.sum(), 100.0, rel_tol=1e-9)
    cv = g.std() / g.mean()
    assert abs(cv - m["arrivals"]["cv"]) < 0.1 * m["arrivals"]["cv"]


def test_traffic_files_name_themselves():
    for path in os.listdir(os.path.join(BENCH, "traffic")):
        with open(os.path.join(BENCH, "traffic", path)) as f:
            assert json.load(f)["name"] + ".json" == path


@pytest.mark.parametrize("name", ["chat", "prompt"])
def test_each_seed_has_its_own_arrival_timeline(name):
    """The seed permutes the gaps too, so bursts fall at other times."""
    a = traffic.schedule(mix(name), 30, 11, 4096)
    b = traffic.schedule(mix(name), 30, 12 + 2**32, 4096)
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert a[0].due_s == b[0].due_s == 0.0
