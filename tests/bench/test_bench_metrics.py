"""Host-clock metric readers on hand-made requests."""
import math
import os

import numpy as np
import pytest

from harness import loop, metrics

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")


def ctx(reqs, profiled=None):
    return metrics.Context(conf={}, traffic={}, seconds=30.0, t0=0.0,
                           reqs=reqs, steps=[], setup_s=0.0,
                           profiled=profiled)


def req(rid, due, first=None):
    r = loop.Req(rid=rid, due=due, prompt=np.zeros(1, np.int32), gen=1)
    if first is not None:
        r.stamps.append(first)
    return r


def first_token(c):
    return metrics.reader(os.path.join(BENCH, "metrics"),
                          "first_token_p95_ms")(c)


def test_first_token_p95_is_the_nearest_rank_tail():
    reqs = [req(i, float(i), float(i) + (i + 1) / 1e3) for i in range(20)]
    assert first_token(ctx(reqs)) == pytest.approx(19.0)


def test_a_request_with_no_first_token_is_infinitely_late():
    reqs = [req(i, float(i), float(i) + 0.001) for i in range(19)]
    reqs.append(req(19, 19.0))
    assert first_token(ctx(reqs)) == pytest.approx(1.0)
    reqs.append(req(20, 20.0))
    assert math.isinf(first_token(ctx(reqs)))


def test_first_token_leaves_out_waits_that_overlap_the_profiler():
    reqs = [req(i, float(i), float(i) + 0.001) for i in range(10)]
    reqs[5].stamps[0] = 7.0  # a 2 s wait across the profiled span
    assert first_token(ctx(reqs)) == pytest.approx(2000.0)
    assert first_token(ctx(reqs, profiled=(6.0, 6.5))) == \
        pytest.approx(1.0)
