"""One general open-loop traffic generator, driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) states the mix:

    {"arrivals": {"process": "gamma", "cv": 2.0, "rate_per_s": 8.0},
     "prompt_len": {"median": 64, "sigma": 0.6, "min": 1, "max": 512},
     "gen_len": {"median": 48, "sigma": 0.6, "min": 1, "max": 256},
     ...}

Inter-arrival gaps follow a gamma distribution of the stated coefficient of
variation (1.0 is Poisson, above 1 is bursty); lengths follow a lognormal
of the stated median and log-space sigma, rounded and clipped.

Every seed gets the same multiset of gaps and lengths: ``n = rate * seconds``
requests, each quantity taken at the ``n`` mid-quantiles ``(i + 0.5) / n``
of its distribution.  The seed only permutes them (prompt lengths and
generation lengths independently) and draws the token ids.  So two seeds
offer the same total work over the same span, in a different order, and a
run-to-run spread measures the system rather than the luck of the draw.
The gaps are permuted by the seed too, so each seed's bursts fall at other
times.
"""
from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist
from typing import List

import numpy as np
from scipy import stats


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due_s: float  # offset from the window's start
    prompt: np.ndarray  # int32 token ids
    gen: int  # tokens to generate


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["arrivals"]["rate_per_s"] * seconds)))


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` lognormal mid-quantile lengths of ``spec``, ascending."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    z = np.array([NormalDist().inv_cdf(u) for u in _mid_quantiles(n)])
    raw = np.rint(np.exp(mu + sigma * z)).astype(np.int64)
    return np.clip(raw, spec["min"], spec["max"])


def gaps(spec: dict, n: int, seconds: float) -> np.ndarray:
    """The ``n`` gamma mid-quantile gaps, scaled to sum to ``seconds``."""
    cv = spec["cv"]
    if spec["process"] != "gamma" or cv <= 0:
        raise ValueError(f"unknown arrival process {spec!r}")
    shape = 1.0 / (cv * cv)
    g = stats.gamma.ppf(_mid_quantiles(n), shape)
    return g * (seconds / g.sum())


def schedule(traffic: dict, seconds: float, seed: int,
             vocab_size: int) -> List[Arrival]:
    """The run's requests in due order; due times lie in ``[0, seconds)``."""
    n = n_requests(traffic, seconds)
    rng = np.random.default_rng(seed)
    prompt = rng.permutation(lengths(traffic["prompt_len"], n))
    gen = rng.permutation(lengths(traffic["gen_len"], n))
    g = rng.permutation(gaps(traffic["arrivals"], n, seconds))
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab_size, size=int(prompt[i]))
        out.append(Arrival(rid=i, due_s=float(due[i]),
                           prompt=toks.astype(np.int32), gen=int(gen[i])))
    return out
