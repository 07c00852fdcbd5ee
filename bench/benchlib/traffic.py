"""The one traffic generator. A mix is a data file (bench/traffic/<name>.json)
of parameters; this module turns it and a seed into prompts, output lengths
and arrival times. Every seed gets the same multiset of sizes and of gaps
between arrivals, in another order, so that the seed changes the order of the
work and not its amount."""

import math
from statistics import NormalDist

import numpy as np


def _quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of the distribution in `spec`."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "lognormal":
        nd = NormalDist()
        qs = [(i + 0.5) / n for i in range(n)]
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q)) for q in qs]
        return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(_quantile_lengths(spec, n))


def token_ids(lens, vocab: dict, rng: np.random.Generator):
    """Random ids in [vocab.low, vocab.high) for each length."""
    return [rng.integers(vocab["low"], vocab["high"], size=int(n)).astype(np.int32)
            for n in lens]


def arrival_times(spec: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop. Poisson:
    the gaps are the mid-quantiles of the exponential distribution at
    `rate_per_s`, permuted; so every seed offers the same number of requests
    over the same span. `burst_cv` > 1 would be a later mix's parameter."""
    if spec["kind"] != "poisson":
        raise ValueError(f"arrival_times: kind {spec['kind']!r} is not an open loop")
    n = max(int(round(spec["rate_per_s"] * seconds)), 1)
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / spec["rate_per_s"]
    gaps *= (seconds / gaps.sum())  # the mid-quantile mean is a hair under 1/rate
    return np.cumsum(rng.permutation(gaps)) - gaps.mean() * 0.5
