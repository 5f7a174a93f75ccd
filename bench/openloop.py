"""Open-loop forecast requests: Poisson arrivals at a fixed rate.

The idea is the program's ``benchmarks/serve_load.py`` replay and the
history family of ``repro.forecast.serving.synthetic_request_stream``
(lognormal level walk x seasonal profile x noise), copied so that the
program cannot move it, with two changes that keep every seed on the same
work:

* arrivals: ``round(rate * seconds)`` of them, uniform over the window and
  sorted, which is a Poisson process of that rate given its count;
* lengths: the count's quantiles of the mix's clipped lognormal (paper
  Table 3), in a seeded order, so each seed serves the same set of lengths;
  the known share is exact and the ids are uniform over the fitted fleet.

Each request is timed from its due time (``arrival``), not from when the
generator got round to submitting it. ``length_bucket`` and
``shape_history`` state how the server shapes a request's history, which
the serve driver's reference follows.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    arrival: float            # seconds after the window opens
    y: np.ndarray             # (T,) float32 history, strictly positive
    category: int
    series_id: int            # -1: a series the fit never saw


def lengths(n: int, mean: float, std: float, lo: int, hi: int) -> np.ndarray:
    """The n quantiles (at (i + 1/2)/n) of a lognormal with these moments,
    clipped to [lo, hi]."""
    sigma2 = np.log(1.0 + std ** 2 / mean ** 2)
    mu = np.log(mean) - 0.5 * sigma2
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n) for i in range(n)])
    return np.clip(np.exp(mu + np.sqrt(sigma2) * z).astype(int), lo, hi)


def histories(rng, lens: np.ndarray, seasonality: int) -> List[np.ndarray]:
    """One history per length: a lognormal level walk times a seasonal
    profile times noise, all drawn in bulk."""
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    seg = np.repeat(np.arange(len(lens)), lens)
    t_in = np.arange(int(lens.sum())) - starts[seg]
    walk = np.cumsum(rng.normal(0.0, 0.002, t_in.size))
    walk -= np.repeat(np.concatenate([[0.0], walk[starts[1:] - 1]]), lens)
    base = np.log(rng.uniform(50.0, 500.0, len(lens)))[seg]
    profiles = np.exp(rng.normal(0.0, 0.08, (len(lens), seasonality)))
    seas = profiles[seg, t_in % seasonality]
    noise = np.exp(rng.normal(0.0, 0.03, t_in.size))
    y = np.maximum(np.exp(base + walk) * seas * noise, 1e-3).astype(np.float32)
    return np.split(y, starts[1:])


def make_requests(mix: dict, config: dict, *, seconds: float,
                  seed: int) -> List[Request]:
    """The window's requests, in arrival order, for one seed."""
    n = int(round(mix["rate_per_s"] * seconds))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    arrivals = np.sort(rng.uniform(0.0, seconds, n))
    lo = config["input_size"] + config["seasonality"]
    lens = rng.permutation(lengths(n, mix["length_mean"], mix["length_std"],
                                   lo, mix["length_max"]))
    n_known = int(round(mix["known_share"] * n))
    known = rng.permutation(np.arange(n) < n_known)
    ids = np.where(known, rng.integers(0, config["n_series"], n), -1)
    cats = rng.integers(0, config["n_categories"], n)
    ys = histories(rng, lens, config["seasonality"])
    return [Request(float(a), y, int(c), int(i))
            for a, y, c, i in zip(arrivals, ys, cats, ids)]


def length_bucket(n_obs: int, buckets) -> int:
    """The first bucket at or above ``n_obs``, else the largest."""
    for b in buckets:
        if n_obs <= b:
            return b
    return buckets[-1]


def shape_history(y: np.ndarray, bucket: int) -> np.ndarray:
    """Left-pad with the first value up to ``bucket``, or keep the last
    ``bucket`` values."""
    y = np.asarray(y, np.float32)
    if len(y) >= bucket:
        return y[-bucket:]
    return np.concatenate([np.full(bucket - len(y), y[0], np.float32), y])
