"""The synthetic M4 fleet the fit and predict cells run on, made from a seed.

``generate`` and ``prepare`` are copies of the program's
``repro.data.synthetic_m4.generate`` and ``repro.data.pipeline.prepare``
(the equalized path, paper sec. 5.2), kept here so that a later change to
the program cannot move the data the benchmark measures with. At scale 1.0
and seed 0 they give the program's own fleets: 8572 quarterly and 35690
monthly series of T=72.

The fleet is the same for every ``--seed`` (the configuration's
``data_seed``); the seed draws the weights, the batch order and the
requests. The program's train step closes over the whole fleet, so its
compiled step carries the data as constants: a fleet drawn per seed would
compile the step anew in every run.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

CATEGORIES = ["Demographic", "Finance", "Industry", "Macro", "Micro", "Other"]

# Table 2 (paper) counts per frequency x category.
TABLE2_COUNTS = {
    "yearly": [1088, 6519, 3716, 3903, 6538, 1236],
    "quarterly": [1858, 5305, 4637, 5315, 6020, 865],
    "monthly": [5728, 10987, 10017, 10016, 10975, 277],
    "weekly": [24, 164, 6, 41, 112, 12],
    "daily": [10, 1559, 422, 127, 1476, 633],
    "hourly": [0, 0, 0, 0, 0, 414],
}

# Table 3 (paper) length stats: mean, std, min, max.
TABLE3_LEN_STATS = {
    "yearly": (25, 24, 7, 829),
    "quarterly": (84, 51, 8, 858),
    "monthly": (198, 137, 24, 2776),
    "weekly": (1009, 707, 67, 2584),
    "daily": (2343, 1756, 79, 9905),
    "hourly": (805, 127, 652, 912),
}

SEASONALITY = {"yearly": 1, "quarterly": 4, "monthly": 12, "weekly": 1,
               "daily": 1, "hourly": 24}
HORIZON = {"yearly": 6, "quarterly": 8, "monthly": 18, "weekly": 13,
           "daily": 14, "hourly": 48}

# per-category generator flavor: (noise_sigma, trend_sigma, seas_strength)
_CATEGORY_FLAVOR = {
    "Demographic": (0.015, 0.002, 0.08),
    "Finance": (0.06, 0.004, 0.05),
    "Industry": (0.03, 0.006, 0.15),
    "Macro": (0.02, 0.003, 0.10),
    "Micro": (0.04, 0.004, 0.12),
    "Other": (0.05, 0.005, 0.10),
}


@dataclasses.dataclass
class Dataset:
    """A bag of variable-length series for one frequency."""

    frequency: str
    series: List[np.ndarray]          # each (T_i,), float32, strictly > 0
    categories: np.ndarray            # (N,) int in [0, 6)
    seasonality: int
    horizon: int


@dataclasses.dataclass
class Fleet:
    """Fixed-shape arrays of one fleet (paper Eq. 7/8 splits).

    train (N, C), val_input (N, C+O), val_target (N, O), test_target (N, O),
    cats (N, 6) one-hot, categories (N,) int.
    """

    frequency: str
    train: np.ndarray
    val_input: np.ndarray
    val_target: np.ndarray
    test_target: np.ndarray
    cats: np.ndarray
    categories: np.ndarray

    @property
    def n_series(self) -> int:
        return self.train.shape[0]


def _sample_lengths(rng, n, freq):
    mean, std, lo, hi = TABLE3_LEN_STATS[freq]
    # lognormal matching the first two moments, clipped to [lo, hi]
    var = std**2
    sigma2 = np.log(1.0 + var / mean**2)
    mu = np.log(mean) - 0.5 * sigma2
    lengths = rng.lognormal(mu, np.sqrt(sigma2), n)
    return np.clip(lengths.astype(int), lo, hi)


def _gen_one(rng, length, seasonality, flavor):
    noise_sigma, trend_sigma, seas_strength = flavor
    base = rng.uniform(50.0, 5000.0)
    # log-level random walk with slowly-varying drift
    drift = rng.normal(0.0, trend_sigma)
    eps = rng.normal(0.0, trend_sigma, length).cumsum()
    log_level = np.log(base) + drift * np.arange(length) + eps
    if seasonality > 1:
        profile = rng.normal(0.0, seas_strength, seasonality)
        profile -= profile.mean()
        seas = np.exp(np.tile(profile, length // seasonality + 1)[:length])
    else:
        seas = 1.0
    noise = np.exp(rng.normal(0.0, noise_sigma, length))
    y = np.exp(log_level) * seas * noise
    return np.maximum(y, 1e-3).astype(np.float32)


def generate(frequency: str, *, scale: float = 0.01, seed: int = 0,
             min_series: int = 8) -> Dataset:
    """Synthetic M4 slice; ``scale`` multiplies the Table-2 counts."""
    rng = np.random.default_rng(seed)
    counts = [max(min_series, int(c * scale)) if c else 0
              for c in TABLE2_COUNTS[frequency]]
    m = SEASONALITY[frequency]
    series, cats = [], []
    for ci, (cat, cnt) in enumerate(zip(CATEGORIES, counts)):
        flavor = _CATEGORY_FLAVOR[cat]
        lengths = _sample_lengths(rng, cnt, frequency)
        for ln in lengths:
            series.append(_gen_one(rng, int(ln), m, flavor))
            cats.append(ci)
    return Dataset(frequency=frequency, series=series,
                   categories=np.asarray(cats, np.int32), seasonality=m,
                   horizon=HORIZON[frequency])


def prepare(ds: Dataset, *, min_length: int) -> Fleet:
    """Equalize + split (paper secs. 5.1/5.2): drop series shorter than
    ``min_length + 2 * horizon``, keep the most recent of the rest."""
    o = ds.horizon
    need = min_length + 2 * o
    keep, train, vin, vt, tt = [], [], [], [], []
    for i, y in enumerate(ds.series):
        if len(y) < need:
            continue
        tail = y[-need:]
        keep.append(i)
        train.append(tail[: need - 2 * o])
        vin.append(tail[: need - o])
        vt.append(tail[need - 2 * o: need - o])
        tt.append(tail[need - o:])
    if not keep:
        raise ValueError(f"no {ds.frequency} series met the min length {need}")
    cats_int = ds.categories[np.asarray(keep)]
    return Fleet(frequency=ds.frequency,
                 train=np.stack(train).astype(np.float32),
                 val_input=np.stack(vin).astype(np.float32),
                 val_target=np.stack(vt).astype(np.float32),
                 test_target=np.stack(tt).astype(np.float32),
                 cats=np.eye(len(CATEGORIES), dtype=np.float32)[cats_int],
                 categories=cats_int)


def build_fleet(config: dict) -> Fleet:
    """The configuration's fleet: ``generate`` at its ``data_scale`` and
    ``data_seed``, equalized to ``min_length``; it must hold ``n_series``."""
    fleet = prepare(generate(config["frequency"], scale=config["data_scale"],
                             seed=config["data_seed"]),
                    min_length=config["min_length"])
    if fleet.n_series != config["n_series"]:
        raise ValueError(f"the {config['frequency']} fleet has "
                         f"{fleet.n_series} series, the configuration "
                         f"states {config['n_series']}")
    return fleet
