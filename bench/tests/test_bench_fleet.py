"""The copied fleet generator and the open-loop request generator."""

import numpy as np
import pytest

from bench import fleet, openloop


@pytest.mark.parametrize("frequency,n_series,t_val", [
    ("quarterly", 8572, 80), ("monthly", 35690, 90)])
def test_seed0_fleet_sizes(frequency, n_series, t_val):
    f = fleet.prepare(fleet.generate(frequency, scale=1.0, seed=0),
                      min_length=72)
    assert f.train.shape == (n_series, 72)
    assert f.val_input.shape == (n_series, t_val)
    assert f.cats.shape == (n_series, 6)
    assert np.all(f.train > 0)


def test_same_seed_same_fleet_and_matches_program_copy():
    from repro.data.pipeline import prepare
    from repro.data.synthetic_m4 import generate

    a = fleet.prepare(fleet.generate("quarterly", scale=0.05, seed=3),
                      min_length=72)
    b = fleet.prepare(fleet.generate("quarterly", scale=0.05, seed=3),
                      min_length=72)
    p = prepare(generate("quarterly", scale=0.05, seed=3), min_length=72)
    for k in ("train", "val_input", "val_target", "test_target", "cats"):
        np.testing.assert_array_equal(getattr(a, k), getattr(b, k))
        np.testing.assert_array_equal(getattr(a, k), getattr(p, k))


def test_build_fleet_checks_the_stated_size():
    cfg = {"frequency": "quarterly", "data_scale": 0.03, "data_seed": 0,
           "min_length": 72, "n_series": 1}
    with pytest.raises(ValueError, match="states 1"):
        fleet.build_fleet(cfg)


MIX = {"rate_per_s": 500, "length_mean": 84, "length_std": 51,
       "length_max": 858, "known_share": 0.8}
CFG = {"input_size": 8, "seasonality": 4, "n_series": 8572,
       "n_categories": 6}


def test_requests_are_deterministic_in_the_seed():
    a = openloop.make_requests(MIX, CFG, seconds=2.0, seed=2**33 + 5)
    b = openloop.make_requests(MIX, CFG, seconds=2.0, seed=2**33 + 5)
    c = openloop.make_requests(MIX, CFG, seconds=2.0, seed=2**33 + 6)
    assert len(a) == len(b) == len(c) == 1000
    for x, y in zip(a, b):
        assert x.arrival == y.arrival and x.series_id == y.series_id
        np.testing.assert_array_equal(x.y, y.y)
    assert any(x.arrival != y.arrival for x, y in zip(a, c))
    # another seed serves the same set of lengths, in another order
    assert sorted(len(r.y) for r in a) == sorted(len(r.y) for r in c)


def test_requests_hit_every_bucket_and_the_known_share():
    reqs = openloop.make_requests(MIX, CFG, seconds=2.0, seed=9)
    lens = np.array([len(r.y) for r in reqs])
    assert lens.min() >= CFG["input_size"] + CFG["seasonality"]
    assert lens.max() <= MIX["length_max"]
    edges = [0, 32, 64, 128, 256, 10**9]
    for lo, hi in zip(edges[:-1], edges[1:]):
        assert np.any((lens > lo) & (lens <= hi)), (lo, hi)
    known = np.array([r.series_id >= 0 for r in reqs])
    assert known.sum() == round(0.8 * len(reqs))
    ids = np.array([r.series_id for r in reqs])[known]
    assert ids.min() >= 0 and ids.max() < CFG["n_series"]
    arrivals = np.array([r.arrival for r in reqs])
    assert np.all(np.diff(arrivals) >= 0)
    assert arrivals.min() >= 0 and arrivals.max() < 2.0
    assert all(np.all(r.y > 0) and r.y.dtype == np.float32 for r in reqs)
