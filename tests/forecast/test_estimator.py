"""ESRNNForecaster tests: golden equivalence, round-trip, quantiles, eval."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.esrnn import esrnn_forecast, esrnn_init, esrnn_loss
from repro.forecast import ESRNNForecaster, get_smoke_spec
from repro.forecast.estimator import NotFittedError


@pytest.fixture(scope="module")
def fitted():
    f = ESRNNForecaster(get_smoke_spec("esrnn-quarterly", data_seed=3))
    f.fit(n_steps=6)
    return f


def test_golden_matches_pure_loss_bit_for_bit(fitted):
    """The estimator's loss IS the pure esrnn_loss on a fixed seed.

    (The bit-for-bit goldens against the *pre-refactor* inline loss /
    forecast math live in tests/core/test_forward.py.)
    """
    f = fitted
    y = jnp.asarray(f.data_.train)
    c = jnp.asarray(f.data_.cats)
    new = f.loss(y, c)
    old = esrnn_loss(f.config, f.params_, y, c)
    assert float(new) == float(old)  # bit-for-bit, no tolerance
    # and from a freshly-initialized fixed seed, independently of fit()
    g = ESRNNForecaster(f.spec)
    g.init_params(f.n_series_, seed=123)
    old_init = esrnn_init(jax.random.PRNGKey(123), f.config, f.n_series_)
    assert float(g.loss(y, c)) == float(
        esrnn_loss(f.config, old_init, y, c))


def test_golden_matches_pure_forecast_bit_for_bit(fitted):
    f = fitted
    np.testing.assert_array_equal(
        f.predict(),
        np.asarray(esrnn_forecast(
            f.config, f.params_,
            jnp.asarray(f.data_.train), jnp.asarray(f.data_.cats))))


def test_fit_save_load_predict_equivalence(fitted, tmp_path):
    f = fitted
    fc = f.predict()
    f.save(str(tmp_path))
    g = ESRNNForecaster.load(str(tmp_path))
    assert g.spec == f.spec
    assert g.n_series_ == f.n_series_
    np.testing.assert_array_equal(fc, g.predict(f.data_.train, f.data_.cats))
    # fitted categories survive the round trip: predict(y) without explicit
    # cats must NOT silently degrade to zero one-hots on a loaded estimator
    np.testing.assert_array_equal(fc, g.predict(f.data_.train))


def test_save_can_share_dir_with_trainer_checkpoints(tmp_path):
    """out_dir == ckpt_dir must not clobber the trainer's resume state."""
    d = str(tmp_path)
    f = ESRNNForecaster(get_smoke_spec("esrnn-quarterly", data_seed=3))
    f.fit(n_steps=3, ckpt_dir=d)
    f.save(d)
    g = ESRNNForecaster(f.spec)
    g.fit(n_steps=3, ckpt_dir=d)  # resume must still restore (params, opt)
    assert g.history_["loss"] == []


def test_predict_series_subset(fitted):
    f = fitted
    full = f.predict()
    sub = f.predict(f.data_.train[2:5], f.data_.cats[2:5], series_idx=[2, 3, 4])
    np.testing.assert_array_equal(full[2:5], sub)


def test_predict_defaults_to_fitted_categories(fitted):
    """predict(y) without cats must use the fitted one-hots, not zeros."""
    f = fitted
    np.testing.assert_array_equal(
        f.predict(f.data_.val_input),
        f.predict(f.data_.val_input, f.data_.cats))
    np.testing.assert_array_equal(
        f.predict(f.data_.train[2:5], series_idx=[2, 3, 4]),
        f.predict(f.data_.train[2:5], f.data_.cats[2:5], series_idx=[2, 3, 4]))


def test_predict_shape_mismatch_raises(fitted):
    with pytest.raises(ValueError, match="per-series table"):
        fitted.predict(fitted.data_.train[:3], fitted.data_.cats[:3])


def test_predict_quantiles_monotone_and_median_is_point(fitted):
    f = fitted
    bands = f.predict_quantiles(taus=(0.05, 0.5, 0.95))
    point = f.predict()
    assert (bands[0.05] <= bands[0.5]).all()
    assert (bands[0.5] <= bands[0.95]).all()
    np.testing.assert_allclose(bands[0.5], point, rtol=1e-5)


def test_evaluate_reports_owa_vs_benchmarks(fitted):
    scores = fitted.evaluate(split="test")
    for key in ("smape", "mase", "owa", "smape_comb", "owa_comb",
                "smape_naive2", "mase_naive2"):
        assert np.isfinite(scores[key]), key
    assert scores["owa"] > 0
    val = fitted.evaluate(split="val")
    assert val["split"] == "val" and np.isfinite(val["smape"])


def test_inference_raises_when_data_parallel_exceeds_devices():
    """Like fit, predict refuses a spec that asks for more devices than the
    host has, rather than quietly running on one."""
    n_dev = len(jax.devices())
    f = ESRNNForecaster(get_smoke_spec("esrnn-quarterly",
                                       data_parallel=n_dev + 1))
    f.init_params(4)
    y = np.full((4, 24), 100.0, np.float32)
    with pytest.raises(ValueError, match="devices"):
        f.predict(y)


def test_unfitted_raises():
    f = ESRNNForecaster(get_smoke_spec("esrnn-quarterly"))
    with pytest.raises(NotFittedError):
        f.predict()
    with pytest.raises(NotFittedError):
        f.evaluate()


def test_fit_resumes_from_trainer_checkpoints(fitted, tmp_path):
    """fit(ckpt_dir=...) wires the spec through the shared Checkpointer."""
    f = ESRNNForecaster(get_smoke_spec("esrnn-quarterly", data_seed=3))
    f.fit(n_steps=4, ckpt_dir=str(tmp_path / "ck"))
    g = ESRNNForecaster(f.spec)
    out = g.fit(n_steps=4, ckpt_dir=str(tmp_path / "ck"))
    assert out.history_["loss"] == []  # resumed at step 4: nothing left to do
