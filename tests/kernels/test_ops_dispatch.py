"""kernels/ops.py interpret-mode dispatch coverage.

``ESRNNConfig(use_pallas=True)`` routes the HW scan and the LSTM cell
through the Pallas kernels; off-TPU those run in interpret mode
(``kernels.ops._interpret()``), so the full kernel wiring -- padding to
hardware-aligned shapes, gate-block padding, constrained-space transforms,
stripping -- is exercised in CI without a TPU. The dispatch must be
numerically equivalent to the pure-jax path: same recurrence, same numbers
(float32 interpret mode vs XLA fusion; atol documented on each assert).

Both directions: the kernels carry custom_vjp rules (time-reversed adjoint
scan for hw_scan, fused gate-gradient kernel for lstm_cell), so
``jax.grad(esrnn_loss)`` with ``use_pallas=True`` must match the pure-jax
gradients on every param-tree leaf, and a full ``fit`` trajectory through
the public estimator must track the reference path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.esrnn import (
    esrnn_forecast, esrnn_init, esrnn_loss, esrnn_loss_fn, make_config,
)
from repro.kernels import ops


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(7)
    n, t = 8, 40
    y = jnp.asarray(np.abs(rng.lognormal(3, 0.4, (n, t))) + 1, jnp.float32)
    cats = jnp.asarray(np.eye(6, dtype=np.float32)[rng.integers(0, 6, n)])
    return y, cats


def _cfg(use_pallas):
    return make_config("quarterly", hidden_size=8, use_pallas=use_pallas)


def _max_leaf_diff(tree_a, tree_b):
    return float(max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: jnp.max(jnp.abs(a - b)), tree_a, tree_b))))


def test_dual_seasonality_has_no_kernel_and_raises():
    """Hourly's second ring has no kernel: ``use_pallas`` must say so, not
    quietly run the jnp scan."""
    from repro.core.holt_winters import hw_init_params, hw_smooth

    p = hw_init_params(4, 24, seasonality2=168)
    y = jnp.ones((4, 200), jnp.float32)
    with pytest.raises(NotImplementedError, match="dual-seasonality"):
        hw_smooth(y, p, seasonality=24, seasonality2=168, use_pallas=True)


def test_interpret_mode_is_selected_off_tpu():
    if jax.default_backend() != "tpu":
        assert ops._interpret()


@pytest.mark.parametrize("use_pallas", [False, True])
def test_esrnn_loss_runs_under_both_dispatches(batch, use_pallas):
    y, cats = batch
    cfg = _cfg(use_pallas)
    params = esrnn_init(jax.random.PRNGKey(0), cfg, y.shape[0])
    loss = esrnn_loss(cfg, params, y, cats)
    assert np.isfinite(float(loss))


def test_esrnn_loss_pallas_matches_pure_jax(batch):
    y, cats = batch
    cfg_ref, cfg_k = _cfg(False), _cfg(True)
    params = esrnn_init(jax.random.PRNGKey(0), cfg_ref, y.shape[0])
    ref = esrnn_loss(cfg_ref, params, y, cats)
    ker = esrnn_loss(cfg_k, params, y, cats)
    # same float32 recurrence, different fusion order: 1e-5 covers it
    np.testing.assert_allclose(float(ker), float(ref), rtol=1e-5, atol=1e-6)


def test_esrnn_forecast_pallas_matches_pure_jax(batch):
    y, cats = batch
    cfg_ref, cfg_k = _cfg(False), _cfg(True)
    params = esrnn_init(jax.random.PRNGKey(0), cfg_ref, y.shape[0])
    ref = esrnn_forecast(cfg_ref, params, y, cats)
    ker = esrnn_forecast(cfg_k, params, y, cats)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# gradients: the kernel path must train
# ---------------------------------------------------------------------------


def test_esrnn_loss_grad_pallas_matches_pure_jax(batch):
    """jax.grad(esrnn_loss) equivalence on every param-tree leaf."""
    y, cats = batch
    cfg_ref, cfg_k = _cfg(False), _cfg(True)
    params = esrnn_init(jax.random.PRNGKey(0), cfg_ref, y.shape[0])
    l_ref, g_ref = jax.value_and_grad(
        lambda p: esrnn_loss(cfg_ref, p, y, cats))(params)
    l_ker, g_ker = jax.value_and_grad(
        lambda p: esrnn_loss(cfg_k, p, y, cats))(params)
    np.testing.assert_allclose(float(l_ker), float(l_ref), rtol=1e-5, atol=1e-6)
    assert _max_leaf_diff(g_ker, g_ref) <= 1e-5
    # gradients reach both param groups (not silently zero anywhere)
    assert float(jnp.max(jnp.abs(g_ker["hw"].alpha_logit))) > 0
    assert float(jnp.max(jnp.abs(g_ker["rnn"][0][0]["wx"]))) > 0


def test_esrnn_loss_grad_pallas_matches_with_mask(batch):
    """Same, under a variable-length observation mask."""
    y, cats = batch
    n, t = y.shape
    rng = np.random.default_rng(3)
    mask = np.ones((n, t), np.float32)
    for i in range(n):
        mask[i, : rng.integers(0, t // 3)] = 0.0   # ragged left-padding
    mask = jnp.asarray(mask)
    cfg_ref, cfg_k = _cfg(False), _cfg(True)
    params = esrnn_init(jax.random.PRNGKey(1), cfg_ref, n)
    g_ref = jax.grad(lambda p: esrnn_loss(cfg_ref, p, y, cats, mask))(params)
    g_ker = jax.grad(lambda p: esrnn_loss(cfg_k, p, y, cats, mask))(params)
    assert _max_leaf_diff(g_ker, g_ref) <= 1e-5


def test_esrnn_loss_grad_wrt_inputs_matches(batch):
    """Cotangents to y itself (not just params) agree across dispatches."""
    y, cats = batch
    cfg_ref, cfg_k = _cfg(False), _cfg(True)
    params = esrnn_init(jax.random.PRNGKey(0), cfg_ref, y.shape[0])
    gy_ref = jax.grad(lambda yy: esrnn_loss_fn(cfg_ref, params, yy, cats))(y)
    gy_ker = jax.grad(lambda yy: esrnn_loss_fn(cfg_k, params, yy, cats))(y)
    np.testing.assert_allclose(np.asarray(gy_ker), np.asarray(gy_ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_fit_trajectory_pallas_matches_reference():
    """12-step smoke fit through the public estimator, kernels vs pure jax.

    Acceptance criterion of the trainable-kernel path: identical batch
    schedule + optimizer, gradients equal to float noise, so the loss
    trajectories and fitted forecasts must track (atol 1e-5 mirrors the
    sharded-vs-single-device fit bound in tests/distributed).
    """
    from repro.forecast import ESRNNForecaster, get_smoke_spec

    spec = get_smoke_spec("esrnn-quarterly", data_seed=5, n_steps=12,
                          batch_size=8, data_scale=0.0005)
    f_ref = ESRNNForecaster(spec).fit()
    f_ker = ESRNNForecaster(spec.replace(use_pallas=True)).fit()
    assert f_ker.spec.use_pallas and f_ker.config.use_pallas
    h_ref = np.asarray(f_ref.history_["loss"])
    h_ker = np.asarray(f_ker.history_["loss"])
    assert len(h_ref) == 12
    np.testing.assert_allclose(h_ker, h_ref, atol=1e-5)
    np.testing.assert_allclose(f_ker.predict(), f_ref.predict(),
                               rtol=1e-4, atol=1e-5)
