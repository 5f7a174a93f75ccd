"""Pallas hw_scan kernel vs pure-jnp oracle: shape/dtype sweep + gradients.

The kernel carries a custom_vjp whose backward is the time-reversed adjoint
recurrence (kernels/hw_scan.py). Gradient coverage here: analytic-vs-autodiff
equivalence against the pure-jnp oracle, finite-difference spot checks on the
raw kernel cotangents, and pad-lane gradient isolation.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.holt_winters import hw_init_params
from repro.kernels import hw_scan as hw_scan_mod
from repro.kernels import ops
from repro.kernels.ref import hw_scan_ref


def _setup(n, t, m, seed, dtype):
    rng = np.random.default_rng(seed)
    y = jnp.asarray(np.abs(rng.lognormal(2, 0.5, (n, t))) + 0.5, dtype)
    p = hw_init_params(n, m, dtype=dtype)
    p = dataclasses.replace(
        p,
        alpha_logit=jnp.asarray(rng.normal(0, 1, n), dtype),
        gamma_logit=jnp.asarray(rng.normal(0, 1, n), dtype),
        init_seas_logit=jnp.asarray(rng.normal(0, 0.2, (n, m)), dtype),
    )
    return y, p


@pytest.mark.parametrize("n", [1, 5, 128, 200])
@pytest.mark.parametrize("t", [8, 73])
@pytest.mark.parametrize("m", [1, 4, 12])
def test_hw_scan_shapes(n, t, m):
    y, p = _setup(n, t, m, seed=n * 1000 + t + m, dtype=jnp.float32)
    lv, ss = ops.hw_scan(y, p, seasonality=m)
    c = p.constrained()
    seas0 = c["init_seas"] if m > 1 else jnp.ones((n, m), y.dtype)
    gamma = c["gamma"] if m > 1 else jnp.zeros_like(c["gamma"])
    lv_ref, ss_ref = hw_scan_ref(y, c["alpha"], gamma, seas0)
    assert lv.shape == (n, t) and ss.shape == (n, t + m)
    np.testing.assert_allclose(lv, lv_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ss, ss_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5), (jnp.bfloat16, 0.05)])
def test_hw_scan_dtypes(dtype, rtol):
    y, p = _setup(37, 40, 4, seed=0, dtype=jnp.float32)
    lv32, ss32 = ops.hw_scan(y, p, seasonality=4)
    yd = y.astype(dtype)
    pd = dataclasses.replace(
        p, alpha_logit=p.alpha_logit.astype(dtype),
        gamma_logit=p.gamma_logit.astype(dtype),
        init_seas_logit=p.init_seas_logit.astype(dtype))
    lv, ss = ops.hw_scan(yd, pd, seasonality=4)
    assert lv.dtype == dtype
    np.testing.assert_allclose(lv.astype(jnp.float32), lv32, rtol=rtol, atol=rtol)


def test_matches_hw_smooth_use_pallas_flag():
    from repro.core.holt_winters import hw_smooth

    y, p = _setup(9, 30, 4, seed=5, dtype=jnp.float32)
    lv1, ss1 = hw_smooth(y, p, seasonality=4, use_pallas=False)
    lv2, ss2 = hw_smooth(y, p, seasonality=4, use_pallas=True)
    np.testing.assert_allclose(lv1, lv2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ss1, ss2, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# gradients (custom_vjp backward kernel)
# ---------------------------------------------------------------------------


def _weighted_sum(n, t, m, seed):
    """A fixed random linear functional of (levels, seas) -> scalar."""
    rng = np.random.default_rng(seed)
    wl = jnp.asarray(rng.normal(0, 1, (n, t)), jnp.float32)
    ws = jnp.asarray(rng.normal(0, 1, (n, t + m)), jnp.float32)
    return wl, ws


@pytest.mark.parametrize("n,t,m", [(5, 23, 4), (128, 10, 1), (40, 8, 12)])
def test_hw_scan_grad_matches_autodiff_reference(n, t, m):
    """Analytic backward kernel == jax.grad through the pure-jnp scan.

    Covers padding (n=5, 40), the non-seasonal m=1 path, and T < m. Grads
    are taken in the unconstrained HWParams space through ops.hw_scan, so
    the sigmoid/exp transforms and pad/strip plumbing are differentiated
    alongside the kernel.
    """
    y, p = _setup(n, t, m, seed=n + t + m, dtype=jnp.float32)
    wl, ws = _weighted_sum(n, t, m, seed=99)

    def proj_kernel(p, y):
        lv, ss = ops.hw_scan(y, p, seasonality=m)
        return jnp.sum(lv * wl) + jnp.sum(ss * ws)

    def proj_ref(p, y):
        c = p.constrained()
        seas0 = c["init_seas"] if m > 1 else jnp.ones((n, m), y.dtype)
        gamma = c["gamma"] if m > 1 else jnp.zeros_like(c["gamma"])
        lv, ss = hw_scan_ref(y, c["alpha"], gamma, seas0)
        return jnp.sum(lv * wl) + jnp.sum(ss * ws)

    gk_p, gk_y = jax.grad(proj_kernel, argnums=(0, 1))(p, y)
    gr_p, gr_y = jax.grad(proj_ref, argnums=(0, 1))(p, y)
    scale = max(1.0, float(jnp.max(jnp.abs(gr_y))))
    np.testing.assert_allclose(gk_y, gr_y, atol=1e-4 * scale)
    for leaf_k, leaf_r in zip(jax.tree_util.tree_leaves(gk_p),
                              jax.tree_util.tree_leaves(gr_p)):
        s = max(1.0, float(jnp.max(jnp.abs(leaf_r))))
        np.testing.assert_allclose(leaf_k, leaf_r, atol=1e-4 * s)


def test_hw_scan_cotangents_finite_difference():
    """Central-difference spot checks on raw hw_scan_tm cotangents."""
    rng = np.random.default_rng(11)
    n, t, m = 128, 12, 4
    y = jnp.asarray(np.abs(rng.lognormal(0.5, 0.3, (n, t))) + 0.5, jnp.float32)
    alpha = jnp.asarray(rng.uniform(0.3, 0.7, n), jnp.float32)
    gamma = jnp.asarray(rng.uniform(0.3, 0.7, n), jnp.float32)
    s0 = jnp.asarray(np.exp(rng.normal(0, 0.1, (m, n))), jnp.float32)
    wl, ws = _weighted_sum(n, t, m, seed=12)

    def f(y, alpha, gamma, s0):
        lv, ss = hw_scan_mod.hw_scan_tm(y.T, alpha, gamma, s0,
                                        interpret=True)
        return jnp.sum(lv.T * wl) + jnp.sum(ss.T * ws)

    grads = jax.grad(f, argnums=(0, 1, 2, 3))(y, alpha, gamma, s0)
    f64 = lambda *a: float(f(*a))
    eps = 1e-3
    # a few fixed coordinates in each input
    checks = [
        (0, y, [(0, 0), (3, 7), (100, t - 1)]),
        (1, alpha, [(5,), (77,)]),
        (2, gamma, [(9,), (50,)]),
        (3, s0, [(0, 4), (m - 1, 64)]),
    ]
    args = [y, alpha, gamma, s0]
    for argnum, arr, coords in checks:
        for coord in coords:
            delta = np.zeros(arr.shape, np.float32)
            delta[coord] = eps
            hi = list(args); hi[argnum] = arr + delta
            lo = list(args); lo[argnum] = arr - delta
            fd = (f64(*hi) - f64(*lo)) / (2 * eps)
            an = float(grads[argnum][coord])
            assert abs(fd - an) <= 2e-2 * max(1.0, abs(fd)), (
                f"argnum {argnum} coord {coord}: fd={fd} analytic={an}")


def test_pad_lane_grads_are_isolated():
    """Padded (N=120 -> 128) grads == unpadded (N=128) grads row-for-row.

    The recurrence is per-series independent, so lane padding must be
    invisible to gradients: any phantom cotangent scattered from a
    duplicated pad lane back into the last real lane would break this.
    """
    y_full, p_full = _setup(128, 20, 4, seed=2, dtype=jnp.float32)
    n_sub = 120
    p_sub = dataclasses.replace(
        p_full,
        alpha_logit=p_full.alpha_logit[:n_sub],
        gamma_logit=p_full.gamma_logit[:n_sub],
        init_seas_logit=p_full.init_seas_logit[:n_sub],
    )
    y_sub = y_full[:n_sub]

    def proj(p, y):
        lv, ss = ops.hw_scan(y, p, seasonality=4)
        return jnp.sum(jnp.log1p(jnp.square(lv))) + jnp.sum(jnp.sqrt(ss))

    g_full_p, g_full_y = jax.grad(proj, argnums=(0, 1))(p_full, y_full)
    g_sub_p, g_sub_y = jax.grad(proj, argnums=(0, 1))(p_sub, y_sub)
    np.testing.assert_array_equal(np.asarray(g_sub_y),
                                  np.asarray(g_full_y)[:n_sub])
    np.testing.assert_array_equal(np.asarray(g_sub_p.alpha_logit),
                                  np.asarray(g_full_p.alpha_logit)[:n_sub])
    np.testing.assert_array_equal(np.asarray(g_sub_p.gamma_logit),
                                  np.asarray(g_full_p.gamma_logit)[:n_sub])
    np.testing.assert_array_equal(np.asarray(g_sub_p.init_seas_logit),
                                  np.asarray(g_full_p.init_seas_logit)[:n_sub])

