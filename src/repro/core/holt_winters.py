"""Vectorized Holt-Winters exponential smoothing (paper Eqs. 1-4, Smyl variant).

This is the paper's pre-processing layer (section 3.1). The contribution of
Fast ES-RNN is that the per-series smoothing parameters (alpha, gamma, and the
S initial seasonality values -- ``N * (2 + S)`` parameters for N series) live
as *batched tensors* so that the whole recurrence runs vectorized across
series and sits inside the autodiff graph, instead of one series at a time.

Two implementations are provided:

* :func:`hw_smooth` -- ``lax.scan`` over time, vectorized over the series
  axis.  This is the differentiable path used in training.
* :func:`hw_smooth_loop_reference` -- the per-series python-loop formulation
  matching Smyl's original CPU structure.  Kept as the numerical oracle for
  the paper's central claim (vectorized == sequential) and as the slow
  baseline for the Table-5 speedup benchmark.

The Smyl/M4 variant drops the linear trend (Eq. 2 is replaced by the RNN, see
paper section 3.1), leaving

    l_t = alpha * y_t / s_t      + (1 - alpha) * l_{t-1}          (level)
    s_{t+m} = gamma * y_t / l_t  + (1 - gamma) * s_t              (seasonality)

with multiplicative seasonality of period ``m``.  Multiple seasonality
(paper section 8.2, Gould et al. 2008) is supported by a second seasonal ring
with its own period/params; de-seasonalization divides by both.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HWParams:
    """Per-series Holt-Winters parameters (the paper's N*(2+S) parameters).

    All leaves have a leading series axis ``(N, ...)``.  Stored as
    unconstrained logits; constrained values are produced by
    :meth:`constrained`:

      alpha = sigmoid(alpha_logit)          in (0, 1)
      gamma = sigmoid(gamma_logit)          in (0, 1)
      init_seas = exp(init_seas_logit)      > 0   (multiplicative)

    ``init_seas_logit2`` is the optional second seasonality (section 8.2);
    ``None`` when single-seasonal.
    """

    alpha_logit: jax.Array           # (N,)
    gamma_logit: jax.Array           # (N,)
    init_seas_logit: jax.Array       # (N, m)
    gamma2_logit: Optional[jax.Array] = None       # (N,)
    init_seas_logit2: Optional[jax.Array] = None   # (N, m2)

    def constrained(self):
        out = dict(
            alpha=jax.nn.sigmoid(self.alpha_logit),
            gamma=jax.nn.sigmoid(self.gamma_logit),
            init_seas=jnp.exp(self.init_seas_logit),
        )
        if self.init_seas_logit2 is not None:
            out["gamma2"] = jax.nn.sigmoid(self.gamma2_logit)
            out["init_seas2"] = jnp.exp(self.init_seas_logit2)
        return out


def hw_init_params(
    n_series: int,
    seasonality: int,
    *,
    seasonality2: int = 0,
    alpha0: float = 0.5,
    gamma0: float = 0.5,
    dtype=jnp.float32,
) -> HWParams:
    """Primer initialization (paper section 3.3): neutral smoothing
    coefficients and flat (== 1.0) initial seasonality."""

    def logit(p):
        return float(np.log(p / (1.0 - p)))

    m = max(seasonality, 1)
    params = HWParams(
        alpha_logit=jnp.full((n_series,), logit(alpha0), dtype),
        gamma_logit=jnp.full((n_series,), logit(gamma0), dtype),
        init_seas_logit=jnp.zeros((n_series, m), dtype),
    )
    if seasonality2:
        params = dataclasses.replace(
            params,
            gamma2_logit=jnp.full((n_series,), logit(gamma0), dtype),
            init_seas_logit2=jnp.zeros((n_series, seasonality2), dtype),
        )
    return params


# ---------------------------------------------------------------------------
# The one-step recurrence (shared by the scan and the online serving path)
# ---------------------------------------------------------------------------


def hw_step(
    y_t,
    level,
    s_t,
    s2_t,
    alpha,
    gamma,
    gamma2=None,
    *,
    seasonal: bool = True,
    dual: bool = False,
):
    """One Holt-Winters update: ``(l_t, s_new, s2_new)`` from observation y_t.

        l_t     = alpha * y_t / (s_t * s2_t) + (1 - alpha) * l_{t-1}
        s_{t+m} = gamma * y_t / (l_t * s2_t) + (1 - gamma) * s_t
        s2_{t+m2} = gamma2 * y_t / (l_t * s_t) + (1 - gamma2) * s2_t

    This IS the body of the :func:`hw_smooth` scan (extracted, not
    duplicated -- the scan calls it), written in pure arithmetic so it runs
    on jax arrays inside ``lax.scan`` AND on host numpy arrays for the
    forecast server's online ``observe`` path, which rolls each series'
    (level, seasonal-ring) state forward in place as new observations
    arrive -- no refit, no re-pass over history. ``seasonal=False`` holds
    the seasonal factor fixed (m == 1 series); ``dual`` enables the second
    ring (section 8.2). Inputs are scalars or arrays with a common batch
    shape; ring rotation is the caller's job (the new factors returned here
    are s_{t+m} / s2_{t+m2}, to be pushed onto the back of the rings).
    """
    s_all = s_t * s2_t
    l_t = alpha * y_t / s_all + (1.0 - alpha) * level
    s_new = (gamma * y_t / (l_t * s2_t) + (1.0 - gamma) * s_t
             if seasonal else s_t)
    s2_new = (gamma2 * y_t / (l_t * s_t) + (1.0 - gamma2) * s2_t
              if dual else s2_t)
    return l_t, s_new, s2_new


# ---------------------------------------------------------------------------
# Vectorized scan implementation (the paper's contribution)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("seasonality", "seasonality2", "use_pallas"))
def hw_smooth(
    y: jax.Array,
    params: HWParams,
    *,
    seasonality: int,
    seasonality2: int = 0,
    use_pallas: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Run the batched Holt-Winters recurrence.

    Args:
      y: ``(N, T)`` strictly-positive series values (multiplicative model).
      params: per-series :class:`HWParams`.
      seasonality: period ``m`` (1 => non-seasonal; seasonality fixed at 1.0).
      seasonality2: optional second period (0 => disabled).
      use_pallas: route the recurrence through the Pallas TPU kernel
        (``kernels/hw_scan.py``). Numerics are identical (kernel is tested
        against this path) and the kernel is differentiable -- its
        custom_vjp runs the adjoint recurrence time-reversed as a second
        kernel, so training with ``use_pallas=True`` works end-to-end. The
        kernel has one seasonality ring: with ``seasonality2`` it raises.

    Returns:
      levels: ``(N, T)`` level l_t after observing y_t.
      seas:   ``(N, T + m)`` multiplicative seasonality aligned so that
        ``seas[:, t]`` is s_t, the factor applied to y_t; positions
        ``T .. T+m-1`` are the smoothed future factors. For ``seasonality2``
        the product of both rings is returned (what de-seasonalization uses).
    """
    if use_pallas:
        if seasonality2:
            raise NotImplementedError(
                "use_pallas=True has no dual-seasonality Holt-Winters kernel: "
                "kernels/hw_scan.py runs one seasonality ring, and this "
                f"config sets seasonality2={seasonality2}")
        from repro.kernels import ops as kernel_ops

        return kernel_ops.hw_scan(y, params, seasonality=seasonality)
    return _hw_smooth_scan(y, params, seasonality, seasonality2)


def _hw_smooth_scan(y, params, seasonality, seasonality2):
    n, t_len = y.shape
    c = params.constrained()
    alpha, gamma = c["alpha"], c["gamma"]
    m = max(seasonality, 1)
    seasonal = seasonality > 1

    # seasonality ring buffer s_{t} .. s_{t+m-1}; index 0 is "current" s_t.
    # Rings live in the *param* dtype (fp32), not y's: under the bf16 policy
    # y streams in half width but the level/seasonality recurrence must
    # accumulate in the state dtype -- each step promotes y_t, so the carry
    # never rounds through bf16.
    seas0 = c["init_seas"] if seasonal else jnp.ones((n, m), alpha.dtype)

    dual = seasonality2 > 1
    if dual:
        m2 = seasonality2
        gamma2 = c["gamma2"]
        seas20 = c["init_seas2"]
    else:
        m2 = 1
        gamma2 = jnp.zeros_like(gamma)
        seas20 = jnp.ones((n, 1), alpha.dtype)

    # initial level: first de-seasonalized observation (primer estimate).
    l0 = y[:, 0] / (seas0[:, 0] * seas20[:, 0])

    def step(carry, y_t):
        l_prev, s_ring, s2_ring = carry
        s_t = s_ring[:, 0]
        s2_t = s2_ring[:, 0]
        l_t, s_new, s2_new = hw_step(
            y_t, l_prev, s_t, s2_t, alpha, gamma, gamma2,
            seasonal=seasonal, dual=dual)
        s_ring = jnp.concatenate([s_ring[:, 1:], s_new[:, None]], axis=1)
        s2_ring = jnp.concatenate([s2_ring[:, 1:], s2_new[:, None]], axis=1)
        return (l_t, s_ring, s2_ring), (l_t, s_t * s2_t)

    (_, s_ring, s2_ring), (levels, seas_used) = jax.lax.scan(
        step, (l0, seas0, seas20), y.T
    )
    levels = levels.T                      # (N, T)
    seas_used = seas_used.T                # (N, T) -- s_t actually applied

    # future factors: remaining ring entries (s_{T} .. s_{T+m-1}); for the
    # dual ring tile the shorter one up to m.
    future = s_ring * jnp.broadcast_to(
        jnp.tile(s2_ring, (1, (m + m2 - 1) // m2))[:, :m], (n, m)
    ) if dual else s_ring
    seas = jnp.concatenate([seas_used, future], axis=1)  # (N, T+m)
    return levels, seas


# ---------------------------------------------------------------------------
# Per-series loop reference (Smyl's original CPU structure)
# ---------------------------------------------------------------------------


def hw_smooth_loop_reference(
    y: np.ndarray, params: HWParams, *, seasonality: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Pure-numpy per-series sequential implementation.

    Mirrors the original C++/DyNet structure the paper vectorized: an outer
    loop over series, an inner loop over time. Used (a) as the oracle for the
    equivalence tests and (b) as the slow baseline in the Table-5 speedup
    benchmark.
    """
    y = np.asarray(y, np.float64)
    n, t_len = y.shape
    m = max(seasonality, 1)
    seasonal = seasonality > 1
    alpha = 1.0 / (1.0 + np.exp(-np.asarray(params.alpha_logit, np.float64)))
    gamma = 1.0 / (1.0 + np.exp(-np.asarray(params.gamma_logit, np.float64)))
    init_seas = np.exp(np.asarray(params.init_seas_logit, np.float64))

    levels = np.empty((n, t_len))
    seas = np.empty((n, t_len + m))
    for i in range(n):  # <- the loop the paper removes
        ring = list(init_seas[i] if seasonal else np.ones(m))
        l_prev = y[i, 0] / ring[0]
        for t in range(t_len):
            s_t = ring[0]
            l_t = alpha[i] * y[i, t] / s_t + (1 - alpha[i]) * l_prev
            if seasonal:
                s_new = gamma[i] * y[i, t] / l_t + (1 - gamma[i]) * s_t
            else:
                s_new = s_t
            ring = ring[1:] + [s_new]
            levels[i, t] = l_t
            seas[i, t] = s_t
            l_prev = l_t
        seas[i, t_len:] = ring
    return levels, seas


# ---------------------------------------------------------------------------
# Classic HW forecast (Eq. 4) -- used by the Comb benchmark and primers
# ---------------------------------------------------------------------------


def hw_forecast(
    levels: jax.Array, seas: jax.Array, horizon: int, *, seasonality: int
) -> jax.Array:
    """h-step forecast y_hat_{T+h} = l_T * s_{T+h} (Eq. 4 with b_t == 1).

    ``seas`` is the ``(N, T+m)`` array from :func:`hw_smooth`; future factors
    beyond T+m tile the last season cyclically (how ESRNN-GPU extends them).
    """
    m = max(seasonality, 1)
    last_level = levels[:, -1]                      # (N,)
    last_season = seas[:, -m:]                      # (N, m)
    reps = -(-horizon // m)
    future = jnp.tile(last_season, (1, reps))[:, :horizon]
    return last_level[:, None] * future


def extend_seasonality(seas: jax.Array, t_len: int, horizon: int, seasonality: int):
    """Seasonality factors s_{T+1} .. s_{T+h} for de-normalizing forecasts.

    ``seas`` has valid entries up to index T+m-1; beyond that the last season
    is tiled cyclically (horizon can exceed m, e.g. quarterly h=8 > m=4).
    """
    m = max(seasonality, 1)
    if horizon <= m:
        return seas[:, t_len : t_len + horizon]
    last_season = seas[:, t_len : t_len + m]
    reps = -(-horizon // m)
    return jnp.tile(last_season, (1, reps))[:, :horizon]
