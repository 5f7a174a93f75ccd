"""The unified ES-RNN state-space forward core.

One pure pass computes everything the model ever derives from a batch of
series -- Holt-Winters levels/seasonality, the normalized input windows
(Eq. 6), and the RNN head outputs at every valid window position -- and
returns it as an :class:`ESRNNStates` pytree. Both consumers read from that
single state:

* the training loss (``repro.core.esrnn.esrnn_loss_terms_fn``) scores the
  RNN outputs against the normalized target windows via :func:`loss_terms`,
* the forecast (``repro.core.esrnn.esrnn_forecast``) de-normalizes the
  *last* position's output via :func:`forecast_from_states` -- and, because
  the whole recurrence is causal, :func:`forecast_at_origins` reads off the
  forecast from *any* earlier origin of the same pass (rolling-origin
  backtesting without re-running the model per origin).

Before this module the smoothing / window / future-seasonal-index logic
lived twice (once in the loss, once in the forecast); now there is exactly
one implementation, and it dispatches through the existing
``kernels/ops.py`` pure-jax/Pallas paths (``cfg.use_pallas``).

Causality contract (what makes :func:`forecast_at_origins` sound): every
quantity at time/position ``t`` depends only on observations ``y[:, :t+1]``
-- the HW scan writes ``levels[:, t]`` and ``seas[:, t+k]`` (k <= m) from
``y[:, :t+1]``, the input windows end at ``t``, and the dilated LSTM (and
the causally-masked attention variant) only looks backwards. A forecast
read off at origin ``o`` therefore equals the forecast of the truncated
history ``y[:, :o]`` (asserted to float precision in
``tests/core/test_forward.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import heads as H
from repro.core import losses as L
from repro.core.holt_winters import hw_smooth, hw_step

__all__ = [
    "ESRNNStates", "esrnn_states", "smooth", "hw_step", "window_positions",
    "future_seasonal_idx", "input_windows", "target_windows", "features",
    "rnn_head", "loss_terms", "forecast_from_states", "quantile_sigma",
    "forecast_at_origins",
]

# ``hw_step`` is re-exported here as part of the forward core's public
# surface: it is the exact body of the :func:`smooth` scan (extracted, not
# duplicated), and the forecast server's online ``observe`` path applies it
# on host to roll a series' (level, seasonal-ring) state forward per new
# observation -- one step of the same recurrence :func:`esrnn_states` runs
# over the whole history, so the rolled state matches a from-scratch pass.


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ESRNNStates:
    """Everything one forward pass derives from a batch ``y`` (N, T).

    levels: (N, T)    HW level l_t after observing y_t
    seas:   (N, T+m)  multiplicative seasonality; [:, T:] are future factors
    pos:    (P,)      valid window positions t = W-1 .. T-1
    x_in:   (N, P, W) normalized/de-seasonalized/log input windows (Eq. 6)
    yhat_n: (N, P, H) RNN head outputs (normalized log-space predictions)
    c_sq:   ()        mean squared LSTM cell state (section-8.4 penalty term)
    """

    levels: jax.Array
    seas: jax.Array
    pos: jax.Array
    x_in: jax.Array
    yhat_n: jax.Array
    c_sq: jax.Array


# ---------------------------------------------------------------------------
# The single smoothing / window / seasonal-extension implementation
# ---------------------------------------------------------------------------


def smooth(cfg, params, y):
    """HW smoothing with the config's dispatch (pure jax or Pallas kernels).

    Under the ``bf16`` precision policy the observation stream is cast to the
    compute dtype before smoothing -- that is what halves the y tiles the HW
    recurrence reads -- while the recurrence itself, the per-series HW table,
    and the returned levels/seasonality stay in the state dtype (fp32): the
    smoothing parameters are fp32 and every step promotes, so the
    accumulated state never rounds through bf16.
    """
    cdt = cfg.compute_dtype
    if y.dtype != cdt:
        y = y.astype(cdt)
    return hw_smooth(
        y,
        params["hw"],
        seasonality=cfg.seasonality,
        seasonality2=cfg.seasonality2,
        use_pallas=cfg.use_pallas,
    )


def window_positions(cfg, t_len: int):
    """Valid window positions t = W-1 .. T-1 (input window fully observed)."""
    return jnp.arange(cfg.input_size - 1, t_len)


def future_seasonal_idx(out_idx, t_len: int, m: int):
    """Seasonality indices for targets t+1..t+H, cyclically clamped.

    ``seas`` from :func:`smooth` has ``t_len + m`` valid entries when the
    series has ``t_len`` observations; indices beyond that wrap into the
    last smoothed season. This single helper is the seasonal-extension rule
    for the loss targets, the end-of-series forecast, AND every backtest
    origin (where ``t_len`` is the origin's observation count), so the
    paths cannot drift apart.
    """
    return jnp.where(
        out_idx < t_len + m,
        out_idx,
        t_len + jnp.mod(out_idx - t_len, m),
    )


def input_windows(cfg, y, levels, seas):
    """Normalized + de-seasonalized + log input windows (Eq. 6).

    Returns feats (N, P, W) and the position vector (P,). Every returned
    position has a fully-observed input window (positions start at W-1), so
    no input-side mask is needed; target-side validity is handled by
    :func:`target_windows`.
    """
    w = cfg.input_size
    _, t_len = y.shape
    pos = window_positions(cfg, t_len)                         # (P,)
    in_idx = pos[:, None] + jnp.arange(-w + 1, 1)[None, :]     # (P, W)
    y_in = y[:, in_idx]                                        # (N, P, W)
    s_in = seas[:, in_idx]
    lvl = levels[:, pos]                                       # (N, P)
    x_in = jnp.log(jnp.maximum(y_in / (lvl[:, :, None] * s_in), 1e-8))
    return x_in, pos


def target_windows(cfg, y, levels, seas, pos):
    """Normalized output windows + the position-validity mask.

    Output windows need y up to t+H, so the last H positions have no
    (complete) target; ``out_mask`` (N, P, H) in {0,1} marks real targets.
    Clamped (out-of-range) entries are masked out of the loss.
    """
    n, t_len = y.shape
    h = cfg.output_size
    out_idx = pos[:, None] + jnp.arange(1, h + 1)[None, :]     # (P, H)
    out_valid = out_idx < t_len                                # (P, H)
    out_idx_c = jnp.minimum(out_idx, t_len - 1)
    lvl = levels[:, pos]                                       # (N, P)
    y_out = y[:, out_idx_c]                                    # (N, P, H)
    m = max(cfg.seasonality, 1)
    s_out = seas[:, future_seasonal_idx(out_idx, t_len, m)]
    y_out_n = jnp.log(jnp.maximum(y_out / (lvl[:, :, None] * s_out), 1e-8))
    out_mask = out_valid[None, :, :].astype(y.dtype) * jnp.ones((n, 1, 1), y.dtype)
    return y_out_n, out_mask


def features(x_in, cats):
    """Input windows + broadcast one-hot category features (N, P, W + C)."""
    n, p, _ = x_in.shape
    cat_feat = jnp.broadcast_to(cats[:, None, :], (n, p, cats.shape[-1]))
    return jnp.concatenate([x_in, cat_feat.astype(x_in.dtype)], axis=-1)


def rnn_head(cfg, params, feats):
    """Dilated residual LSTM -> (attention) -> tanh dense -> linear head.

    Kept as the public name of the paper's head; the implementation lives
    in :mod:`repro.core.heads` as the ``lstm`` entry of the head registry
    (same math, bit-for-bit -- the goldens assert it).
    """
    return H.lstm_head_apply(cfg, params, feats)


# ---------------------------------------------------------------------------
# The one forward pass
# ---------------------------------------------------------------------------


def esrnn_states(cfg, params, y, cats) -> ESRNNStates:
    """Run the full state-space forward pass once: smoothing, windows, head.

    This is the shared core of the loss and every forecast/backtest path.
    ``y`` (N, T) strictly positive, ``cats`` (N, C) one-hot. The network
    that maps windowed features to normalized predictions is pluggable:
    ``cfg.head`` selects it from the :mod:`repro.core.heads` registry
    (``lstm`` -- the paper's dilated LSTM, ``esn``, ``ssm``, or anything
    registered since). Every head must be causal along the position axis,
    which is what keeps :func:`forecast_at_origins` sound.
    """
    with jax.named_scope("esrnn.hw"):
        levels, seas = smooth(cfg, params, y)
    with jax.named_scope("esrnn.windows"):
        x_in, pos = input_windows(cfg, y, levels, seas)
        feats = features(x_in, cats)
    # The head computes in the policy's dtype (bf16 halves every activation
    # and weight tile it streams); its readout re-emits yhat_n in fp32 so the
    # pinball reduction and the Eq.-5 exp stay full precision.
    cdt = cfg.compute_dtype
    if feats.dtype != cdt:
        feats = feats.astype(cdt)
    with jax.named_scope("esrnn.head"):
        yhat_n, c_sq = H.get_head(cfg.head).apply(cfg, params, feats)
    return ESRNNStates(levels=levels, seas=seas, pos=pos, x_in=x_in,
                       yhat_n=yhat_n, c_sq=c_sq)


# ---------------------------------------------------------------------------
# Consumers: loss terms, forecasts, rolling origins
# ---------------------------------------------------------------------------


def loss_terms(cfg, states: ESRNNStates, y, mask=None):
    """Decomposed training-loss terms ``(pinball_sum, valid_count, penalties)``.

    The target windows are scored against the precomputed RNN outputs;
    ``mask`` (N, T) excludes window positions whose input overlaps the
    left-padding of variable-length series. The decomposition exists for
    exact distributed reduction (psum the first two, divide once globally).
    """
    y_out_n, out_mask = target_windows(cfg, y, states.levels, states.seas,
                                       states.pos)
    if mask is not None:
        valid_in = mask[:, states.pos - cfg.input_size + 1]    # (N, P)
        out_mask = out_mask * valid_in[:, :, None]
    pin_sum, pin_cnt = L.pinball_terms(states.yhat_n, y_out_n, tau=cfg.tau,
                                       mask=out_mask)
    penalties = (L.level_variability_penalty(states.levels, cfg.level_penalty)
                 + L.cstate_penalty(states.c_sq, cfg.cstate_penalty))
    return pin_sum, pin_cnt, penalties


def forecast_from_states(cfg, states: ESRNNStates, t_len: int):
    """h-step forecast from the end of the series: (N, H), de-normalized.

    Eq. 5: ``yhat_{T+1..T+h} = exp(rnn_last) * l_T * s_{T+1..T+h}`` with the
    future seasonality extended by the :func:`future_seasonal_idx` cyclic
    rule at the final position T-1 (indices T..T+H-1).
    """
    with jax.named_scope("esrnn.readout"):
        last = states.yhat_n[:, -1, :]                   # (N, H) log-space
        m = max(cfg.seasonality, 1)
        fut_idx = t_len + jnp.arange(cfg.output_size)    # targets of pos T-1
        s_fut = states.seas[:, future_seasonal_idx(fut_idx, t_len, m)]
        return jnp.exp(last) * states.levels[:, -1:] * s_fut


def quantile_sigma(states: ESRNNStates, y):
    """Per-series log-residual spread sigma (N, 1) for quantile bands.

    The multiplicative model says ``y_t = l_t * s_t * eps_t``, so the std
    of ``log(y) - log(l * s)`` over the in-sample window measures the
    series' own noise scale -- the estimator widens it random-walk style
    (``exp(z_tau * sigma * sqrt(h))``) around the point forecast. Reads the
    fitted levels/seasonality straight off the shared forward states (no
    second smoothing pass).
    """
    t_len = y.shape[1]
    fitted = states.levels * states.seas[:, :t_len]
    log_resid = jnp.log(jnp.maximum(y, 1e-8)) - jnp.log(
        jnp.maximum(fitted, 1e-8))
    return jnp.std(log_resid, axis=1, keepdims=True)


def forecast_at_origins(cfg, states: ESRNNStates,
                        origins: Tuple[int, ...], t_len: int):
    """Rolling-origin forecasts off one forward pass: (N, K, H).

    ``origins[k]`` is an observation count ``o`` (forecast as if only
    ``y[:, :o]`` had been seen). Because every state at position ``o-1``
    is causal in ``y[:, :o]``, reading the RNN output at that position and
    de-normalizing with ``levels[:, o-1]`` and the seasonal factors of a
    length-``o`` series reproduces ``esrnn_forecast(cfg, params,
    y[:, :o], cats)`` -- the ES states are re-primed per origin for free,
    no refit and no per-origin re-run.

    Each origin must satisfy ``cfg.input_size <= o <= t_len`` (the input
    window at o-1 must be fully observed). ``origins`` is static (a tuple),
    so the gather indices are compile-time constants.
    """
    w, h = cfg.input_size, cfg.output_size
    m = max(cfg.seasonality, 1)
    for o in origins:
        if not w <= o <= t_len:
            raise ValueError(
                f"backtest origin {o} outside [{w}, {t_len}]: the input "
                f"window needs {w} observations and the series has {t_len}")
    outs = []
    for o in origins:
        last = states.yhat_n[:, o - w, :]                # position o-1
        fut_idx = o + jnp.arange(h)                      # targets o..o+H-1
        s_fut = states.seas[:, future_seasonal_idx(fut_idx, o, m)]
        outs.append(jnp.exp(last) * states.levels[:, o - 1 : o] * s_fut)
    return jnp.stack(outs, axis=1)                       # (N, K, H)
