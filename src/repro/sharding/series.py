"""Series-axis data parallelism for the ES-RNN (Mesh/NamedSharding/shard_map).

The paper's contribution is vectorizing the per-series Holt-Winters
parameters so one device trains all series at once; the next scaling axis is
sharding that series dimension across devices. The per-series HW parameter
table ``params["hw"]`` (all leaves ``(N, ...)``) shards trivially along a
1-D ``series`` mesh axis -- each device owns its rows and their gradients
stay device-local -- while the shared RNN/head/attention weights are
replicated and their gradients all-reduced (the transpose of replication
under ``shard_map`` autodiff is exactly the psum the data-parallel update
needs).

Built on :func:`jax.make_mesh` with ``AxisType.Auto`` axes (the default
is now Explicit, which would make every jit outside a mesh context fail),
:class:`jax.sharding.NamedSharding` and :func:`jax.shard_map` with its
varying-manual-axes check (``check_vma``) on.

Runs on CPU hosts via forced host devices, which is how CI exercises it:

    XLA_FLAGS=--xla_force_host_platform_device_count=8

The loss is a pure traceable function, so the fused training engine
(``repro.train.engine``) can wrap it in ``jax.lax.scan``: one donated
superstep scans K training steps, each evaluating this ``shard_map``-wrapped
loss and its transpose-inserted collectives -- K steps' worth of
all-reduces dispatch as one XLA computation, which is exactly where
multi-device training stops being dispatch-bound.

Semantics of :func:`esrnn_loss_dp`: the loss core is evaluated per-shard in
its decomposed form (``esrnn_loss_terms_fn``: masked pin-ball sum, valid
count, penalty sum) and reduced exactly -- ``psum(masked_sum) /
psum(valid_count)`` plus a pmean of the equal-shaped penalty terms. This is
the *global* masked mean: with ``variable_length`` masks whose valid-target
counts differ across shards it still matches the single-device masked mean
to float-summation order (the old per-shard-mean ``pmean`` only agreed for
equalized masks).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

import jax.numpy as jnp

from repro.core import losses as L
from repro.core.esrnn import (
    ESRNNConfig, esrnn_forecast_at_fn, esrnn_forecast_fn, esrnn_loss_terms_fn,
    esrnn_predict_stats_fn,
)
from repro.kernels import ops as kernel_ops

SERIES_AXIS = "series"


def make_series_mesh(
    n_devices: Optional[int] = None,
    *,
    axis_name: str = SERIES_AXIS,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices (default: all).

    On a CPU host, more than one device requires forcing host devices
    *before* jax initializes:  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs) if n_devices is None else int(n_devices)
    if n < 1 or n > len(devs):
        raise ValueError(
            f"requested {n} devices but {len(devs)} are available; on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=<n> "
            "before the first jax call")
    return jax.make_mesh((n,), (axis_name,), (AxisType.Auto,),
                         devices=devs[:n])


def esrnn_param_specs(params, *, axis_name: str = SERIES_AXIS):
    """PartitionSpec pytree for an ES-RNN params tree.

    The ``hw`` subtree (per-series table, leading N axis) shards on the
    series axis; every other group (rnn / head / attn) is replicated.
    """
    def group_specs(name, subtree):
        sharded = name == "hw"
        return jax.tree_util.tree_map(
            lambda leaf: P(axis_name) if sharded else P(), subtree)

    return {k: group_specs(k, v) for k, v in params.items()}


def esrnn_param_shardings(mesh: Mesh, params, *, axis_name: str = SERIES_AXIS):
    """NamedSharding pytree matching ``params`` (hw sharded, rest replicated)."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        esrnn_param_specs(params, axis_name=axis_name),
        is_leaf=lambda x: isinstance(x, P),
    )


def _check_vma(cfg: ESRNNConfig) -> bool:
    """Whether ``shard_map`` checks varying manual axes: everywhere except
    for kernels run by the Pallas interpreter, whose internal loop does not
    carry the varying axes of its inputs. Natively compiled kernels declare
    them on their outputs and run checked."""
    return not (cfg.use_pallas and kernel_ops._interpret())


def check_series_divisible(n: int, mesh: Mesh) -> int:
    """The shard_map path needs the batch to divide the mesh evenly."""
    d = mesh.devices.size
    if n % d:
        raise ValueError(
            f"series batch of {n} does not divide the {d}-device "
            f"'{'/'.join(mesh.axis_names)}' mesh; pick a batch size that is "
            f"a multiple of {d}")
    return d


def esrnn_loss_dp(
    cfg: ESRNNConfig,
    params,
    y,
    cats,
    mask=None,
    *,
    mesh: Mesh,
    axis_name: str = SERIES_AXIS,
):
    """Data-parallel ES-RNN training loss: shard_map over the series axis.

    Exact global masked mean: each shard contributes its masked pin-ball
    *sum* and *valid count* (``esrnn_loss_terms_fn``); both are psum'd and
    divided once, so unequal per-shard mask counts (``variable_length``
    data) still reproduce the single-device masked mean. The section-8.4
    penalties reduce over equal-shaped per-shard tensors, so their pmean is
    already the global mean.

    Differentiable: taking ``jax.grad`` through this function yields
    device-local gradients for the per-series HW rows and psum'd (all-reduced)
    gradients for the replicated RNN/head weights -- shard_map's transpose
    rule inserts the collective, so the trainer needs no manual psum. This
    composes with ``cfg.use_pallas``: the kernels' custom_vjp backward runs
    per-shard inside the shard_map.

    ``params`` is the *batch* params tree (hw rows already gathered for the
    batch); ``y``/``cats``/``mask`` lead with the same series axis, whose
    size the mesh must divide evenly (see :func:`check_series_divisible`).
    """
    check_series_divisible(y.shape[0], mesh)
    pspecs = esrnn_param_specs(params, axis_name=axis_name)
    rows = (y, cats) if mask is None else (y, cats, mask)

    def local_loss(p, *r):
        pin_sum, pin_cnt, penalties = esrnn_loss_terms_fn(cfg, p, *r)
        pin_sum = jax.lax.psum(pin_sum, axis_name)
        pin_cnt = jax.lax.psum(pin_cnt, axis_name)
        return (pin_sum / jnp.maximum(pin_cnt, 1.0)
                + jax.lax.pmean(penalties, axis_name))

    return jax.shard_map(
        local_loss, mesh=mesh,
        in_specs=(pspecs,) + (P(axis_name),) * len(rows), out_specs=P(),
        check_vma=_check_vma(cfg),
    )(params, *rows)


# ---------------------------------------------------------------------------
# Sharded inference: forecast / quantile stats / eval / backtest
# ---------------------------------------------------------------------------


def _shard_rows(cfg, local_fn, params, rows, *, mesh, axis_name, out_specs):
    """shard_map a per-shard row function over the series axis.

    ``params`` shard like training (hw rows device-local, shared weights
    replicated); every array in ``rows`` leads with the series axis.
    """
    check_series_divisible(rows[0].shape[0], mesh)
    pspecs = esrnn_param_specs(params, axis_name=axis_name)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(pspecs,) + (P(axis_name),) * len(rows),
        out_specs=out_specs,
        check_vma=_check_vma(cfg),
    )(params, *rows)


def esrnn_forecast_dp(
    cfg: ESRNNConfig, params, y, cats, *,
    mesh: Mesh, axis_name: str = SERIES_AXIS,
):
    """Data-parallel h-step forecast: shard_map over the series axis.

    Each device forecasts its own rows from its device-local HW table slice
    and the replicated RNN/head weights -- the per-series structure the
    paper vectorized shards embarrassingly, so there are no collectives at
    all in the forward program. Returns (N, H), sharded on the series axis.
    """
    def local_fc(p, yy, cc):
        return esrnn_forecast_fn(cfg, p, yy, cc)

    return _shard_rows(cfg, local_fc, params, (y, cats), mesh=mesh,
                       axis_name=axis_name, out_specs=P(axis_name))


def esrnn_predict_stats_dp(
    cfg: ESRNNConfig, params, y, cats, *,
    mesh: Mesh, axis_name: str = SERIES_AXIS,
):
    """Sharded ``(forecast, quantile sigma)`` -- the predict_quantiles path.

    Both outputs are per-series rows off the same device-local forward
    states, so they shard with the batch like :func:`esrnn_forecast_dp`.
    """
    def local_stats(p, yy, cc):
        return esrnn_predict_stats_fn(cfg, p, yy, cc)

    return _shard_rows(cfg, local_stats, params, (y, cats), mesh=mesh,
                       axis_name=axis_name,
                       out_specs=(P(axis_name), P(axis_name)))


def esrnn_eval_dp(
    cfg: ESRNNConfig, params, y, cats, target, insample, *,
    seasonality: int, mesh: Mesh, row_mask=None,
    axis_name: str = SERIES_AXIS,
):
    """Sharded sMAPE/MASE of the model forecast as *exact* global means.

    Each shard forecasts its rows from ``y`` and contributes its masked
    metric sums and valid counts (``losses.smape_terms``/``mase_terms``);
    both are psum'd and divided once -- the PR-3 ``psum(sum)/psum(count)``
    pattern, so rows padded up to the mesh multiple (``row_mask`` 0) and
    ragged horizons cannot skew the mean. Returns replicated scalars
    ``{"smape": ..., "mase": ...}`` identical to the single-device metrics
    up to float summation order.

    ``target`` (N, h) is the scoring window, ``insample`` (N, T_in) the
    history for the MASE seasonal-naive scale; ``row_mask`` (N,) is 1 for
    real rows, 0 for padding rows.
    """
    h = target.shape[1]
    rows = ((y, cats, target, insample) if row_mask is None
            else (y, cats, target, insample, row_mask))

    def local_eval(p, yy, cc, tt, ins, *rm):
        fc = esrnn_forecast_fn(cfg, p, yy, cc)[:, :h]
        mask = None if not rm else rm[0][:, None]
        s_sum, s_cnt = L.smape_terms(fc, tt, mask=mask)
        m_sum, m_cnt = L.mase_terms(fc, tt, ins, seasonality, mask=mask)
        s_sum, s_cnt, m_sum, m_cnt = (
            jax.lax.psum(v, axis_name) for v in (s_sum, s_cnt, m_sum, m_cnt))
        return {"smape": 200.0 * s_sum / jnp.maximum(s_cnt, 1.0),
                "mase": m_sum / jnp.maximum(m_cnt, 1.0)}

    return _shard_rows(cfg, local_eval, params, rows, mesh=mesh,
                       axis_name=axis_name,
                       out_specs={"smape": P(), "mase": P()})


def esrnn_backtest_dp(
    cfg: ESRNNConfig, params, y, cats, origins, target, tmask, *,
    seasonality: int, mesh: Mesh, axis_name: str = SERIES_AXIS,
):
    """Sharded rolling-origin forecasts + metric *terms* in one dispatch.

    ``target``/``tmask`` are (N, K, H): per-origin scoring windows and
    their validity masks (0 where the window runs past the series end or
    the row is padding). Returns ``(fc, (s_sum, s_cnt, m_sum, m_cnt))``:
    the (N, K, H) forecasts sharded on the series axis, and the replicated
    (K,) metric terms already psum'd across shards -- the caller divides
    once per origin (and once overall), so sharded backtest metrics match
    single-device to float summation order. One forward pass serves both.
    """
    origins = tuple(int(o) for o in origins)

    def local_bt(p, yy, cc, tt, tm):
        fc = esrnn_forecast_at_fn(cfg, p, yy, cc, origins)
        terms = L.rolling_metric_terms(fc, tt, tm, yy, origins, seasonality)
        return fc, tuple(jax.lax.psum(t, axis_name) for t in terms)

    return _shard_rows(cfg, local_bt, params, (y, cats, target, tmask),
                       mesh=mesh, axis_name=axis_name,
                       out_specs=(P(axis_name), (P(), P(), P(), P())))
