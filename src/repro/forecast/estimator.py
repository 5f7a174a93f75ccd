"""ESRNNForecaster: estimator-style entry point for the hybrid ES-RNN.

One object, six verbs -- the whole paper workflow behind a stable surface:

    f = ESRNNForecaster("esrnn-quarterly")          # or a ForecastSpec
    f.fit(data)                                     # joint two-group training
    yhat = f.predict()                              # (N, H) point forecast
    bands = f.predict_quantiles(taus=(0.1, 0.5, 0.9))
    scores = f.evaluate(split="test")               # sMAPE/MASE/OWA vs
                                                    # Comb / Naive2
    bt = f.backtest(origins=(72, 80))               # rolling-origin scores,
                                                    # one forward pass
    f.save(path);  g = ESRNNForecaster.load(path)   # shared Checkpointer
    srv = f.serve()                                 # continuous-batching
                                                    # online server

Every inference verb accepts ``mesh=`` (or inherits ``spec.data_parallel``)
to run series-sharded across devices with exact psum'd metrics; rows are
padded to the device multiple and stripped, so any N works.

The estimator wraps the pure ``esrnn_init/esrnn_loss/esrnn_forecast*``
functions from ``repro.core.esrnn`` (all backed by the single
``repro.core.forward`` state-space pass) -- it holds state (spec, params,
data), the math stays functional and jitted.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import spans
from repro.checkpoint.checkpointer import Checkpointer
from repro.core import losses as L
from repro.core.comb import comb_forecast, naive2_forecast
from repro.core.esrnn import (
    esrnn_forecast, esrnn_forecast_at, esrnn_init, esrnn_loss,
    esrnn_loss_and_grad, esrnn_predict_stats, gather_series,
)
from repro.data.pipeline import PreparedData, chunk_bounds, prepare
from repro.data.synthetic_m4 import M4Dataset, generate
from repro.forecast.spec import ForecastSpec, get_spec
from repro.train.trainer import train_from_spec

_META_FILE = "forecaster.json"


class NotFittedError(RuntimeError):
    pass


def _pad_rows(a, pad: int):
    """Repeat the last row ``pad`` times (sharded-inference row padding)."""
    if pad == 0:
        return a
    return jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)], axis=0)


class ESRNNForecaster:
    """Scikit-style estimator over the vectorized ES-RNN."""

    def __init__(self, spec: Union[str, ForecastSpec] = "esrnn-quarterly",
                 **overrides):
        if isinstance(spec, str):
            spec = get_spec(spec, **overrides)
        elif overrides:
            spec = spec.replace(**overrides)
        self.spec = spec
        self.params_: Optional[Dict] = None
        self.history_: Optional[Dict] = None
        self.n_series_: Optional[int] = None
        self.data_: Optional[PreparedData] = None
        self.cats_: Optional[np.ndarray] = None   # fitted one-hots, persisted

    # -- config shortcuts ----------------------------------------------------

    @property
    def config(self):
        return self.spec.model

    @property
    def horizon(self) -> int:
        return self.spec.horizon

    def _check_fitted(self):
        if self.params_ is None:
            raise NotFittedError(
                "this ESRNNForecaster has no params; call fit(), "
                "init_params(), or load() first")

    # -- data ----------------------------------------------------------------

    def make_data(self) -> PreparedData:
        """Spec-driven synthetic M4 slice (Tables 2/3 profile, section 5)."""
        spec = self.spec
        ds = generate(spec.frequency, scale=spec.data_scale, seed=spec.data_seed)
        return prepare(ds, min_length=spec.min_length,
                       variable_length=spec.variable_length)

    def _coerce_data(self, data) -> PreparedData:
        if data is None:
            return self.make_data()
        if isinstance(data, M4Dataset):
            return prepare(data, min_length=self.spec.min_length,
                           variable_length=self.spec.variable_length)
        if isinstance(data, PreparedData):
            return data
        raise TypeError(f"cannot fit on {type(data).__name__}; "
                        "pass PreparedData, M4Dataset, or None")

    # -- fit -----------------------------------------------------------------

    def init_params(self, n_series: int, seed: Optional[int] = None):
        """Primer initialization without training (cold-start serving)."""
        seed = self.spec.seed if seed is None else seed
        self.params_ = esrnn_init(jax.random.PRNGKey(seed), self.config, n_series)
        self.n_series_ = n_series
        return self.params_

    def fit(self, data=None, *, ckpt_dir: Optional[str] = None,
            n_steps: Optional[int] = None, hooks=None,
            mesh=None) -> "ESRNNForecaster":
        """Joint two-group training (spec's rnn_lr / hw_lr); returns self.

        ``mesh``: optional 1-D series mesh for multi-device data-parallel
        training (see ``repro.sharding.series.make_series_mesh``); without
        one, ``spec.data_parallel > 1`` builds a mesh over that many local
        devices. Fitted params are identical in structure either way, so
        predict/evaluate/save/serve are unchanged.

        ``spec.scan_steps > 1`` trains through the fused superstep engine
        (K steps per donated ``lax.scan`` dispatch, host sync at superstep
        boundaries) -- same loss trajectory, fewer dispatches; composes
        with ``mesh``/``data_parallel`` and ``use_pallas``. When ``hooks``
        contains ``on_step`` it then fires once per superstep with the
        segment's loss array. ``spec.sparse_adam`` switches the per-series
        table to the sparse segment optimizer.
        """
        pdata = self._coerce_data(data)
        out = train_from_spec(self.spec, pdata, ckpt_dir=ckpt_dir,
                              n_steps=n_steps, params=self.params_, hooks=hooks,
                              mesh=mesh)
        self.params_ = out["params"]
        self.history_ = out["history"]
        self.n_series_ = pdata.n_series
        self.data_ = pdata
        self.cats_ = np.asarray(pdata.cats, np.float32)
        return self

    # -- predict -------------------------------------------------------------

    def _resolve_inputs(self, y, cats, series_idx, *, host: bool = False):
        """Resolve (params, y, cats). ``host=True`` keeps everything in host
        numpy (the chunked-streaming verbs slice rows out before any device
        transfer, so an out-of-core table never lands on device whole)."""
        xp = np if host else jnp
        self._check_fitted()
        if y is None:
            if self.data_ is None:
                raise NotFittedError("predict() without y requires fit(data)")
            y = self.data_.train
        y = xp.asarray(y, self.config.jdtype)
        if cats is None and self.cats_ is not None:
            # fitted categories: the rows of y are (a subset of) the fitted
            # series, so reuse their one-hots rather than zeroing the feature
            # (survives save/load -- cats_ is persisted in forecaster.json)
            if series_idx is not None:
                cats = self.cats_[np.asarray(series_idx)]
            elif y.shape[0] == self.cats_.shape[0]:
                cats = self.cats_
        if cats is None:
            cats = xp.zeros((y.shape[0], self.config.n_categories))
        cats = xp.asarray(cats, self.config.jdtype)
        params = self.params_
        if series_idx is not None:
            params = gather_series(params, np.asarray(series_idx))
        n_hw = params["hw"].alpha_logit.shape[0]
        if y.shape[0] != n_hw:
            raise ValueError(
                f"y has {y.shape[0]} series but the fitted per-series table "
                f"has {n_hw}; pass series_idx to select rows")
        return params, y, cats

    # -- sharded-inference plumbing ------------------------------------------

    def _resolve_mesh(self, mesh):
        """Explicit mesh, else one built from ``spec.data_parallel`` (> 1).

        Mirrors ``fit``'s resolution rule so an estimator fitted with
        ``data_parallel=8`` serves predict/evaluate/backtest sharded the
        same way without re-plumbing a mesh through every call. Like
        ``fit``, it raises when fewer devices are present than the spec
        asks for (on a smaller host, lower ``spec.data_parallel`` or pass a
        mesh that fits). A 1-device mesh degenerates to the single-device path
        (identical math, no shard_map hop).
        """
        if mesh is None and self.spec.data_parallel > 1:
            from repro.sharding.series import make_series_mesh

            mesh = make_series_mesh(self.spec.data_parallel)
        if mesh is not None and mesh.devices.size == 1:
            mesh = None
        return mesh

    def _shard_rows(self, params, arrays, mesh):
        """Pad rows (params hw + batch arrays) up to the mesh multiple.

        Inference batches are whatever the caller has -- unlike training
        batches they need not divide the device count -- so the rows are
        padded by repeating the last one (``pad`` returned for stripping /
        masking the metrics).
        """
        n = arrays[0].shape[0]
        pad = (-n) % mesh.devices.size
        if pad:
            params = {
                k: (jax.tree_util.tree_map(lambda a: _pad_rows(a, pad), v)
                    if k == "hw" else v)
                for k, v in params.items()}
            arrays = tuple(_pad_rows(jnp.asarray(a), pad) for a in arrays)
        return params, arrays, pad

    def _chunk_ranges(self, n: int):
        """[lo, hi) series chunks when the spec streams, else None."""
        c = self.spec.series_chunk
        if c and c > 0 and n > c:
            return chunk_bounds(n, c)
        return None

    def _forecast_chunk(self, params, y, cats, mesh):
        """One chunk's forecast: host slices in, (rows, H) numpy out.

        Composes chunk streaming (outer loop) with the series mesh (inner
        shard): the chunk's rows are padded to the device multiple and the
        pad stripped, exactly like resident sharded inference.
        """
        on = spans.recording()
        with spans.span("predict.inputs", on=on):
            p_c = {k: (jax.tree_util.tree_map(jnp.asarray, v) if k == "hw"
                       else v) for k, v in params.items()}
            y = jnp.asarray(y)
            cats = jnp.asarray(cats)
        return self._forecast_rows(p_c, y, cats, mesh, on)

    def _forecast_rows(self, params, y, cats, mesh, on: bool):
        """Forecast rows whose copies to the device are under way: (rows, H)
        numpy out, under the spans ``predict.forecast`` (the dispatch),
        ``predict.transfer`` and ``predict.result`` (the read back). The
        copies run on a worker thread, so the caller's ``predict.inputs``
        holds only their start; while a trace runs, ``predict.transfer``
        waits for them after the dispatch, when the forecast already stands
        behind them on the device, so the wait moves nothing there."""
        rows = y.shape[0]
        with spans.span("predict.forecast", on=on):
            if mesh is None:
                fc = esrnn_forecast(self.config, params, y, cats)
            else:
                from repro.sharding.series import esrnn_forecast_dp

                params, (y, cats), _pad = self._shard_rows(
                    params, (y, cats), mesh)
                fc = esrnn_forecast_dp(self.config, params, y, cats,
                                       mesh=mesh)
        if on:
            with spans.span("predict.transfer", on=on):
                jax.block_until_ready((y, cats))
        with spans.span("predict.result", on=on):
            return np.asarray(fc)[:rows]

    def predict(self, y=None, cats=None, *,
                series_idx: Optional[Sequence[int]] = None,
                mesh=None) -> np.ndarray:
        """Point forecast (N, H) from the end of each series (Eq. 5).

        With no arguments, forecasts the fitted training series. ``y`` may be
        any history for the fitted series (e.g. train+val to forecast the test
        window); ``series_idx`` selects per-series HW rows when y is a subset.

        ``mesh``: optional 1-D series mesh for sharded inference (defaults
        to one over ``spec.data_parallel`` devices when that is > 1): each
        device forecasts its own HW-table rows under ``shard_map``; rows
        are padded to the device multiple and stripped, so any N works.

        ``spec.series_chunk > 0`` streams the forecast: rows move to device
        one ``series_chunk``-sized shard at a time (params table included --
        after a chunked fit its leaves are host numpy and never land on
        device whole), each shard running through the same jitted forecast
        (and the same mesh, when sharded).
        """
        mesh = self._resolve_mesh(mesh)
        n_in = (self.n_series_ if y is None else np.shape(y)[0])
        on = spans.recording()
        with spans.span("predict.call", leaf=False, on=on):
            if series_idx is None and self._chunk_ranges(n_in or 0):
                params, y, cats = self._resolve_inputs(y, cats, None,
                                                       host=True)
                out = np.empty((y.shape[0], self.horizon), np.float32)
                shared = {k: v for k, v in params.items() if k != "hw"}
                for lo, hi in self._chunk_ranges(y.shape[0]):
                    p_c = {"hw": jax.tree_util.tree_map(
                        lambda a: a[lo:hi], params["hw"]), **shared}
                    out[lo:hi] = self._forecast_chunk(
                        p_c, y[lo:hi], cats[lo:hi], mesh)
                return out
            with spans.span("predict.inputs", on=on):
                params, y, cats = self._resolve_inputs(y, cats, series_idx)
            return self._forecast_rows(params, y, cats, mesh, on)

    def predict_quantiles(
        self, y=None, cats=None, *, taus: Tuple[float, ...] = (0.1, 0.5, 0.9),
        series_idx: Optional[Sequence[int]] = None, mesh=None,
    ) -> Dict[float, np.ndarray]:
        """Quantile bands around the point forecast.

        The model is trained on a single pinball quantile (spec ``tau``), so
        its output is one quantile path. Bands are derived from the fitted
        Holt-Winters in-sample residuals: the multiplicative model says
        y_t = l_t * s_t * eps_t, so per-series log-residual spread sigma gives
        q_tau(h) = yhat * exp(z_tau * sigma * sqrt(h)) -- a random-walk
        widening in log-space (beyond-paper convenience; tau=0.5 returns the
        point forecast exactly). Point and sigma come off ONE forward-core
        pass (``esrnn_predict_stats``); ``mesh`` shards it like ``predict``.
        """
        params, y, cats = self._resolve_inputs(y, cats, series_idx)
        mesh = self._resolve_mesh(mesh)
        n = y.shape[0]
        if mesh is None:
            point, sigma = esrnn_predict_stats(self.config, params, y, cats)
        else:
            from repro.sharding.series import esrnn_predict_stats_dp

            params, (y, cats), _pad = self._shard_rows(params, (y, cats), mesh)
            point, sigma = esrnn_predict_stats_dp(
                self.config, params, y, cats, mesh=mesh)
            point, sigma = point[:n], sigma[:n]
        steps = jnp.sqrt(jnp.arange(1, self.horizon + 1))[None, :]  # (1, H)
        out = {}
        for tau in taus:
            z = jax.scipy.special.ndtri(jnp.asarray(tau, jnp.float32))
            out[tau] = np.asarray(point * jnp.exp(z * sigma * steps))
        return out

    # -- loss (golden-equivalence surface + benchmarks) ----------------------

    def loss(self, y, cats) -> jax.Array:
        """Training loss through the estimator (same jitted fn the fit uses)."""
        self._check_fitted()
        return esrnn_loss(self.config, self.params_,
                          jnp.asarray(y), jnp.asarray(cats))

    def loss_and_grad(self, y, cats):
        self._check_fitted()
        return esrnn_loss_and_grad(self.config, self.params_,
                                   jnp.asarray(y), jnp.asarray(cats))

    # -- evaluate ------------------------------------------------------------

    def evaluate(self, data: Optional[PreparedData] = None,
                 split: str = "test", *, mesh=None) -> Dict[str, float]:
        """M4-style scores: sMAPE/MASE/OWA vs the Comb and Naive2 benchmarks.

        ``split="test"`` forecasts from train+val and scores on the test
        window (Eq. 7); ``split="val"`` forecasts from train and scores on
        the validation window.

        ``mesh`` (or ``spec.data_parallel > 1``) shards the model's
        forecast + scoring over the series axis: each device scores its own
        rows and the metric sums/counts are psum'd once -- the exact global
        masked mean, so padded rows (N not a device multiple) contribute
        nothing and the scores match single-device to float summation
        order. The Comb/Naive2 baselines are cheap numpy and stay on host.
        """
        self._check_fitted()
        data = data if data is not None else self.data_
        if data is None:
            raise NotFittedError("evaluate() needs PreparedData (fit or pass)")
        if split == "test":
            insample, target = data.val_input, data.test_target
        elif split == "val":
            insample, target = data.train, data.val_target
        else:
            raise ValueError(f"split must be 'val' or 'test', got {split!r}")
        m, h = data.seasonality, min(self.horizon, target.shape[1])
        mesh = self._resolve_mesh(mesh)
        if self._chunk_ranges(insample.shape[0]):
            return self._evaluate_chunked(
                data, insample, target, m, h, split, mesh)
        target_j = jnp.asarray(target[:, :h])
        insample_j = jnp.asarray(insample)

        if mesh is None:
            fc = self.predict(insample, data.cats)[:, :h]
            s_es = float(L.smape(jnp.asarray(fc), target_j))
            m_es = float(L.mase(jnp.asarray(fc), target_j, insample_j, m))
        else:
            from repro.sharding.series import esrnn_eval_dp

            n = insample.shape[0]
            params = self.params_
            if params["hw"].alpha_logit.shape[0] != n:
                raise ValueError(
                    f"evaluate data has {n} series but the fitted table has "
                    f"{params['hw'].alpha_logit.shape[0]}")
            params, arrays, pad = self._shard_rows(
                params,
                (jnp.asarray(insample, self.config.jdtype),
                 jnp.asarray(data.cats, self.config.jdtype),
                 target_j, insample_j),
                mesh)
            y_p, cats_p, target_p, ins_p = arrays
            # padded rows score 0 into both numerator and denominator
            rmask_p = jnp.asarray(
                np.concatenate([np.ones(n), np.zeros(pad)]).astype(np.float32))
            scores = esrnn_eval_dp(
                self.config, params, y_p, cats_p, target_p, ins_p,
                seasonality=m, mesh=mesh, row_mask=rmask_p)
            s_es, m_es = float(scores["smape"]), float(scores["mase"])

        fc_comb = np.asarray(comb_forecast(insample, h, m), np.float32)
        fc_n2 = np.asarray(naive2_forecast(insample, h, m), np.float32)

        def score(f):
            f = jnp.asarray(f)
            return (float(L.smape(f, target_j)),
                    float(L.mase(f, target_j, insample_j, m)))

        s_cb, m_cb = score(fc_comb)
        s_n2, m_n2 = score(fc_n2)
        return {
            "split": split,
            "smape": s_es, "mase": m_es,
            "owa": float(L.owa(s_es, m_es, s_n2, m_n2)),
            "smape_comb": s_cb, "mase_comb": m_cb,
            "owa_comb": float(L.owa(s_cb, m_cb, s_n2, m_n2)),
            "smape_naive2": s_n2, "mase_naive2": m_n2,
        }

    def _evaluate_chunked(self, data, insample, target, m, h, split, mesh):
        """Streamed scores: model + baselines chunk by chunk, exact terms.

        Identical math to the resident path -- sMAPE/MASE are global
        sums-over-counts and every per-series scale is row-local, so
        accumulating each chunk's ``smape_terms``/``mase_terms`` and
        dividing once reproduces the full-batch masked means. Nothing
        N-sized ever lands on device.
        """
        params, y, cats = self._resolve_inputs(
            insample, data.cats, None, host=True)
        shared = {k: v for k, v in params.items() if k != "hw"}
        tgt = np.asarray(target[:, :h], np.float32)
        acc = {k: np.zeros(4, np.float64) for k in ("esrnn", "comb", "naive2")}

        def add(name, fc, tgt_c, ins_c):
            fc_j, tgt_j = jnp.asarray(fc), jnp.asarray(tgt_c)
            s0, s1 = L.smape_terms(fc_j, tgt_j)
            m0, m1 = L.mase_terms(fc_j, tgt_j, jnp.asarray(ins_c), m)
            acc[name] += np.array(
                [float(s0), float(s1), float(m0), float(m1)])

        for lo, hi in self._chunk_ranges(y.shape[0]):
            p_c = {"hw": jax.tree_util.tree_map(
                lambda a: a[lo:hi], params["hw"]), **shared}
            fc = self._forecast_chunk(p_c, y[lo:hi], cats[lo:hi], mesh)[:, :h]
            ins_c = np.asarray(y[lo:hi])
            add("esrnn", fc, tgt[lo:hi], ins_c)
            add("comb", np.asarray(comb_forecast(ins_c, h, m), np.float32),
                tgt[lo:hi], ins_c)
            add("naive2", np.asarray(naive2_forecast(ins_c, h, m), np.float32),
                tgt[lo:hi], ins_c)

        def score(name):
            s, sc, mm, mc = acc[name]
            return 200.0 * s / max(sc, 1.0), mm / max(mc, 1.0)

        s_es, m_es = score("esrnn")
        s_cb, m_cb = score("comb")
        s_n2, m_n2 = score("naive2")
        return {
            "split": split,
            "smape": s_es, "mase": m_es,
            "owa": float(L.owa(s_es, m_es, s_n2, m_n2)),
            "smape_comb": s_cb, "mase_comb": m_cb,
            "owa_comb": float(L.owa(s_cb, m_cb, s_n2, m_n2)),
            "smape_naive2": s_n2, "mase_naive2": m_n2,
        }

    # -- rolling-origin backtest ---------------------------------------------

    def backtest(self, data: Optional[PreparedData] = None, *,
                 origins: Optional[Sequence[int]] = None,
                 y=None, cats=None, mesh=None) -> Dict:
        """Rolling-origin backtest: forecast at several origins, no refit.

        For each origin ``o`` (an observation count), the model forecasts as
        if only ``y[:, :o]`` had been observed and is scored on the next
        ``H`` actuals. All origins are read off ONE forward pass of the
        unified state-space core (``esrnn_forecast_at``): the causal HW
        recurrence means the states at position ``o-1`` ARE the re-primed
        truncated-history states, so K origins cost one dispatch, not K
        re-runs (Hewamalage et al.'s rolling-origin protocol made cheap).

        Defaults: the full fitted history (train+val+test) with origins at
        the end of train and the end of val -- i.e. the validation and test
        windows of ``evaluate``, produced by one call. ``origins`` may be
        any increasing observation counts in ``[input_size, T]``; horizons
        that run past the series end are masked out of the metrics (an
        origin with no scorable targets at all reports NaN).

        ``mesh`` (or ``spec.data_parallel > 1``) shards rows like
        ``predict``; metric sums/counts are psum'd for the exact global
        masked mean. Returns per-origin and overall sMAPE/MASE plus the
        (N, K, H) forecasts.
        """
        self._check_fitted()
        if y is None:
            data = data if data is not None else self.data_
            if data is None:
                raise NotFittedError(
                    "backtest() needs PreparedData (fit or pass data=)")
            y = np.concatenate([data.val_input, data.test_target], axis=1)
            cats = data.cats if cats is None else cats
            if origins is None:
                train_len = data.train.shape[1]
                origins = (train_len, train_len + data.horizon)
        elif origins is None:
            raise ValueError("backtest(y=...) needs explicit origins")
        chunked = bool(self._chunk_ranges(np.shape(y)[0]))
        params, y, cats = self._resolve_inputs(y, cats, None, host=chunked)
        m = max(self.config.seasonality, 1)
        h = self.horizon
        n, t_len = y.shape
        origins = tuple(int(o) for o in origins)

        # per-origin scoring windows + validity masks (numpy, host-side)
        y_np = np.asarray(y)
        target = np.zeros((n, len(origins), h), np.float32)
        tmask = np.zeros((n, len(origins), h), np.float32)
        for k, o in enumerate(origins):
            avail = max(0, min(h, t_len - o))
            target[:, k, :avail] = y_np[:, o:o + avail]
            tmask[:, k, :avail] = 1.0

        mesh = self._resolve_mesh(mesh)
        if chunked:
            # stream chunks through the one-pass multi-origin forecast; the
            # per-origin metric terms are exact sums, so they accumulate
            shared = {k: v for k, v in params.items() if k != "hw"}
            fc = np.empty((n, len(origins), h), np.float32)
            tacc = np.zeros((4, len(origins)), np.float64)
            for lo, hi in self._chunk_ranges(n):
                rows = hi - lo
                p_c = {"hw": jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a[lo:hi]), params["hw"]), **shared}
                y_c, c_c = jnp.asarray(y[lo:hi]), jnp.asarray(cats[lo:hi])
                if mesh is None:
                    fc_c = esrnn_forecast_at(
                        self.config, p_c, y_c, c_c, origins)
                    terms_c = L.rolling_metric_terms(
                        fc_c, jnp.asarray(target[lo:hi]),
                        jnp.asarray(tmask[lo:hi]), y_c, origins, m)
                else:
                    from repro.sharding.series import esrnn_backtest_dp

                    p_p, arrays, pad = self._shard_rows(
                        p_c, (y_c, c_c, jnp.asarray(target[lo:hi])), mesh)
                    y_p, c_p, t_p = arrays
                    tm_p = jnp.asarray(np.concatenate(
                        [tmask[lo:hi],
                         np.zeros((pad,) + tmask.shape[1:], np.float32)]))
                    fc_p, terms_c = esrnn_backtest_dp(
                        self.config, p_p, y_p, c_p, origins, t_p, tm_p,
                        seasonality=m, mesh=mesh)
                    fc_c = np.asarray(fc_p)[:rows]
                fc[lo:hi] = np.asarray(fc_c)
                tacc += np.stack(
                    [np.asarray(t, np.float64) for t in terms_c])
            terms = tuple(tacc)
        elif mesh is None:
            fc = esrnn_forecast_at(self.config, params, y, cats, origins)
            terms = L.rolling_metric_terms(
                fc, jnp.asarray(target), jnp.asarray(tmask), y, origins, m)
            fc = np.asarray(fc)
        else:
            from repro.sharding.series import esrnn_backtest_dp

            params_p, arrays, pad = self._shard_rows(
                params, (y, cats, jnp.asarray(target)), mesh)
            y_p, cats_p, target_p = arrays
            # padded rows are fully masked out of the metric sums/counts
            tmask_p = jnp.asarray(np.concatenate(
                [tmask, np.zeros((pad,) + tmask.shape[1:], np.float32)]))
            fc_p, terms = esrnn_backtest_dp(
                self.config, params_p, y_p, cats_p, origins, target_p,
                tmask_p, seasonality=m, mesh=mesh)
            fc = np.asarray(fc_p)[:n]

        s_sum, s_cnt, m_sum, m_cnt = (np.asarray(t, np.float64) for t in terms)

        def ratio(num, cnt):
            # an origin with no scorable targets (e.g. origin == T) is
            # unscored: NaN, not a perfect-looking 0.0
            return float(num / cnt) if cnt > 0 else float("nan")

        per_origin = [
            {"origin": o,
             "smape": ratio(200.0 * s_sum[k], s_cnt[k]),
             "mase": ratio(m_sum[k], m_cnt[k])}
            for k, o in enumerate(origins)]
        return {
            "origins": list(origins),
            "horizon": h,
            "per_origin": per_origin,
            "smape": ratio(200.0 * s_sum.sum(), s_cnt.sum()),
            "mase": ratio(m_sum.sum(), m_cnt.sum()),
            "forecasts": fc,
        }

    # -- serving -------------------------------------------------------------

    def serve(self, *, server_config=None,
              length_buckets: Tuple[int, ...] = (32, 64, 128, 256),
              batch_buckets: Tuple[int, ...] = (1, 4, 16, 64),
              mesh=None, seed_histories: bool = False):
        """Continuous-batching online server over the fitted params.

        Returns an (unstarted) :class:`repro.forecast.server.ForecastServer`
        -- ``start()`` it for threaded serving or drive ``step()``/``drain()``
        synchronously. ``seed_histories=True`` pre-registers every fitted
        series' training history in the online store (masked left-padding
        stripped), so ``observe``/history-less forecasts work for known ids
        from the first request instead of only after their first write.
        Inherits ``spec.data_parallel`` sharding like the other verbs.
        """
        self._check_fitted()
        from repro.forecast.server import ForecastServer

        srv = ForecastServer(
            self.config, self.params_, server_config=server_config,
            length_buckets=length_buckets, batch_buckets=batch_buckets,
            mesh=self._resolve_mesh(mesh))
        if seed_histories:
            if self.data_ is None:
                raise NotFittedError(
                    "serve(seed_histories=True) needs fitted data; call "
                    "fit(data) first")
            y = np.asarray(self.data_.train, np.float32)
            mask = np.asarray(self.data_.mask, np.float32)
            for sid in range(y.shape[0]):
                real = y[sid][mask[sid] > 0]
                srv.store.seed(
                    sid, real, row=srv.dispatcher.resolve_row(sid),
                    category=int(np.argmax(self.cats_[sid]))
                    if self.cats_ is not None else None)
        return srv

    # -- persistence (shared Checkpointer) -----------------------------------

    def save(self, directory: str) -> str:
        """Persist spec + params atomically via the shared Checkpointer.

        Params live under ``<directory>/params/`` so a saved estimator can
        share a directory with trainer checkpoints (``fit(ckpt_dir=...)``
        writes ``step_<n>/`` trees of (params, opt_state) at the top level;
        colliding with those would corrupt crash-resume).
        """
        self._check_fitted()
        ckpt = Checkpointer(os.path.join(directory, "params"), keep=self.spec.keep)
        step = len(self.history_["loss"]) if self.history_ else 0
        ckpt.save(step, self.params_)
        meta = {
            "spec": self.spec.to_dict(),
            "n_series": int(self.n_series_),
            "step": step,
            "cats": self.cats_.tolist() if self.cats_ is not None else None,
        }
        tmp = os.path.join(directory, _META_FILE + ".tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=2)
        os.replace(tmp, os.path.join(directory, _META_FILE))
        return directory

    @classmethod
    def load(cls, directory: str) -> "ESRNNForecaster":
        with open(os.path.join(directory, _META_FILE)) as f:
            meta = json.load(f)
        spec = ForecastSpec.from_dict(meta["spec"])
        f = cls(spec)
        template = esrnn_init(
            jax.random.PRNGKey(spec.seed), spec.model, meta["n_series"])
        _, f.params_ = Checkpointer(
            os.path.join(directory, "params")).restore(template, step=meta["step"])
        f.n_series_ = meta["n_series"]
        if meta.get("cats") is not None:
            f.cats_ = np.asarray(meta["cats"], np.float32)
        return f
