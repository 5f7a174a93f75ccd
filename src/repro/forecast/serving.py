"""Bucketed forecast dispatch: pad-to-bucket batching + jit-cache reuse.

Mirrors the prefill/decode structure of ``repro.launch.serve``, adapted to
forecasting: the "prefill" is the HW-smooth + dilated-LSTM pass over the
request's history, the "decode" is the seasonal de-normalization of the H
output steps. Requests arrive with ragged history lengths and ragged batch
sizes; XLA recompiles per shape, so a naive server would compile once per
distinct (batch, length) -- fatal under heavy traffic. Instead:

* **length buckets**: each request's history is snapped to the smallest
  bucket >= its length (left-padded with its first value, exactly the
  section-8.1 variable-length convention of ``data.pipeline``); longer
  histories keep their most recent ``max(bucket)`` observations, counted
  in ``ServeStats.truncated_series`` (the forecast then conditions on the
  truncated tail -- a real, visible serving decision, not a silent clamp),
* **batch buckets**: each group is padded up to the smallest batch bucket by
  repeating the last row (extra rows dropped on return),

so the jit cache holds at most ``len(length_buckets) * len(batch_buckets)``
entries and every subsequent request is a cache hit. ``ServeStats`` reports
the hit/compile split to prove the reuse, plus per-request latency
percentiles and the queue's high-water mark for the continuous-batching
front end.

The module splits serving into two layers:

* :class:`BucketDispatcher` -- the shared kernel-dispatch core: history
  shaping, per-request HW-row resolution against a host-side table
  snapshot, bucket-padded batched dispatch through
  ``esrnn_forecast``/``esrnn_forecast_dp``. Both servers drive it.
* :class:`BatchedForecastServer` -- **deprecated** thin wrapper over the
  dispatcher's synchronous batch surface. The production front end is
  :class:`repro.forecast.server.ForecastServer`, the continuous-batching
  request loop with online ``observe`` state ingestion; scripted/batch
  workloads call :meth:`BucketDispatcher.forecast_batch` directly.

Per-series HW parameters are looked up by ``series_id`` for series seen at
fit time; unknown series fall back to a primer row (alpha = gamma = 0.5,
flat seasonality -- the paper's section-3.3 initialization), which is the
cold-start behaviour of a real forecast service.

Sharding interaction: the fitted table may arrive sharded across a series
mesh (a ``data_parallel`` fit). Request rows are arbitrary (any mix of
known ids and cold-start primers), so resolving them directly against the
*device* table would gather the whole sharded table through the mesh on
every request. Instead the dispatcher snapshots the extended table (fitted
rows + primer row) to **host memory once** at construction; per-request
resolution is then a numpy row gather, and only the gathered ``(B, ...)``
rows ever move to devices -- row-sharded over the serving ``mesh`` when one
is passed, which runs the forecast itself under ``shard_map``
(``esrnn_forecast_dp``) with the batch padded to the device multiple.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import time
import warnings
from functools import partial
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import spans
from repro.analysis.recompile import CompileCounter
from repro.core.esrnn import ESRNNConfig, esrnn_forecast, esrnn_init

log = logging.getLogger("repro.forecast.serving")

# latency samples kept for the percentile estimate (FIFO window; sustained
# runs see the *recent* distribution, not a forever-average)
_LATENCY_WINDOW = 65536


@dataclasses.dataclass
class ForecastRequest:
    """One series to forecast: raw history + category + optional identity.

    ``y=None`` is allowed when ``series_id`` is set and the serving layer
    tracks that series' history online (the continuous server's ``observe``
    verb); the dispatcher itself requires a resolved history.
    """

    y: Optional[np.ndarray] = None   # (T,) strictly positive history
    category: int = 0
    series_id: Optional[int] = None  # row in the fitted per-series table


@dataclasses.dataclass
class ServeStats:
    """Serving counters + latency/queue telemetry.

    Counter fields are plain ints (single-writer: the dispatching thread);
    ``latencies_s`` is a bounded FIFO window over per-request latencies
    (submit -> result for the continuous server, batch wall-time per
    request for the synchronous wrapper).
    """

    requests: int = 0
    batches: int = 0
    compiles: int = 0                # bucket-grid shapes the dispatcher
                                     # intended to compile
    xla_compiles: int = 0            # backend compiles XLA actually ran
                                     # while a dispatch was armed (ground
                                     # truth; catches compiles the bucket
                                     # accounting cannot see)
    compile_budget: Optional[int] = None  # declared bound: len(length
                                     # buckets) x len(batch buckets)
    cache_hits: int = 0
    padded_series: int = 0           # batch-padding rows added (wasted lanes)
    truncated_series: int = 0        # histories longer than the largest
                                     # length bucket (served on the tail)
    observes: int = 0                # online observations absorbed
    write_batches: int = 0           # batched write-absorption passes
    finetunes: int = 0               # idle incremental fine-tune runs
    queue_peak: int = 0              # high-water mark of the request queue
    total_s: float = 0.0
    latencies_s: Deque[float] = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=_LATENCY_WINDOW),
        repr=False)

    @property
    def requests_per_s(self) -> float:
        # guard: a zero-elapsed window (no timed work yet, or a clock with
        # coarse resolution on a trivial batch) reports 0, not a ZeroDivision
        return self.requests / self.total_s if self.total_s > 0 else 0.0

    def record_latency(self, seconds: float) -> None:
        self.latencies_s.append(seconds)

    def note_queue_depth(self, depth: int) -> None:
        self.queue_peak = max(self.queue_peak, depth)

    def reset(self) -> None:
        """Zero every counter and drop the latency window.

        Benchmarks call this after the jit-cache warm-up pass so that
        compile-time latencies never pollute the measured distribution (the
        jit cache itself survives -- only the telemetry resets).
        """
        self.requests = self.batches = self.compiles = self.cache_hits = 0
        self.xla_compiles = 0        # compile_budget survives: it is a
                                     # declaration, not a counter
        self.padded_series = self.truncated_series = 0
        self.observes = self.write_batches = self.finetunes = 0
        self.queue_peak = 0
        self.total_s = 0.0
        self.latencies_s.clear()

    def latency_percentiles(self) -> Dict[str, float]:
        """p50/p95/p99 of the recorded request latencies, in milliseconds.

        NaN (not 0.0) when nothing has been recorded -- an empty window must
        not read as a perfect latency.
        """
        if not self.latencies_s:
            nan = float("nan")
            return {"p50_ms": nan, "p95_ms": nan, "p99_ms": nan}
        lat_ms = np.asarray(self.latencies_s, np.float64) * 1e3
        p50, p95, p99 = np.percentile(lat_ms, [50.0, 95.0, 99.0])
        return {"p50_ms": float(p50), "p95_ms": float(p95),
                "p99_ms": float(p99)}


def _pick_bucket(value: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= value; the largest bucket when value exceeds all.

    The overflow case means *truncation* for length bucketing (only the most
    recent ``buckets[-1]`` observations are served) -- callers that route
    histories through this must count it (``ServeStats.truncated_series``)
    so the clamp is visible in telemetry rather than silent.
    """
    for b in buckets:
        if value <= b:
            return b
    return buckets[-1]


class BucketDispatcher:
    """The shared serving core: shape, resolve, and dispatch one bucket.

    Owns the jit-cache discipline (length x batch bucket grid), the
    host-side HW-table snapshot, and the sharded/single-device forecast
    callable. Both the synchronous :class:`BatchedForecastServer` and the
    continuous-batching ``repro.forecast.server.ForecastServer`` drive it;
    neither re-implements any batching math.
    """

    def __init__(
        self,
        config: ESRNNConfig,
        params,
        *,
        length_buckets: Tuple[int, ...] = (32, 64, 128, 256),
        batch_buckets: Tuple[int, ...] = (1, 4, 16, 64),
        max_batch: Optional[int] = None,
        mesh=None,
        stats: Optional[ServeStats] = None,
        compile_budget: Optional[int] = None,
    ):
        self.config = config
        self.mesh = mesh if mesh is not None and mesh.devices.size > 1 else None
        min_len = config.input_size + max(config.seasonality, 1)
        self.length_buckets = tuple(sorted(max(b, min_len) for b in length_buckets))
        if self.mesh is not None:
            # sharded serving: snap the buckets up to the device multiple at
            # construction so every padded chunk still lands ON a bucket --
            # max_batch and the jit-cache bound keep their documented
            # meaning (a post-hoc pad in the hot path would exceed both)
            d = self.mesh.devices.size
            batch_buckets = {b + (-b) % d for b in batch_buckets}
        self.batch_buckets = tuple(sorted(batch_buckets))
        # a chunk must always fit the largest batch bucket
        self.max_batch = min(max_batch or self.batch_buckets[-1],
                             self.batch_buckets[-1])
        self.stats = stats if stats is not None else ServeStats()
        # the declared jit-cache bound the recompile sentinel audits against;
        # ServeStats.xla_compiles counts what XLA actually did while armed
        self.compile_budget = (
            compile_budget if compile_budget is not None
            else len(self.length_buckets) * len(self.batch_buckets))
        self.stats.compile_budget = self.compile_budget
        self._xla_counter = CompileCounter(stats=self.stats)
        self._seen_shapes = set()
        self._warned_truncation = False
        self.set_params(params)
        if self.mesh is None:
            # esrnn_forecast is already jitted (cfg static); XLA caches per
            # (B, L) shape -- the bucket discipline keeps that cache small.
            self._forecast = partial(esrnn_forecast, self.config)
        else:
            from repro.sharding.series import esrnn_forecast_dp

            # sharded serving: per-series rows device-local under shard_map
            # (jit of the shard_map caches per shape exactly the same way)
            self._forecast = jax.jit(partial(
                esrnn_forecast_dp, self.config, mesh=self.mesh))

    # -- params / host table -------------------------------------------------

    def set_params(self, params) -> None:
        """(Re)install params and rebuild the host-side HW-table snapshot.

        Called at construction and again whenever the serving params change
        in place (the idle fine-tune hook) -- the snapshot must never go
        stale relative to the table the batched forecast closes over.
        """
        from repro.train.host_table import HostStateTable

        self.params = params
        self.n_known = params["hw"].alpha_logit.shape[0]
        # per-series table extended by one primer row for cold-start series
        # (section 3.3 initialization); row n_known == "unknown series".
        # Host-side by construction: the fitted table may be sharded across
        # a series mesh, and per-request row resolution (arbitrary
        # known/primer mixes) against the device table would re-gather the
        # whole sharded table per request. The snapshot is a HostStateTable
        # + primer *view* (``ExtendedHWView``) rather than a concatenated
        # second copy -- zero-copy when the fitted leaves are already host
        # numpy (a chunked fit / chunked checkpoint), one D2H otherwise;
        # only the gathered (B, ...) rows ever go to devices.
        primer = esrnn_init(jax.random.PRNGKey(0), self.config, 1)
        self._host_table = HostStateTable.from_hw(params["hw"])
        self._hw_table = self._host_table.extended(primer["hw"])

    # -- shaping -------------------------------------------------------------

    def pick_length_bucket(self, n_obs: int) -> int:
        """Length bucket for a history of ``n_obs``, counting truncation."""
        b = _pick_bucket(n_obs, self.length_buckets)
        if n_obs > self.length_buckets[-1]:
            self.stats.truncated_series += 1
            if not self._warned_truncation:
                self._warned_truncation = True
                log.warning(
                    "history of %d observations exceeds the largest length "
                    "bucket (%d); serving on the most recent %d (counted in "
                    "ServeStats.truncated_series; further truncations are "
                    "counted silently)", n_obs, b, b)
        return b

    def shape_history(self, y: np.ndarray, bucket: int) -> np.ndarray:
        y = np.asarray(y, np.float32)
        if len(y) >= bucket:
            return y[-bucket:]
        pad = np.full(bucket - len(y), y[0], np.float32)
        return np.concatenate([pad, y])

    def resolve_row(self, series_id: Optional[int]) -> int:
        """Extended-table row for a request: fitted row or the primer row."""
        if series_id is not None and 0 <= series_id < self.n_known:
            return int(series_id)
        return self.n_known

    def hw_rows(self, requests: Sequence[ForecastRequest]):
        """Per-request HW rows: fitted rows for known ids, primer otherwise.

        One vectorized gather from the extended table (fitted rows + primer
        row) -- no per-request device ops on the serving hot path.
        """
        idx = np.asarray([self.resolve_row(r.series_id) for r in requests])
        # numpy gather through the host view: no device op, and in
        # particular no cross-device gather of a mesh-sharded fitted table
        return self._hw_table.rows(idx)

    # -- dispatch ------------------------------------------------------------

    def run_bucket(self, requests: List[ForecastRequest], bucket: int):
        """Forecast one length-bucket group, padded to a batch bucket.

        Every request must carry a resolved history (``y`` not None) -- the
        online-store resolution happens upstream in the continuous server.
        Its phases are the spans ``serve.shape`` (pad, one-hot, HW rows),
        ``serve.launch`` (transfer and dispatch) and ``serve.result`` (the
        read back, which waits on the device).
        """
        n = len(requests)
        # with a mesh, the buckets were snapped to the device multiple at
        # construction, so bb always divides the mesh evenly
        bb = _pick_bucket(n, self.batch_buckets)
        padded = requests + [requests[-1]] * (bb - n)
        self.stats.padded_series += bb - n

        with spans.span("serve.shape"):
            y = np.stack([self.shape_history(r.y, bucket) for r in padded])
            cats = np.zeros((bb, self.config.n_categories), np.float32)
            for row, r in enumerate(padded):
                # out-of-range category -> all-zero one-hot (cold start, like
                # an unknown series_id); never let one bad request fail the
                # batch
                if 0 <= r.category < self.config.n_categories:
                    cats[row, r.category] = 1.0

            hw = self.hw_rows(padded)
            params = dict(self.params, hw=hw)

        shape = (bb, bucket)
        if shape in self._seen_shapes:
            self.stats.cache_hits += 1
        else:
            self._seen_shapes.add(shape)
            self.stats.compiles += 1
        # armed sentinel: every backend compile XLA runs inside this block
        # lands in ServeStats.xla_compiles, including ones the bucket
        # accounting above cannot see (the fc[:n] slice family was exactly
        # such an invisible compile per distinct partial fill)
        with self._xla_counter:
            with spans.span("serve.launch"):
                fc = self._forecast(params, jnp.asarray(y), jnp.asarray(cats))
            self.stats.batches += 1
            # strip the batch padding on the HOST copy: fc[:n] on the device
            # array is a jitted slice op that XLA compiles once per distinct
            # partial fill n -- an unbounded compile family (~tens of ms
            # each) on the latency path. Transferring padded rows is cheap.
            with spans.span("serve.result"):
                out = np.asarray(fc)[:n]
        return out

    def forecast_batch(
        self, requests: Sequence[ForecastRequest]
    ) -> List[np.ndarray]:
        """Serve a batch of ragged requests synchronously, in order.

        The scripted/batch entry point: group by length bucket, chunk by
        ``max_batch``, dispatch each chunk through :meth:`run_bucket`,
        return one (H,) forecast per request. Blocks until the whole batch
        is back; per-request latency is the batch wall-time amortized over
        the batch (the continuous server records real arrival times).
        """
        t0 = time.perf_counter()
        groups: Dict[int, List[int]] = {}
        for i, r in enumerate(requests):
            if r.y is None:
                raise ValueError(
                    "ForecastRequest.y is required for batch serving; "
                    "history-less series_id requests need the online "
                    "ForecastServer (repro.forecast.server)")
            groups.setdefault(
                self.pick_length_bucket(len(r.y)), []).append(i)

        out: List[Optional[np.ndarray]] = [None] * len(requests)
        for bucket, idxs in sorted(groups.items()):
            for lo in range(0, len(idxs), self.max_batch):
                chunk = idxs[lo:lo + self.max_batch]
                fc = self.run_bucket([requests[i] for i in chunk], bucket)
                for j, i in enumerate(chunk):
                    out[i] = fc[j]
        dt = time.perf_counter() - t0
        self.stats.requests += len(requests)
        self.stats.total_s += dt
        if requests:
            # batch wall-time attributed to each request: this surface has no
            # per-request arrival times (the continuous server does)
            per_req = dt / len(requests)
            for _ in requests:
                self.stats.record_latency(per_req)
        return out  # type: ignore[return-value]


class BatchedForecastServer:
    """Deprecated synchronous wrapper -- use the dispatcher or ForecastServer.

    Kept one release for callers of the historical surface: constructing one
    emits a :class:`DeprecationWarning` and every call delegates to a
    :class:`BucketDispatcher` (batch workloads call its
    :meth:`~BucketDispatcher.forecast_batch` directly; request loops want
    :class:`repro.forecast.server.ForecastServer`).
    """

    def __init__(
        self,
        config: ESRNNConfig,
        params,
        *,
        length_buckets: Tuple[int, ...] = (32, 64, 128, 256),
        batch_buckets: Tuple[int, ...] = (1, 4, 16, 64),
        max_batch: Optional[int] = None,
        mesh=None,
    ):
        warnings.warn(
            "BatchedForecastServer is deprecated: use "
            "repro.forecast.server.ForecastServer for request serving, or "
            "BucketDispatcher.forecast_batch for synchronous batch "
            "workloads", DeprecationWarning, stacklevel=2)
        self._dispatch = BucketDispatcher(
            config, params, length_buckets=length_buckets,
            batch_buckets=batch_buckets, max_batch=max_batch, mesh=mesh)

    # the dispatcher owns the state; expose the historical surface
    @property
    def config(self):
        return self._dispatch.config

    @property
    def params(self):
        return self._dispatch.params

    @property
    def mesh(self):
        return self._dispatch.mesh

    @property
    def stats(self) -> ServeStats:
        return self._dispatch.stats

    @property
    def length_buckets(self):
        return self._dispatch.length_buckets

    @property
    def batch_buckets(self):
        return self._dispatch.batch_buckets

    @property
    def max_batch(self):
        return self._dispatch.max_batch

    @property
    def compile_budget(self):
        return self._dispatch.compile_budget

    @property
    def n_known(self):
        return self._dispatch.n_known

    @property
    def _hw_table(self):
        return self._dispatch._hw_table

    def _hw_rows(self, requests):
        return self._dispatch.hw_rows(requests)

    def _shape_history(self, y, bucket):
        return self._dispatch.shape_history(y, bucket)

    def forecast_batch(
        self, requests: Sequence[ForecastRequest]
    ) -> List[np.ndarray]:
        return self._dispatch.forecast_batch(requests)


def synthetic_request_stream(
    config: ESRNNConfig, n_requests: int, *, n_known: int = 0, seed: int = 0,
    len_range: Tuple[int, int] = (20, 200),
) -> List[ForecastRequest]:
    """Ragged request stream for smoke/benchmark runs (lognormal level walks).

    Deterministic in ``seed``: the same (config, n_requests, n_known, seed,
    len_range) produces bit-identical histories, categories and series-id
    assignments -- benchmark baselines and continuous-batching runs replay
    the exact same offered load.
    """
    rng = np.random.default_rng(seed)
    m = max(config.seasonality, 1)
    reqs = []
    for i in range(n_requests):
        t = int(rng.integers(*len_range))
        drift = rng.normal(0, 0.002, t).cumsum()
        seas = np.tile(np.exp(rng.normal(0, 0.08, m)), t // m + 1)[:t]
        y = np.exp(np.log(rng.uniform(50, 500)) + drift) * seas
        y = np.maximum(y * np.exp(rng.normal(0, 0.03, t)), 1e-3)
        sid = int(rng.integers(0, n_known)) if n_known and rng.random() < 0.5 else None
        reqs.append(ForecastRequest(
            y=y.astype(np.float32),
            category=int(rng.integers(0, config.n_categories)),
            series_id=sid,
        ))
    return reqs
