"""Driver ``serve``: open-loop requests into ``ForecastServer.submit``.

Set-up builds the server over weights from the seed (the default
``ServerConfig``, the mix's length and batch buckets), runs every
(length bucket, batch bucket) shape once, and starts the server's
scheduler thread. The requests are made in set-up; set-up's heap is then
frozen out of the collector's scans (``harness.frozen_heap``), and the
futures are not kept (``sut.Completions``), so that the client's objects do
not weigh on the server's garbage collections. The window submits each
request at its due time from this thread; a request's latency runs from its
due time to the moment the server hands its result over, so a late
generator or a full queue counts against the server, and ``serve_p95_ms``
is the 95th percentile over every request of the window, one never
answered counting as infinitely late. After the window closes, results
still owed are waited for, up to a minute, and every answer is compared
with the reference.

Mix keys: ``rate_per_s``, ``length_mean``, ``length_std``, ``length_max``,
``known_share`` (see ``bench/openloop.py``), ``length_buckets``,
``batch_buckets``, ``reference_block``.
"""

from __future__ import annotations

import time

import numpy as np

from bench import compare, harness, openloop, sut

WAIT_AFTER_CLOSE_S = 60.0


def _reference(cell, w0, reqs, buckets):
    """The reference's forecast of every request, grouped by length bucket."""
    ref = cell.reference
    hw0 = {k: np.asarray(v) for k, v in w0["hw"].items()}
    out = np.empty((len(reqs), cell.config["output_size"]), np.float64)
    groups = {}
    for i, r in enumerate(reqs):
        groups.setdefault(openloop.length_bucket(len(r.y), buckets), []).append(i)
    for bucket, idx in groups.items():
        ids = np.array([reqs[i].series_id for i in idx])
        known = ids >= 0
        rows = {k: np.where(known.reshape((-1,) + (1,) * (v.ndim - 1)),
                            v[np.maximum(ids, 0)], ref.PRIMER_HW[k])
                for k, v in hw0.items()}
        y = np.stack([openloop.shape_history(reqs[i].y, bucket) for i in idx])
        cats = np.zeros((len(idx), cell.config["n_categories"]), np.float32)
        cats[np.arange(len(idx)), [reqs[i].category for i in idx]] = 1.0
        out[idx] = ref.forecast(cell.model, {**w0, "hw": rows}, y, cats,
                                block=cell.mix["reference_block"])
    return out


def run(cell, spec_overrides=None) -> harness.Outcome:
    from repro.forecast import ESRNNForecaster, ForecastRequest
    from repro.forecast.server import ServerConfig

    cfg, mix = cell.config, cell.mix
    w0 = cell.reference.init_weights(cfg, cfg["n_series"], cell.seed)
    f = ESRNNForecaster(cell.adapter.make_spec(cfg, **(spec_overrides or {})))
    f.params_ = cell.adapter.program_params(cfg, w0)
    f.n_series_ = cfg["n_series"]
    srv = f.serve(server_config=ServerConfig(),
                  length_buckets=tuple(mix["length_buckets"]),
                  batch_buckets=tuple(mix["batch_buckets"]))
    lo = cfg["input_size"] + cfg["seasonality"]
    buckets = tuple(sorted(max(b, lo) for b in mix["length_buckets"]))
    reqs = openloop.make_requests(mix, cfg, seconds=cell.window_seconds,
                                  seed=cell.seed)

    def program_request(r):
        return ForecastRequest(y=r.y, category=r.category,
                               series_id=None if r.series_id < 0
                               else r.series_id)

    for bucket in buckets:
        pool = [r for r in reqs if openloop.length_bucket(len(r.y), buckets)
                == bucket] or reqs
        for bb in mix["batch_buckets"]:
            srv.dispatcher.run_bucket(
                [program_request(pool[i % len(pool)]) for i in range(bb)],
                bucket)
    srv.forecast_batch([program_request(r) for r in reqs[:64]])
    srv.stats.reset()
    # the client's requests are made before the window, as a client that
    # sends them would have them
    program_reqs = [program_request(r) for r in reqs]

    n, width = len(reqs), cfg["output_size"]
    late = np.zeros(n)
    with sut.record_completions(n, width) as done, \
            harness.frozen_heap() as pauses:
        srv.start()
        try:
            with cell.window():
                t0 = time.perf_counter()
                for i, r in enumerate(reqs):
                    due = t0 + r.arrival
                    wait = due - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    late[i] = time.perf_counter() - due
                    done.register(srv.submit(program_reqs[i]), i)
                close = t0 + cell.window_seconds
                wait = close - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
            done.wait(n, close + WAIT_AFTER_CLOSE_S)
        finally:
            srv.stop(drain=False)
    due_at = t0 + np.array([r.arrival for r in reqs])
    # a request never answered (refused, errored, or not within a minute
    # of the close) counts as infinitely late
    latency = np.where(np.isfinite(done.done_at), done.done_at - due_at,
                       np.inf)
    answers = done.answers
    stats = srv.stats
    counters = {"requests": stats.requests, "batches": stats.batches,
                "padded_series": stats.padded_series,
                "dispatch_s": stats.total_s, "queue_peak": stats.queue_peak,
                "xla_compiles": stats.xla_compiles,
                "truncated": stats.truncated_series}
    peak = harness.memory_peak_bytes()
    del f, srv
    harness.free_program_state()

    answered = np.isfinite(latency)
    expect = _reference(cell, w0, reqs, buckets)
    numbers = compare.forecast_errors([answers[answered]], expect[answered])
    numbers["unanswered"] = float(n - answered.sum())
    in_window = int(np.sum(done.done_at <= close))
    pct = np.percentile(latency * 1e3, [50, 95, 99])
    gen2 = [s for g, s in pauses if g == 2]
    return harness.Outcome(
        attempted=n, failed=int(n - answered.sum()), numbers=numbers,
        metrics={"serve_p95_ms": float(pct[1]),
                 "serve_requests_per_s": in_window / cell.window_seconds},
        memory_peak_bytes=peak, work=counters,
        notes=[f"serve: {n} requests at {mix['rate_per_s']} /s over "
               f"{cell.window_seconds} s; answered {int(answered.sum())}, "
               f"{in_window} in the window; latency ms p50 {pct[0]:.3f} p95 "
               f"{pct[1]:.3f} p99 {pct[2]:.3f} max "
               f"{float(np.max(latency)) * 1e3:.3f}",
               f"serve: garbage collections while serving {len(pauses)} "
               f"(full {len(gen2)}), longest pause ms "
               f"{max((s for _, s in pauses), default=0.0) * 1e3:.3f}",
               f"serve: generator lateness ms p50 "
               f"{np.percentile(late, 50) * 1e3:.3f} p99 "
               f"{np.percentile(late, 99) * 1e3:.3f} max "
               f"{late.max() * 1e3:.3f}; counters {counters}"])
