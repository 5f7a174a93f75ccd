"""Driver ``predict``: ``ESRNNForecaster.predict`` of the whole fleet, called
again and again until the window has lasted ``--seconds``.

Each call forecasts every series from its ``val_input`` history (train plus
validation), from host arrays, as a user's batch forecast does: transfer to
the device, the forward core, and the forecasts back to the host. A seeded
sample of the calls' answers (and the last call's) is kept and compared
with the reference after the window.

Mix keys: ``history`` (the fleet's array to forecast from), ``keep_share``,
``reference_block``.
"""

from __future__ import annotations

import time

import numpy as np

from bench import compare, harness
from bench.fleet import build_fleet


def run(cell, spec_overrides=None) -> harness.Outcome:
    from repro.forecast import ESRNNForecaster

    cfg, mix = cell.config, cell.mix
    ref, adapter = cell.reference, cell.adapter
    fleet = build_fleet(cfg)
    w0 = ref.init_weights(cfg, fleet.n_series, cell.seed)
    f = ESRNNForecaster(adapter.make_spec(cfg, **(spec_overrides or {})))
    f.params_ = adapter.program_params(cfg, w0)
    f.n_series_ = fleet.n_series
    y, cats = getattr(fleet, mix["history"]), fleet.cats
    for _ in range(2):
        f.predict(y=y, cats=cats)

    keep = np.random.default_rng(harness.sub_seed(cell.seed, 11))
    kept, calls, failed = [], 0, 0
    with cell.window():
        t0 = time.perf_counter()
        while True:
            out = f.predict(y=y, cats=cats)
            calls += 1
            failed += int(np.sum(~np.isfinite(out).all(axis=1)))
            if keep.random() < mix["keep_share"]:
                kept.append(out)
            if time.perf_counter() - t0 >= cell.window_seconds:
                break
        wall = time.perf_counter() - t0
    kept.append(out)
    peak = harness.memory_peak_bytes()
    del f, out
    harness.free_program_state()

    expect = ref.forecast(cell.model, w0, y, cats,
                          block=mix["reference_block"])
    numbers = compare.forecast_errors(kept, expect)
    n = fleet.n_series
    return harness.Outcome(
        attempted=calls * n, failed=failed, numbers=numbers,
        metrics={"predict_series_per_s": calls * n / wall},
        memory_peak_bytes=peak,
        work={"calls": calls, "window_s": wall,
              "flops": calls * ref.forecast_flops(cell.model, n,
                                                  y.shape[1])},
        notes=[f"predict: {n} series x T={y.shape[1]}, {calls} calls in "
               f"{wall:.3f} s, {len(kept)} calls' answers compared"])
