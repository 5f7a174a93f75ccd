"""The readers of program spans, on a smoke-size fit, predict and server
traced on the CPU: each reads a finite number where its spans were kept,
and None where none were, or where the program has no span facility."""

import math
import sys

import jax
import numpy as np
import pytest

from bench import harness

from .conftest import ROOT

FIT = ("host_ms_per_step.fit", "loss_sync_ms.fit")
PREDICT = ("h2d_ms.predict",)
SERVE = ("queue_wait_ms.serve", "dispatch_host_ms.serve",
         "result_wait_ms.serve")
READERS = FIT + PREDICT + SERVE


@pytest.fixture(scope="module")
def forecaster():
    from repro.forecast import ESRNNForecaster, get_smoke_spec

    f = ESRNNForecaster(get_smoke_spec("esrnn-quarterly", eval_every=2))
    f.fit(n_steps=2)            # compiles the step and the eval
    return f


@pytest.fixture(autouse=True)
def clean():
    from repro.analysis import spans

    spans.reset()
    yield
    spans.reset()


def _read(metric):
    return harness.reader(ROOT, metric)({})


def _finite(metric):
    value = _read(metric)
    assert value is not None and math.isfinite(value) and value >= 0.0, \
        (metric, value)
    return value


@pytest.fixture(scope="module")
def fit_traced(forecaster, tmp_path_factory):
    from repro.analysis import spans

    spans.reset()
    with jax.profiler.trace(str(tmp_path_factory.mktemp("fit"))):
        forecaster.fit(n_steps=4)
    return spans.summary()


@pytest.mark.parametrize("metric", FIT)
def test_fit_readers_read_a_traced_fit(fit_traced, metric, monkeypatch):
    from repro.analysis import spans

    monkeypatch.setattr(spans, "summary", lambda: fit_traced)
    _finite(metric)


def test_a_traced_fit_holds_its_steps_and_evals(fit_traced):
    s = fit_traced
    assert sum(i["k"] for i in s["fit.step"].ids) == 4
    for leaf in ("fit.index", "fit.dispatch", "fit.loss_sync"):
        assert s[leaf].count == 4
    assert s["fit.boundary"].count == 4 and s["fit.eval"].count == 2
    # the step's own time is what its three leaves leave over
    leaves = sum(s[n].total_s for n in ("fit.index", "fit.dispatch",
                                        "fit.loss_sync"))
    assert s["fit.step"].self_s == pytest.approx(
        s["fit.step"].total_s - leaves, abs=1e-9)


@pytest.mark.parametrize("metric", PREDICT)
def test_predict_readers_read_two_traced_calls(forecaster, metric, tmp_path):
    from repro.analysis import spans

    forecaster.predict()
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            forecaster.predict()
    s = spans.summary()
    # one of each phase a call, the copies' wait included
    for name in ("predict.call", "predict.inputs", "predict.forecast",
                 "predict.transfer", "predict.result"):
        assert s[name].count == 2, name
    _finite(metric)


def _serve_traced(forecaster, tmp_path, n=12):
    from repro.analysis import spans
    from repro.forecast import synthetic_request_stream

    srv = forecaster.serve()
    reqs = synthetic_request_stream(forecaster.config, n, n_known=4, seed=3,
                                    len_range=(20, 120))
    srv.forecast_batch(reqs)            # compiles the buckets it uses
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        futs = [srv.submit(r) for r in reqs]
        answered = srv.drain()
    assert all(f.done() for f in futs)
    return answered


@pytest.mark.parametrize("metric", SERVE)
def test_serve_readers_read_a_traced_drain(forecaster, metric, tmp_path):
    _serve_traced(forecaster, tmp_path)
    _finite(metric)


def test_one_queue_wait_sample_per_answered_request(forecaster, tmp_path):
    from repro.analysis import spans

    answered = _serve_traced(forecaster, tmp_path)
    s = spans.summary()
    assert answered == 12
    assert len(s["serve.queue_wait"].values) == answered
    assert all(v >= 0.0 for v in s["serve.queue_wait"].values)
    # each sample names the dispatch that served it
    batches = {i["batch"] for i in s["serve.dispatch"].ids}
    assert {i["batch"] for i in s["serve.queue_wait"].ids} == batches
    assert sum(i["rows"] for i in s["serve.dispatch"].ids) == answered


@pytest.mark.parametrize("metric", READERS)
def test_readers_read_nothing_after_reset(forecaster, metric, tmp_path):
    from repro.analysis import spans

    with jax.profiler.trace(str(tmp_path)):
        forecaster.predict()
    spans.reset()
    assert _read(metric) is None


@pytest.mark.parametrize("metric", READERS)
def test_readers_read_nothing_from_a_program_without_spans(metric,
                                                          monkeypatch):
    import repro.analysis

    monkeypatch.delattr(repro.analysis, "spans")
    monkeypatch.setitem(sys.modules, "repro.analysis.spans", None)
    assert _read(metric) is None


def test_queue_wait_is_the_95th_percentile(monkeypatch):
    from repro.analysis import spans

    waits = np.linspace(0.0, 0.1, 101)
    monkeypatch.setattr(spans, "summary", lambda: {
        "serve.queue_wait": spans.Stat(count=101, values=list(waits))})
    assert _read("queue_wait_ms.serve") == pytest.approx(95.0)
