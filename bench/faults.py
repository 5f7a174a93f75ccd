"""Faults planted underneath the timed path, to show ``correct`` catches them.

Each is a context manager that patches the program for the duration of a
run: ``bench/tests/test_bench_faults.py`` drives whole runs with them on the
CPU, and ``bench/calibrate.py`` reads them on the chip. The benchmark's own
runs never use them.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name, wrap):
    inner = getattr(module, name)
    setattr(module, name, wrap(inner))
    try:
        yield
    finally:
        setattr(module, name, inner)


def _wrap_step(change):
    def make(inner):
        def make_step_fn(*args, **kwargs):
            return change(inner(*args, **kwargs))
        return make_step_fn
    return make


def state_unchanged():
    """Every training step returns the parameters and optimizer state it
    was given (its loss is still computed)."""
    from repro.train import trainer

    def change(step):
        def fn(params, opt_state, idx):
            return params, opt_state, step(params, opt_state, idx)[2]
        return fn
    return _patched(trainer, "make_step_fn", _wrap_step(change))


def half_batch():
    """Every training step leaves out the second half of its batch: the
    mean is taken over the first half only."""
    from repro.train import trainer

    def change(step):
        def fn(params, opt_state, idx):
            return step(params, opt_state, idx[: idx.shape[0] // 2])
        return fn
    return _patched(trainer, "make_step_fn", _wrap_step(change))


def _altered(factor):
    def wrap(inner):
        def esrnn_forecast(*args, **kwargs):
            out = inner(*args, **kwargs)
            return out.at[0, 0].multiply(factor)
        return esrnn_forecast
    return wrap


def altered_predict_answer(factor: float = 1.01):
    """``ESRNNForecaster.predict`` returns the first series' first forecast
    off by ``factor``."""
    from repro.forecast import estimator

    return _patched(estimator, "esrnn_forecast", _altered(factor))


def altered_served_answer(factor: float = 1.01):
    """Each served batch returns its first request's first forecast off by
    ``factor`` (the dispatcher binds the forecast when it is built)."""
    from repro.forecast import serving

    return _patched(serving, "esrnn_forecast", _altered(factor))
