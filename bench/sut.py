"""The benchmark's door into the system under test (``repro``), for every
model family.

A family's own spec, weights and leaf names pass through its adapter
(``bench/adapters/<family>.py``); what is the same for every family is
here: the optimizer state that a fit ends with, and the served results
with the moment each was handed over.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np


@contextlib.contextmanager
def capture_fit_state():
    """Keep the optimizer state that each ``ESRNNForecaster.fit`` ends with.

    ``fit`` drops the state that ``train_from_spec`` returns; this wraps
    that call for the duration of the block and appends each returned
    ``opt_state`` to the yielded list. Nothing else changes.
    """
    from repro.forecast import estimator

    states = []
    inner = estimator.train_from_spec

    def spy(*args, **kwargs):
        out = inner(*args, **kwargs)
        states.append(out["opt_state"])
        return out

    estimator.train_from_spec = spy
    try:
        yield states
    finally:
        estimator.train_from_spec = inner


class Completions:
    """Every served result, kept by the index its request was registered
    under, with the moment the server handed it over.

    The futures themselves are not kept: a client in another process would
    not hold them in the server's heap either, where each would stay for the
    collector to scan. A result set before its future was registered waits
    under the future's id, which cannot be reused meanwhile, since the
    submitting thread still holds the future.
    """

    def __init__(self, n: int, width: int):
        import threading

        self.answers = np.full((n, width), np.nan)
        self.done_at = np.full(n, np.nan)      # perf_counter; NaN: never
        self.settled = 0                        # results and errors
        self._index, self._early = {}, {}
        self._cv = threading.Condition()

    def register(self, future, i: int) -> None:
        with self._cv:
            early = self._early.pop(id(future), None)
            if early is None:
                self._index[id(future)] = i
            else:
                self._settle(i, *early)

    def _settle(self, i, t, value):
        if value is not None:
            self.answers[i] = value
            self.done_at[i] = t
        self.settled += 1
        self._cv.notify_all()

    def _handed(self, future, value):
        t = time.perf_counter()
        with self._cv:
            i = self._index.pop(id(future), None)
            if i is None:
                self._early[id(future)] = (t, value)
            else:
                self._settle(i, t, value)

    def wait(self, n: int, deadline: float) -> None:
        """Until ``n`` requests have settled, or ``deadline`` has passed."""
        with self._cv:
            while self.settled < n:
                left = deadline - time.perf_counter()
                if left <= 0 or not self._cv.wait(left):
                    return


@contextlib.contextmanager
def record_completions(n: int, width: int):
    """Yield a :class:`Completions` that every ``ForecastFuture`` result
    (and error) set inside the block reports to; nothing else changes."""
    from repro.forecast.server.engine import ForecastFuture

    rec = Completions(n, width)
    inner_result = ForecastFuture.set_result
    inner_error = ForecastFuture.set_exception

    def set_result(self, value):
        rec._handed(self, value)
        inner_result(self, value)

    def set_exception(self, err):
        rec._handed(self, None)
        inner_error(self, err)

    ForecastFuture.set_result = set_result
    ForecastFuture.set_exception = set_exception
    try:
        yield rec
    finally:
        ForecastFuture.set_result = inner_result
        ForecastFuture.set_exception = inner_error
