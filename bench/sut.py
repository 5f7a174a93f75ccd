"""The benchmark's one door into the system under test (``repro``).

Everything the drivers hand to the program or read back from it passes
through here: the spec built from a configuration file, the benchmark's
weights and fleet put in the program's own types, and the program's
parameter and optimizer trees read back under the benchmark's leaf names.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

# configuration keys that map one to one onto ForecastSpec / ESRNNConfig
SPEC_KEYS = ("seasonality", "input_size", "output_size", "hidden_size",
             "n_categories", "tau", "rnn_lr", "hw_lr", "clip_norm", "dtype",
             "precision", "use_pallas", "sparse_adam", "scan_steps", "head")


def make_spec(config: dict, **overrides):
    """The program's ForecastSpec for a configuration, as the file states it."""
    from repro.forecast import get_spec

    kw = {k: config[k] for k in SPEC_KEYS}
    kw["dilations"] = tuple(tuple(b) for b in config["dilations"])
    kw.update(overrides)
    return get_spec(config["spec"], **kw)


def program_params(config: dict, w: dict) -> dict:
    """The benchmark's weights (``bench.weights`` layout) as program params."""
    from repro.core.holt_winters import HWParams

    hw = HWParams(alpha_logit=w["hw"]["alpha_logit"],
                  gamma_logit=w["hw"]["gamma_logit"],
                  init_seas_logit=w["hw"]["seas_logit"])
    layers = iter(w["lstm"])
    rnn = [[dict(next(layers)) for _ in block] for block in config["dilations"]]
    head = {k: w[k] for k in ("dense_w", "dense_b", "out_w", "out_b")}
    return {"hw": hw, "rnn": rnn, "head": head}


def program_leaves(tree) -> dict:
    """A program params-shaped tree (params, or an Adam moment) as the flat
    ``{name: array}`` view of ``bench.weights.leaves``."""
    out = {"hw.alpha_logit": tree["hw"].alpha_logit,
           "hw.gamma_logit": tree["hw"].gamma_logit,
           "hw.seas_logit": tree["hw"].init_seas_logit}
    layer = 0
    for block in tree["rnn"]:
        for cell in block:
            for k in ("wx", "wh", "b"):
                out[f"lstm{layer}.{k}"] = cell[k]
            layer += 1
    for k in ("dense_w", "dense_b", "out_w", "out_b"):
        out[k] = tree["head"][k]
    return out


def program_data(config: dict, fleet):
    """A ``bench.fleet.Fleet`` as the program's PreparedData (equalized)."""
    from repro.data.pipeline import PreparedData

    return PreparedData(
        frequency=fleet.frequency, seasonality=config["seasonality"],
        horizon=config["output_size"],
        train=fleet.train, val_input=fleet.val_input,
        val_target=fleet.val_target, test_target=fleet.test_target,
        mask=np.ones_like(fleet.train), cats=fleet.cats,
        categories=fleet.categories)


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
