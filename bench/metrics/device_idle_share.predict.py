"""Idle share of the device under ``ESRNNForecaster.predict``, in %: one
minus the union of device-op intervals over a traced span from the middle
of the window of predict calls."""

from bench.trace import idle_share as read  # noqa: F401
