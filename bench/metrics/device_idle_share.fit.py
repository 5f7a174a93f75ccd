"""Idle share of the device under the trainer loop (``train/trainer.py``,
``train/engine.py``), in %: one minus the union of device-op intervals over
a traced span from the middle of the fit window."""

from bench.trace import idle_share as read  # noqa: F401
