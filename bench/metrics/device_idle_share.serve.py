"""Idle share of the device under the forecast server, in %: one minus the
union of device-op intervals over a traced span from the middle of the
window of open-loop requests."""

from bench.trace import idle_share as read  # noqa: F401
