"""Recompile sentinel: count XLA backend compiles against a declared budget.

The serving path's whole performance story rests on a *bounded* jit cache:
``len(length_buckets) x len(batch_buckets)`` executables, every later
request a cache hit. ``ServeStats.compiles`` counts bucket-grid shapes the
dispatcher *intended* to compile -- but the PR-6 ``fc[:n]`` regression
showed the dangerous failure mode is the compile the dispatcher does NOT
know about: a device-array slice per distinct partial fill spawned an
unbounded executable family while the bucket counters stayed green.

This module counts what XLA actually does. A process-wide listener on the
``/jax/core/compile/backend_compile_duration`` monitoring event bumps every
*armed* :class:`CompileCounter`; the serving dispatcher arms one around each
dispatch so ``ServeStats.xla_compiles`` is ground truth, and the pytest
fixture ``compile_sentinel`` (tests/conftest.py) wraps any suspect region in
:meth:`CompileCounter.expect` so a hot path exceeding its compile budget
fails the test instead of silently burning latency in production.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Set

# the monitoring event jax records once per XLA backend compilation
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_active: Set["CompileCounter"] = set()
_lock = threading.Lock()
_listener_installed = False


class CompileBudgetExceeded(AssertionError):
    """A hot path compiled more executables than its declared budget."""


def _install_listener() -> None:
    """Register the process-wide compile listener once (idempotent)."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        from jax._src import monitoring

        def _on_duration(event: str, duration: float, **kwargs) -> None:
            if event != COMPILE_EVENT:
                return
            with _lock:
                counters = list(_active)
            for counter in counters:
                counter._bump(duration)

        monitoring.register_event_duration_secs_listener(_on_duration)
        _listener_installed = True


class CompileCounter:
    """Context manager counting XLA backend compiles while armed.

    Counts every backend compile in the process during the armed window
    (that is the point: the ``fc[:n]`` family was invisible to any
    per-callable accounting) and sums their durations in ``seconds``.
    Optionally mirrors each count into a
    :class:`~repro.forecast.serving.ServeStats` via ``stats`` so serving
    telemetry reports true XLA compiles next to its bucket-grid intent.
    """

    def __init__(self, stats=None):
        self.count = 0
        self.seconds = 0.0
        self._stats = stats

    def _bump(self, duration: float) -> None:
        self.count += 1
        self.seconds += duration
        if self._stats is not None:
            self._stats.xla_compiles += 1

    def __enter__(self) -> "CompileCounter":
        _install_listener()
        with _lock:
            _active.add(self)
        return self

    def __exit__(self, *exc) -> None:
        with _lock:
            _active.discard(self)

    @contextlib.contextmanager
    def expect(self, budget: int, what: str = "hot path"):
        """Fail if the wrapped region compiles more than ``budget`` times.

        The sentinel form the tests use::

            with counter.expect(budget=len(grid), what="serving waves"):
                drive_requests()
        """
        before = self.count
        yield self
        grew = self.count - before
        if grew > budget:
            raise CompileBudgetExceeded(
                f"{what} compiled {grew} XLA executables, over its declared "
                f"budget of {budget}: an unbounded compile family on a hot "
                f"path (the PR-6 fc[:n] bug class)")


def check_compile_budget(stats, budget: Optional[int] = None) -> int:
    """Assert a ServeStats' true-XLA compile count is within its budget.

    ``budget`` defaults to ``stats.compile_budget`` (the dispatcher declares
    it from the bucket grid at construction). Returns the compile count on
    success; raises :class:`CompileBudgetExceeded` otherwise.
    """
    if budget is None:
        budget = getattr(stats, "compile_budget", None)
    if budget is None:
        raise ValueError("no compile budget declared on stats or passed in")
    if stats.xla_compiles > budget:
        raise CompileBudgetExceeded(
            f"serving compiled {stats.xla_compiles} XLA executables, over "
            f"the declared bucket-grid budget of {budget} "
            f"({stats.compiles} intended bucket compiles, "
            f"{stats.cache_hits} cache hits)")
    return stats.xla_compiles
