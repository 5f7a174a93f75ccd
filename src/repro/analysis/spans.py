"""Program spans: named phases of the program on the profiler's clock.

``span(name, **ids)`` marks a phase. While a profiler trace is active
(``jax.profiler.start_trace`` or ``jax.profiler.trace``), a leaf span is a
``jax.profiler.TraceAnnotation``, so it lies in the trace beside the device
ops on the same clock, and every span is also kept in memory as a
:class:`Record`: name, start and end (``perf_counter``), the enclosing
record, its ids and its thread. ``summary()`` reduces the records per name.
With no trace active a span costs one ``TraceAnnotation.is_enabled()``
check and keeps nothing.

Only leaves become profiler events. A reader of the trace names a stretch of
time by the host event that overlaps it the most; an enclosing event would
tie with its children, and win, since it starts first. Parents
(``leaf=False``) are kept in memory only; their self time is their duration
less what their children cover.

A loop that opens several spans a step asks ``recording()`` once and hands
the answer to each as ``on``, so that with no trace active its step pays
one check. ``sample(name, value, **ids)`` keeps one measured value, such as
one request's queue wait, under the same rule as a span.

The kept records are process-wide, as the profiler's trace is: a span on
any thread lands in the one buffer, and ``reset()`` clears it for every
caller. Parentage is per thread.

The names are the contract with the benchmark's readers (``bench/metrics``):
``fit.*`` in :mod:`repro.train.trainer`, ``predict.*`` in
:mod:`repro.forecast.estimator`, ``serve.*`` in
:mod:`repro.forecast.server.engine` and :mod:`repro.forecast.serving`.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Dict, List, Optional

import jax

log = logging.getLogger("repro.analysis.spans")

# records (and, separately, samples) kept between resets; a trace long
# enough to fill the buffer keeps its first MAX_RECORDS and warns once
MAX_RECORDS = 1 << 17

_Annotation = jax.profiler.TraceAnnotation
_records: List["Record"] = []
_samples: List["Sample"] = []
_full_lock = threading.Lock()
_warned = False
_local = threading.local()


class Record:
    """One span: entered with ``with``; ``seconds`` once it has ended."""

    __slots__ = ("name", "ids", "parent", "thread", "start", "end",
                 "child_s", "_leaf", "_ann")

    def __init__(self, name: str, ids: dict, leaf: bool):
        self.name, self.ids = name, ids
        self._leaf = leaf
        self.parent: Optional[Record] = None
        self.thread = threading.get_ident()
        self.start = self.end = 0.0
        self.child_s = 0.0
        self._ann = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def self_s(self) -> float:
        return self.seconds - self.child_s

    def __enter__(self) -> "Record":
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        if self._leaf:
            self._ann = _Annotation(self.name)
            self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _stack().pop()
        if self.parent is not None:
            self.parent.child_s += self.seconds
        _keep(_records, self)
        return False


@dataclasses.dataclass
class Sample:
    name: str
    value: float
    ids: dict


class _Off:
    """The span of a phase that nothing records: enters as None."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()


def recording() -> bool:
    """Whether spans are kept now, that is, a profiler trace is active."""
    return _Annotation.is_enabled()


def span(name: str, *, leaf: bool = True, on: Optional[bool] = None, **ids):
    """A context manager for the phase ``name``, yielding its
    :class:`Record` while recording, else None. ``leaf=False`` marks a span
    that encloses others: it is kept in memory and not written to the
    profiler's trace. ``on`` is :func:`recording`'s answer where the caller
    already has it; left None, the span asks itself."""
    if on is None:
        on = _Annotation.is_enabled()
    return Record(name, ids, leaf) if on else _OFF


def sample(name: str, value: float, **ids) -> None:
    """Keep one measured value under ``name`` while recording."""
    if _Annotation.is_enabled():
        _keep(_samples, Sample(name, float(value), ids))


@dataclasses.dataclass
class Stat:
    """What ``summary()`` holds for one name: of a span, its count, total
    and self seconds; of a sample, its count and values. ``ids`` are the
    records' or samples' ids, in the order they ended or were taken."""

    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0
    values: List[float] = dataclasses.field(default_factory=list)
    ids: List[dict] = dataclasses.field(default_factory=list)


def summary() -> Dict[str, Stat]:
    """Per name, the spans and samples kept since the last ``reset()``."""
    out: Dict[str, Stat] = {}
    for r in list(_records):
        s = out.setdefault(r.name, Stat())
        s.count += 1
        s.total_s += r.seconds
        s.self_s += r.self_s
        s.ids.append(r.ids)
    for x in list(_samples):
        s = out.setdefault(x.name, Stat())
        s.count += 1
        s.values.append(x.value)
        s.ids.append(x.ids)
    return out


def reset() -> None:
    """Drop every kept span and sample."""
    global _warned
    with _full_lock:
        _records.clear()
        _samples.clear()
        _warned = False


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _keep(buf: list, item) -> None:
    # list.append is atomic under the GIL, so the common path takes no lock;
    # threads racing at the bound may overshoot it by a few
    if len(buf) < MAX_RECORDS:
        buf.append(item)
        return
    global _warned
    with _full_lock:
        if not _warned:
            _warned = True
            log.warning("span buffer full (%d): later spans and samples are "
                        "not kept until reset()", MAX_RECORDS)
