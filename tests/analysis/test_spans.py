"""The span facility: nothing kept with the profiler off; under a trace,
self time, samples' ids, and only leaves in the profiler's own trace."""

import glob
import os
import threading
import time

import jax
import pytest

from repro.analysis import spans


@pytest.fixture(autouse=True)
def clean():
    spans.reset()
    yield
    spans.reset()


def _nest():
    with spans.span("t.outer", leaf=False, step=3) as outer:
        with spans.span("t.inner", i=0):
            time.sleep(0.002)
        time.sleep(0.001)
        with spans.span("t.inner", i=1):
            time.sleep(0.002)
    spans.sample("t.wait", 0.25, batch=7)
    spans.sample("t.wait", 0.5, batch=8)
    return outer


def test_profiler_off_keeps_nothing():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    outer = _nest()
    assert outer is None
    with spans.span("t.leaf") as leaf:
        pass
    assert leaf is None
    assert spans.summary() == {}


def test_a_step_asks_once(tmp_path):
    """``on`` carries one ``recording()`` answer to every span of a step:
    a step begun before the trace keeps nothing of itself."""
    assert not spans.recording()
    on = spans.recording()
    with jax.profiler.trace(str(tmp_path)):
        assert spans.recording()
        with spans.span("t.step", leaf=False, on=on) as step:
            with spans.span("t.leaf", on=on) as leaf:
                pass
        on = spans.recording()
        with spans.span("t.step", leaf=False, on=on, k=2) as kept:
            pass
    assert step is None and leaf is None
    assert kept.ids == {"k": 2}
    assert set(spans.summary()) == {"t.step"}


def test_self_time_and_samples_under_a_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert jax.profiler.TraceAnnotation.is_enabled()
        outer = _nest()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    s = spans.summary()
    assert set(s) == {"t.outer", "t.inner", "t.wait"}
    assert s["t.outer"].count == 1 and s["t.inner"].count == 2
    assert s["t.outer"].total_s == pytest.approx(outer.seconds)
    assert s["t.outer"].self_s == pytest.approx(
        s["t.outer"].total_s - s["t.inner"].total_s, abs=1e-12)
    assert 0.0005 < s["t.outer"].self_s < s["t.outer"].total_s
    # a leaf's self time is its whole time
    assert s["t.inner"].self_s == pytest.approx(s["t.inner"].total_s)
    assert s["t.outer"].ids == [{"step": 3}]
    assert s["t.inner"].ids == [{"i": 0}, {"i": 1}]
    assert s["t.wait"].values == [0.25, 0.5]
    assert s["t.wait"].ids == [{"batch": 7}, {"batch": 8}]
    assert s["t.wait"].total_s == 0.0


def test_parentage_is_per_thread(tmp_path):
    seen = {}

    def other():
        with spans.span("t.other") as r:
            seen["parent"] = r.parent

    with jax.profiler.trace(str(tmp_path)):
        with spans.span("t.outer", leaf=False) as outer:
            t = threading.Thread(target=other)
            t.start()
            t.join()
            with spans.span("t.inner") as inner:
                pass
    assert seen["parent"] is None
    assert inner.parent is outer
    assert spans.summary()["t.outer"].self_s == pytest.approx(
        outer.seconds - inner.seconds, abs=1e-12)


def test_reset_drops_everything(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _nest()
    assert spans.summary()
    spans.reset()
    assert spans.summary() == {}


def test_the_profiler_trace_holds_leaves_only(tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        _nest()
    found = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    names = set()
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            names.update(ev.name for ev in line.events)
    assert "t.inner" in names
    assert "t.outer" not in names
    assert "t.wait" not in names


def test_threads_keep_every_span_and_sample(tmp_path):
    """More threads than cores, a short switch interval: no record is lost
    and every parent's self time is its own duration less its child's."""
    import sys

    n_threads, n_spans = 4 * (os.cpu_count() or 1), 200

    def work(t):
        for i in range(n_spans):
            with spans.span("t.outer", leaf=False, t=t, i=i):
                with spans.span("t.inner"):
                    pass
                spans.sample("t.wait", float(i), t=t)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=work, args=(t,))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    s = spans.summary()
    total = n_threads * n_spans
    assert s["t.outer"].count == s["t.inner"].count == total
    assert len(s["t.wait"].values) == total
    assert sorted((i["t"], i["i"]) for i in s["t.outer"].ids) == sorted(
        (t, i) for t in range(n_threads) for i in range(n_spans))
    assert s["t.outer"].self_s == pytest.approx(
        s["t.outer"].total_s - s["t.inner"].total_s, abs=1e-9)
