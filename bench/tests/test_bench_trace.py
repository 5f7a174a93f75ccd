"""The trace reduction, on a small trace recorded on a TPU v5e
(``bench/tests/record_trace.py``)."""

from pathlib import Path

import pytest

from bench import trace

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(SMALL))


def test_busy_time_is_inside_the_window(summary):
    assert summary.n_devices == 1
    assert 0.0 < summary.busy_s <= summary.window_s
    assert 0.0 <= summary.idle_share < 1.0
    assert SMALL.stat().st_size < 1 << 20


def test_breakdown_names_ops_and_gaps(summary):
    b = trace.breakdown(summary)
    assert 0 < len(b["device_ops"]) <= trace.TOP
    assert 0 < len(b["idle_gaps"]) <= trace.TOP
    ops = [v for _, v in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)
    assert ops[0] <= summary.busy_s
    gaps = [v for _, v in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= summary.window_s - summary.busy_s + 1e-9
    assert all(isinstance(n, str) and n for n, _ in b["idle_gaps"])


def test_idle_share_reader(summary):
    assert trace.idle_share({"trace": summary}) == pytest.approx(
        100.0 * (1.0 - summary.busy_s / summary.window_s))
    assert trace.idle_share({"trace": None}) is None


def test_a_trace_without_the_window_span_raises(tmp_path):
    with pytest.raises(ValueError, match="no.such.span"):
        trace.reduce(str(SMALL), window_span="no.such.span")
