"""What the per-layer readers of program spans share: the program's span
summary (``repro.analysis.spans``), empty where the program has none."""

from __future__ import annotations


def summary() -> dict:
    """Per span or sample name, what the program kept over the traced span;
    empty for a program without the span facility."""
    try:
        from repro.analysis import spans
    except ImportError:
        return {}
    return spans.summary()


def self_s(s: dict, *names: str) -> float:
    """The summed self time of the named spans (0 for a name not kept)."""
    return sum(s[n].self_s for n in names if n in s)


def fit_steps(s: dict) -> int:
    """Training steps the traced ``fit.step`` spans hold (their ``k``)."""
    return sum(i["k"] for i in s["fit.step"].ids) if "fit.step" in s else 0


def per_dispatch_ms(s: dict, *names: str):
    """The named spans' time per ``serve.dispatch``, in ms; None without
    a dispatch."""
    if "serve.dispatch" not in s:
        return None
    return 1e3 * self_s(s, *names) / s["serve.dispatch"].count
