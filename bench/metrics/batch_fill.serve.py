"""Share of the server's dispatched rows that carry a request, in %:
``requests / (requests + padded_series)`` of ``ServeStats`` over the
traced window (``forecast/server/engine.py`` bucket fill)."""


def read(ctx):
    w = ctx["work"]
    rows = w.get("requests", 0) + w.get("padded_series", 0)
    return None if not rows else 100.0 * w["requests"] / rows
