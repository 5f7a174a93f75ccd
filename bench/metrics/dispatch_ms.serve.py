"""Host time per bucket dispatch of the server, in ms: ``ServeStats.total_s
/ batches`` over the traced window (``forecast/serving.py``
``BucketDispatcher``: pad, row gather, transfer, forecast, result copy)."""


def read(ctx):
    w = ctx["work"]
    return None if not w.get("batches") else 1e3 * w["dispatch_s"] / w["batches"]
