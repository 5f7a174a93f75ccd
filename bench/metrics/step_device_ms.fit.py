"""Device busy time per training step, in ms: the traced span's busy share
times the fit window's time per step (eval forecasts included)."""


def read(ctx):
    tr, work = ctx.get("trace"), ctx["work"]
    if tr is None or not work.get("steps") or not work.get("window_s"):
        return None
    return 1e3 * (tr.busy_s / tr.window_s) * work["window_s"] / work["steps"]
