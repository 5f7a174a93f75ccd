"""Host time per training step, in ms, over the traced span: the self time
of the program spans ``fit.step``, ``fit.index`` (the batch schedule to the
device), ``fit.dispatch`` and ``fit.boundary`` (its ``fit.eval`` left out),
over the steps the ``fit.step`` spans hold (``train/trainer.py``)."""

from bench import spans


def read(ctx):
    s = spans.summary()
    steps = spans.fit_steps(s)
    if not steps:
        return None
    return 1e3 * spans.self_s(s, "fit.step", "fit.index", "fit.dispatch",
                              "fit.boundary") / steps
