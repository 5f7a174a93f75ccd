"""Input transfer per predict call, in ms, over the traced span: the program
spans ``predict.inputs`` (the history and one-hots' copies started) and
``predict.transfer`` (the wait, after the forecast's dispatch, for those
copies to land) over the ``predict.call`` count (``forecast/estimator.py``
``predict``)."""

from bench import spans


def read(ctx):
    s = spans.summary()
    if "predict.call" not in s:
        return None
    return 1e3 * spans.self_s(s, "predict.inputs",
                              "predict.transfer") / s["predict.call"].count
