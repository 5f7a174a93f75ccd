"""The wait for a dispatch's forecasts, in ms, over the traced span: the
program span ``serve.result`` (``np.asarray`` of the forecast: the device
forward and the D2H, as the host sees them) over the ``serve.dispatch``
count (``forecast/serving.py`` ``BucketDispatcher.run_bucket``)."""

from bench import spans


def read(ctx):
    return spans.per_dispatch_ms(spans.summary(), "serve.result")
