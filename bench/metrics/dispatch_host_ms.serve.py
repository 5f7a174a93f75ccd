"""Host time a dispatch spends before the device, in ms, over the traced
span: the program spans ``serve.shape`` (pad, one-hot, HW rows) and
``serve.launch`` (transfer and dispatch) over the ``serve.dispatch`` count
(``forecast/serving.py`` ``BucketDispatcher.run_bucket``)."""

from bench import spans


def read(ctx):
    return spans.per_dispatch_ms(spans.summary(), "serve.shape",
                                 "serve.launch")
