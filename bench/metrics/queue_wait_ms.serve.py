"""95th percentile of the requests' wait from submit to the start of their
dispatch, in ms, over the requests dispatched in the traced span: the
program's ``serve.queue_wait`` samples (``forecast/server/engine.py``)."""

import numpy as np

from bench import spans


def read(ctx):
    s = spans.summary()
    if "serve.queue_wait" not in s:
        return None
    return 1e3 * float(np.percentile(s["serve.queue_wait"].values, 95))
