"""The host's wait for a step's losses, in ms per training step, over the
traced span: the program span ``fit.loss_sync`` (``np.asarray`` of the
losses: the device step's rest and the D2H, as the host sees them), over the
steps the ``fit.step`` spans hold (``train/trainer.py``)."""

from bench import spans


def read(ctx):
    s = spans.summary()
    steps = spans.fit_steps(s)
    if not steps or "fit.loss_sync" not in s:
        return None
    return 1e3 * s["fit.loss_sync"].total_s / steps
