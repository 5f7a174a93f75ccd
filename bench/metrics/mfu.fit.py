"""The fit window's share of the chip's bf16 matrix peak, in %.

The operations are those the algorithm requires for the window's fit call
(``train_step_flops`` and ``forecast_flops`` of the configuration's family,
``bench/reference/<family>.py``: every step's forward and backward pass and
every eval forecast), over the call's wall time. A default-precision
float32 dot is one bf16 pass on the MXU, so the bf16 peak is the divisor.
"""


def read(ctx):
    work, peaks = ctx["work"], ctx.get("peaks")
    if not work.get("flops") or not work.get("window_s") or not peaks:
        return None
    return 100.0 * work["flops"] / work["window_s"] / peaks["bf16_flops_per_s"]
