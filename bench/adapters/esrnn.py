"""The ``esrnn`` family's program side: the spec built from a configuration
file, the benchmark's weights (``bench/reference/esrnn.py`` layout) and
fleet put in the program's own types, and the program's parameter and
optimizer trees read back under the reference's leaf names.
"""

from __future__ import annotations

import numpy as np

# configuration keys that map one to one onto ForecastSpec / ESRNNConfig
SPEC_KEYS = ("seasonality", "input_size", "output_size", "hidden_size",
             "n_categories", "tau", "rnn_lr", "hw_lr", "clip_norm", "dtype",
             "precision", "use_pallas", "sparse_adam", "scan_steps", "head")


def make_spec(config: dict, **overrides):
    """The program's ForecastSpec for a configuration, as the file states it."""
    from repro.forecast import get_spec

    kw = {k: config[k] for k in SPEC_KEYS}
    kw["dilations"] = tuple(tuple(b) for b in config["dilations"])
    kw.update(overrides)
    return get_spec(config["spec"], **kw)


def program_params(config: dict, w: dict) -> dict:
    """The reference's weights (``init_weights``) as the program's params."""
    from repro.core.holt_winters import HWParams

    hw = HWParams(alpha_logit=w["hw"]["alpha_logit"],
                  gamma_logit=w["hw"]["gamma_logit"],
                  init_seas_logit=w["hw"]["seas_logit"])
    layers = iter(w["lstm"])
    rnn = [[dict(next(layers)) for _ in block] for block in config["dilations"]]
    head = {k: w[k] for k in ("dense_w", "dense_b", "out_w", "out_b")}
    return {"hw": hw, "rnn": rnn, "head": head}


def program_leaves(tree) -> dict:
    """A program params-shaped tree (params, or an Adam moment) as the flat
    ``{name: array}`` view of the reference's ``leaves``."""
    out = {"hw.alpha_logit": tree["hw"].alpha_logit,
           "hw.gamma_logit": tree["hw"].gamma_logit,
           "hw.seas_logit": tree["hw"].init_seas_logit}
    layer = 0
    for block in tree["rnn"]:
        for cell in block:
            for k in ("wx", "wh", "b"):
                out[f"lstm{layer}.{k}"] = cell[k]
            layer += 1
    for k in ("dense_w", "dense_b", "out_w", "out_b"):
        out[k] = tree["head"][k]
    return out


def program_data(config: dict, fleet):
    """A ``bench.fleet.Fleet`` as the program's PreparedData (equalized)."""
    from repro.data.pipeline import PreparedData

    return PreparedData(
        frequency=fleet.frequency, seasonality=config["seasonality"],
        horizon=config["output_size"],
        train=fleet.train, val_input=fleet.val_input,
        val_target=fleet.val_target, test_target=fleet.test_target,
        mask=np.ones_like(fleet.train), cats=fleet.cats,
        categories=fleet.categories)
