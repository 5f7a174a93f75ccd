"""Random ES-RNN weights made on the device from a seed, in one jitted call.

The layout is the benchmark's own, shared by the plain reference
(``bench.reference``) and by the adapter that hands the same arrays to the
program (``bench.sut``):

    {"hw":    {"alpha_logit": (N,), "gamma_logit": (N,), "seas_logit": (N, m)},
     "lstm":  [{"wx": (I, 4H), "wh": (H, 4H), "b": (4H,)}, ...]  # one per layer,
                                                                # blocks in order
     "dense_w": (H, H), "dense_b": (H,), "out_w": (H, O), "out_b": (O,)}

LSTM gates are laid out (i, f, g, o) along the 4H axis. Weight matrices
are uniform in +-1/sqrt(fan_in); biases and the Holt-Winters logits are
small and random, so every term of the model moves the result.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def n_layers(cfg) -> int:
    return sum(len(block) for block in cfg["dilations"])


def key_from_seed(seed: int, purpose: int):
    """A JAX key for one purpose, for any non-negative seed (64-bit too)."""
    words = np.random.SeedSequence([seed, purpose]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


@partial(jax.jit, static_argnames=("n_series", "m", "inp", "hid", "out",
                                   "layers"))
def _init(key, *, n_series, m, inp, hid, out, layers):
    keys = iter(jax.random.split(key, 8 + 3 * layers))

    def unif(shape, fan_in):
        return jax.random.uniform(next(keys), shape, jnp.float32, -1.0,
                                  1.0) / jnp.sqrt(float(fan_in))

    def normal(shape, scale):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    hw = {"alpha_logit": normal((n_series,), 0.5),
          "gamma_logit": normal((n_series,), 0.5),
          "seas_logit": normal((n_series, m), 0.05)}
    lstm = []
    for layer in range(layers):
        fan = inp if layer == 0 else hid
        lstm.append({"wx": unif((fan, 4 * hid), fan),
                     "wh": unif((hid, 4 * hid), hid),
                     "b": normal((4 * hid,), 0.1)})
    return {"hw": hw, "lstm": lstm,
            "dense_w": unif((hid, hid), hid), "dense_b": normal((hid,), 0.1),
            "out_w": unif((hid, out), hid), "out_b": normal((out,), 0.1)}


def init_weights(cfg, n_series: int, seed: int):
    """Weights for ``n_series`` series of configuration ``cfg`` (a dict)."""
    return _init(key_from_seed(seed, 1), n_series=n_series,
                 m=cfg["seasonality"],
                 inp=cfg["input_size"] + cfg["n_categories"],
                 hid=cfg["hidden_size"], out=cfg["output_size"],
                 layers=n_layers(cfg))


def leaves(w) -> dict:
    """Flat ``{name: array}`` view of a weights tree, in a fixed order."""
    out = {f"hw.{k}": w["hw"][k] for k in ("alpha_logit", "gamma_logit",
                                           "seas_logit")}
    for i, layer in enumerate(w["lstm"]):
        for k in ("wx", "wh", "b"):
            out[f"lstm{i}.{k}"] = layer[k]
    for k in ("dense_w", "dense_b", "out_w", "out_b"):
        out[k] = w[k]
    return out
