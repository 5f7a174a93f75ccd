"""The ``esrnn`` family's plain side: a float32 ES-RNN in jax.numpy, its
weights made from a seed, and the operations it requires.

It follows the paper (Redd et al. 2019, secs. 3.1-3.5; Smyl's ES-RNN) and
imports nothing of the program. Every dot runs at ``Precision.HIGHEST``, so
on a TPU it multiplies in float32 and not in one bfloat16 pass.

* Holt-Winters without trend, multiplicative seasonality of period m:
  ``l_t = a y_t / s_t + (1 - a) l_{t-1}``, ``s_{t+m} = g y_t / l_t + (1 - g) s_t``,
  ``l_{-1} = y_0 / s_0``; a, g are sigmoids of per-series logits and the
  first season is the exp of per-series logits.
* Eq. 6 windows at positions t = W-1 .. T-1:
  ``x_t = log(y_{t-W+1..t} / (l_t s_{t-W+1..t}))`` with the category one-hot
  appended; targets ``log(y_{t+1..t+O} / (l_t s_{t+1..t+O}))`` where
  ``t+k < T``. Seasonal factors past index T+m-1 repeat the last season.
* The dilated residual LSTM (Table 1): the layer of dilation d feeds the
  state of position t-d to position t (zero before the first d); blocks
  after the first add their input to their output. Gates (i, f, g, o).
* Readout ``tanh(h W_d + b_d) W_o + b_o``; pinball loss at tau over the
  valid targets (a masked mean); Eq. 5 forecast
  ``exp(yhat_{T-1}) l_{T-1} s_{T..T+O-1}``.
* Training: the batch's rows of the per-series table are gathered, the
  gradient is clipped to a global norm and applied by Adam with two
  learning rates (per-series rows, shared weights) over the whole table.

Where a cell serves requests, ``PRIMER_HW`` states the server's documented
row for a series the fit never saw: alpha = gamma = 0.5 and a flat season.

The weights (``init_weights``) are random, made on the device from a seed
in one jitted call, in the layout that the family's adapter
(``bench/adapters/esrnn.py``) hands to the program:

    {"hw":    {"alpha_logit": (N,), "gamma_logit": (N,), "seas_logit": (N, m)},
     "lstm":  [{"wx": (I, 4H), "wh": (H, 4H), "b": (4H,)}, ...]  # one per layer,
                                                                # blocks in order
     "dense_w": (H, H), "dense_b": (H,), "out_w": (H, O), "out_b": (O,)}

LSTM gates are laid out (i, f, g, o) along the 4H axis. Weight matrices
are uniform in +-1/sqrt(fan_in); biases and the Holt-Winters logits are
small and random, so every term of the model moves the result.

The operation counts (``train_step_flops``, ``forecast_flops``) are of
matrix-multiply work only: the LSTM gate products (``x W_x + h W_h``) of
every layer and the readout's dense and output products, two operations
per multiply-add. The Holt-Winters recurrence, the Eq.-6 window
arithmetic, the gate nonlinearities and the loss are elementwise work on
the vector unit and are left out, so a share of the chip's matrix peak is
never overstated. Only what a result needs is counted:

* a training step needs the network at every window position that has at
  least one target inside the series (positions W-1 .. T-2: the last
  position has none), forward and backward; backward counts twice forward;
* a forecast needs only the last position's readout, and of each LSTM
  layer only the positions that the last position reaches through its
  dilations (a layer of dilation d at position t needs t-d, t-2d, ...);
* padding that an implementation adds (a dilation's ragged tail, a batch
  bucket's repeated rows, a length bucket's left pad) is not counted.
"""

from __future__ import annotations

from functools import partial
from typing import Iterable, List

import jax
import jax.numpy as jnp
import numpy as np

# the configuration keys that ``train`` and ``forecast`` read (flat,
# hashable): a cell's ``model``
MODEL_KEYS = ("seasonality", "input_size", "output_size", "hidden_size",
              "dilations", "n_categories", "tau", "rnn_lr", "hw_lr",
              "clip_norm", "adam_b1", "adam_b2", "adam_eps")

HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


def smooth(y, hw, m: int):
    """Holt-Winters levels (N, T) and seasonal factors (N, T + m)."""
    alpha = jax.nn.sigmoid(hw["alpha_logit"])
    gamma = jax.nn.sigmoid(hw["gamma_logit"])
    season = jnp.exp(hw["seas_logit"])

    def step(carry, y_t):
        level, ring = carry
        s_t = ring[:, 0]
        level = alpha * y_t / s_t + (1.0 - alpha) * level
        s_next = gamma * y_t / level + (1.0 - gamma) * s_t
        return (level, jnp.concatenate([ring[:, 1:], s_next[:, None]], 1)), (
            level, s_t)

    (_, ring), (levels, used) = jax.lax.scan(
        step, (y[:, 0] / season[:, 0], season), y.T)
    return levels.T, jnp.concatenate([used.T, ring], axis=1)


def _season_index(idx, t_len: int, m: int):
    return jnp.where(idx < t_len + m, idx, t_len + jnp.mod(idx - t_len, m))


def windows(cfg, y, levels, seas):
    """Eq. 6 inputs (N, P, W), targets (N, P, O) and their validity (P, O)."""
    w, o, m = cfg["input_size"], cfg["output_size"], cfg["seasonality"]
    t_len = y.shape[1]
    pos = np.arange(w - 1, t_len)
    in_idx = pos[:, None] + np.arange(1 - w, 1)[None, :]
    lvl = levels[:, pos][:, :, None]
    x = jnp.log(jnp.maximum(y[:, in_idx] / (lvl * seas[:, in_idx]), 1e-8))
    out_idx = pos[:, None] + np.arange(1, o + 1)[None, :]
    valid = out_idx < t_len
    y_out = y[:, np.minimum(out_idx, t_len - 1)]
    s_out = seas[:, _season_index(jnp.asarray(out_idx), t_len, m)]
    target = jnp.log(jnp.maximum(y_out / (lvl * s_out), 1e-8))
    return x, target, jnp.asarray(valid, y.dtype)


def _cell(p, x, h, c):
    z = _dot(x, p["wx"]) + _dot(h, p["wh"]) + p["b"]
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


def _dilated_layer(p, xs, d: int):
    """One LSTM layer over time-major xs (P, B, I); position t reads t-d."""
    zeros = jnp.zeros((d, xs.shape[1], p["wh"].shape[0]), xs.dtype)

    def step(carry, x):
        hs, cs = carry
        h, c = _cell(p, x, hs[0], cs[0])
        return (jnp.concatenate([hs[1:], h[None]]),
                jnp.concatenate([cs[1:], c[None]])), h

    return jax.lax.scan(step, (zeros, zeros), xs)[1]


def network(cfg, w, feats):
    """Dilated residual LSTM + readout: (N, P, I) -> (N, P, O)."""
    inp = jnp.swapaxes(feats, 0, 1)
    layer = 0
    for bi, block in enumerate(cfg["dilations"]):
        block_in = inp
        for d in block:
            inp = _dilated_layer(w["lstm"][layer], inp, int(d))
            layer += 1
        if bi > 0:
            inp = inp + block_in
    hid = jnp.swapaxes(inp, 0, 1)
    z = jnp.tanh(_dot(hid, w["dense_w"]) + w["dense_b"])
    return _dot(z, w["out_w"]) + w["out_b"]


def _states(cfg, w, y, cats):
    levels, seas = smooth(y, w["hw"], cfg["seasonality"])
    x, target, valid = windows(cfg, y, levels, seas)
    cat = jnp.broadcast_to(cats[:, None, :], x.shape[:2] + cats.shape[-1:])
    yhat = network(cfg, w, jnp.concatenate([x, cat], axis=-1))
    return levels, seas, yhat, target, valid


@partial(jax.jit, static_argnames=("cfg",))
def _forecast(cfg, w, y, cats):
    cfg = dict(cfg)
    levels, seas, yhat, _, _ = _states(cfg, w, y, cats)
    t_len, o = y.shape[1], cfg["output_size"]
    s_fut = seas[:, _season_index(t_len + jnp.arange(o), t_len,
                                  cfg["seasonality"])]
    return jnp.exp(yhat[:, -1, :]) * levels[:, -1:] * s_fut


def _frozen(cfg):
    """A hashable copy of a flat model dict (for ``jit``'s static argument)."""
    return tuple(sorted((k, tuple(map(tuple, v)) if k == "dilations" else v)
                        for k, v in cfg.items()))


def forecast(cfg, w, y, cats, *, block: int = 4096):
    """Eq. 5 forecasts (N, O) of histories y (N, T), ``block`` rows at a time.

    ``w["hw"]`` holds one row per series of ``y``. The last block is padded
    to ``block`` rows so that every block runs one compiled program.
    """
    n = y.shape[0]
    key = _frozen(cfg)
    out = []
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        pad = block - (hi - lo) if n > block else 0
        rows = np.concatenate([np.arange(lo, hi), np.full(pad, hi - 1)])
        w_b = {**w, "hw": jax.tree_util.tree_map(lambda a: a[rows], w["hw"])}
        fc = _forecast(key, w_b, jnp.asarray(y[rows]), jnp.asarray(cats[rows]))
        out.append(np.asarray(fc)[: hi - lo])
    return np.concatenate(out)


def batch_loss(cfg, w, y, cats):
    """Pinball loss over the valid targets of a batch (all rows in ``w``)."""
    _, _, yhat, target, valid = _states(cfg, w, y, cats)
    diff = target - yhat
    tau = cfg["tau"]
    loss = jnp.maximum(tau * diff, (tau - 1.0) * diff)
    mask = jnp.broadcast_to(valid[None], loss.shape)
    return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@partial(jax.jit, static_argnames=("cfg",))
def _train(cfg, w, y, cats, schedule):
    cfg = dict(cfg)
    b1, b2, eps = cfg["adam_b1"], cfg["adam_b2"], cfg["adam_eps"]

    def loss_at(w, idx):
        rows = {**w, "hw": jax.tree_util.tree_map(lambda a: a[idx], w["hw"])}
        return batch_loss(cfg, rows, y[idx], cats[idx])

    def lr_tree(w):
        return {k: jax.tree_util.tree_map(
            lambda _: cfg["hw_lr"] if k == "hw" else cfg["rnn_lr"], v)
            for k, v in w.items()}

    lrs = lr_tree(w)
    mu = jax.tree_util.tree_map(jnp.zeros_like, w)
    nu = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad = [], None
    for k in range(schedule.shape[0]):
        loss, g = jax.value_and_grad(loss_at)(w, schedule[k])
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                            for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, cfg["clip_norm"] /
                                      jnp.maximum(norm, 1e-12)), g)
        t = k + 1
        mu = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, mu, g)
        nu = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b,
                                    nu, g)
        w = jax.tree_util.tree_map(
            lambda p, a, b, lr: p - lr * (a / (1 - b1 ** t)) / (
                jnp.sqrt(b / (1 - b2 ** t)) + eps), w, mu, nu, lrs)
        losses.append(loss)
        if first_grad is None:
            first_grad = g
    return jnp.stack(losses), first_grad, w


def train(cfg, w, y, cats, schedule):
    """Run ``len(schedule)`` steps from ``w`` on the batches' row indices.

    Returns (losses (K,), the first step's clipped gradient, the weights
    after the last step). ``cfg`` carries the model widths and the
    optimizer's ``rnn_lr``, ``hw_lr``, ``clip_norm`` and ``adam_*``.
    """
    return _train(_frozen(cfg), w, jnp.asarray(y), jnp.asarray(cats),
                  jnp.asarray(schedule))


# alpha = gamma = 0.5 and a flat first season: the server's row for a series
# the fit never saw (paper sec. 3.3 primer initialization)
PRIMER_HW = {"alpha_logit": 0.0, "gamma_logit": 0.0, "seas_logit": 0.0}


# weights


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


# operation counts


def _layers(cfg) -> List[tuple]:
    """(input width, dilation) of every LSTM layer, blocks in order."""
    out, width = [], cfg["input_size"] + cfg["n_categories"]
    for block in cfg["dilations"]:
        for d in block:
            out.append((width, int(d)))
            width = cfg["hidden_size"]
    return out


def cell_flops(cfg, width: int) -> int:
    """One LSTM cell at one position: gates of width 4H from [x, h]."""
    h = cfg["hidden_size"]
    return 2 * (width + h) * 4 * h


def readout_flops(cfg) -> int:
    h = cfg["hidden_size"]
    return 2 * h * h + 2 * h * cfg["output_size"]


def position_flops(cfg) -> int:
    """All LSTM layers plus the readout, at one position of one series."""
    return sum(cell_flops(cfg, w) for w, _ in _layers(cfg)) + readout_flops(cfg)


def train_step_flops(cfg, batch: int, t_len: int) -> int:
    """Forward and backward of one step on ``batch`` series of length T."""
    positions = t_len - cfg["input_size"]          # W-1 .. T-2
    return 3 * batch * positions * position_flops(cfg)


def _needed(positions: Iterable[int], d: int) -> set:
    """Positions a dilation-d chain must compute to reach ``positions``."""
    out = set()
    for p in positions:
        while p >= 0 and p not in out:
            out.add(p)
            p -= d
    return out


def forecast_flops(cfg, n_series: int, t_len: int) -> int:
    """One Eq.-5 forecast from the end of each of ``n_series`` histories."""
    last = t_len - cfg["input_size"]               # index of position T-1
    need, total = {last}, 0
    blocks = []
    layers = iter(_layers(cfg))
    for block in cfg["dilations"]:
        blocks.append([next(layers) for _ in block])
    # walk from the output back to the input: each layer computes the
    # closure of what the layer above reads (its output positions, and for
    # a residual block the block input at the block's output positions)
    for block in reversed(blocks):
        out_need = need
        for width, d in reversed(block):
            need = _needed(need, d)
            total += len(need) * cell_flops(cfg, width)
        need = need | out_need if block is not blocks[0] else need
    return n_series * (total + readout_flops(cfg))
