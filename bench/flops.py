"""Floating-point operations the ES-RNN algorithm requires, from its shapes.

The count is of matrix-multiply work only: the LSTM gate products
(``x W_x + h W_h``) of every layer and the readout's dense and output
products, two operations per multiply-add. The Holt-Winters recurrence,
the Eq.-6 window arithmetic, the gate nonlinearities and the loss are
elementwise work on the vector unit and are left out, so a share of the
chip's matrix peak is never overstated.

Only what a result needs is counted:

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

from typing import Iterable, List


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
