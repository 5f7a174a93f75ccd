"""Dilated residual LSTM stack (paper section 3.2, Table 1, Figure 1).

Structure (Chang et al., Dilated RNN): blocks of LSTM layers; the layer with
dilation ``d`` connects cell/hidden state from step ``t - d`` to step ``t``.
Blocks after the first add a residual connection from block input to block
output (dimensions match at ``hidden_size``).

Two implementations:

* :func:`drnn_apply` -- the *interleaved* formulation (also from Chang et
  al.): a dilation-d LSTM over T steps is exactly d independent LSTMs over
  the d stride-d sub-sequences. Each layer is a dense ``lax.scan`` with a
  flat ``(B*d, H)`` carry -- no ring buffers, no dynamic-index updates, d x
  fewer backward residuals, and d x larger (better MXU-shaped) gate matmuls.
  This is the production path (see EXPERIMENTS.md section Perf, ES-RNN
  hillclimb).
* :func:`drnn_apply_reference` -- the direct ring-buffer formulation kept as
  the numerical oracle (tests assert both paths agree).

Everything is pure-functional: ``drnn_init`` builds a params pytree. A single
fused-cell step is exposed (``lstm_cell``) so the Pallas kernel
(kernels/lstm_cell.py) can slot in behind the same signature.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.vma import match_vma


@jax.custom_vjp
def _gates_lowp(wx, wh, b, x, h):
    """Gate pre-activations for sub-f32 streams: f32 accumulators, low-p IO.

    Two deliberate departures from the fp32 formulation, both invisible to
    it (this function is only reached for sub-f32 streams):

    * the x- and h-dots fuse into ONE concatenated dot_general -- same
      fp32 accumulator via ``preferred_element_type``, one MXU dispatch,
      and one (B, 4H) f32 emission instead of two plus an f32 add; the
      bias joins *after* the stream-dtype cast (a depth-1 pointwise add
      needs no fp32 accumulator).
    * a custom backward: XLA's native AD would transpose the trailing
      f32->bf16 cast into a bf16->f32 convert on ``dgates``, promoting
      every backward dot to full f32 operands. Here ``dgates`` stays in
      the stream dtype, each backward dot keeps low-precision operands
      with an fp32 accumulator, and emits stream-dtype cotangents
      (custom_vjp requires primal dtypes anyway). This is what makes the
      backward half of the fit roofline's byte ratio drop, not just the
      forward half.
    """
    xh = jnp.concatenate([x, h], axis=1)
    w = jnp.concatenate([wx, wh], axis=0)
    return (jnp.dot(xh, w, preferred_element_type=jnp.float32)
            .astype(x.dtype) + b.astype(x.dtype))


def _gates_lowp_fwd(wx, wh, b, x, h):
    return _gates_lowp(wx, wh, b, x, h), (wx, wh, b, x, h)


def _gates_lowp_bwd(res, dg):
    # stream-dtype emissions throughout: a bf16 dot accumulates in fp32
    # inside the MXU regardless of its output dtype, so requesting an f32
    # emission here would only round-trip the identical accumulator through
    # HBM at twice the width before the very next op rounds it anyway
    wx, wh, b, x, h = res
    i = x.shape[1]
    xh = jnp.concatenate([x, h], axis=1)
    w = jnp.concatenate([wx, wh], axis=0)
    dxh = jnp.dot(dg, w.T)
    dw = jnp.dot(xh.T, dg)
    db = jnp.sum(dg, axis=0).astype(b.dtype)
    return (dw[:i].astype(wx.dtype), dw[i:].astype(wh.dtype), db,
            dxh[:, :i].astype(x.dtype), dxh[:, i:].astype(h.dtype))


_gates_lowp.defvjp(_gates_lowp_fwd, _gates_lowp_bwd)


def lstm_cell(params, x, h_prev, c_prev, *, use_pallas: bool = False):
    """One fused LSTM step. x:(B,I) h,c:(B,H) -> (h,c):(B,H).

    Gate order (i, f, g, o) matches the Pallas kernel and ref oracle.
    """
    if use_pallas:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.lstm_cell(params["wx"], params["wh"], params["b"], x, h_prev, c_prev)
    # fp32 *accumulation*, stream-dtype elementwise: the gate pre-activations
    # are deep sums (dot_generals over I and H plus bias), so they accumulate
    # in fp32 regardless of the stream dtype -- same contract as the Pallas
    # kernel's MXU accumulators. The nonlinearities and the single-step state
    # update are pointwise (no accumulation depth), so they run in the stream
    # dtype; under bf16 this is what actually halves the cell's HBM-level
    # traffic (the roofline fit row). The fp32 branch keeps XLA's native AD
    # (bit-identical to the historical formulation); sub-f32 streams route
    # through the custom-vjp linear block so the backward dots stay in the
    # stream dtype too.
    if jnp.dtype(x.dtype) == jnp.float32:
        gates = (jnp.dot(x, params["wx"], preferred_element_type=jnp.float32)
                 + jnp.dot(h_prev, params["wh"], preferred_element_type=jnp.float32)
                 + params["b"].astype(jnp.float32)).astype(x.dtype)
    else:
        gates = _gates_lowp(*match_vma(params["wx"], params["wh"], params["b"],
                                       x, h_prev))
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = (jax.nn.sigmoid(f) * c_prev.astype(x.dtype)
         + jax.nn.sigmoid(i) * jnp.tanh(g))
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, c


def _cell_init(key, input_size: int, hidden_size: int, dtype):
    k1, k2 = jax.random.split(key)
    scale_x = 1.0 / jnp.sqrt(jnp.asarray(input_size, jnp.float32))
    scale_h = 1.0 / jnp.sqrt(jnp.asarray(hidden_size, jnp.float32))
    return {
        "wx": (jax.random.uniform(k1, (input_size, 4 * hidden_size), jnp.float32, -1, 1) * scale_x).astype(dtype),
        "wh": (jax.random.uniform(k2, (hidden_size, 4 * hidden_size), jnp.float32, -1, 1) * scale_h).astype(dtype),
        "b": jnp.zeros((4 * hidden_size,), dtype),
    }


def drnn_init(
    key,
    input_size: int,
    hidden_size: int,
    dilations: Sequence[Sequence[int]],
    dtype=jnp.float32,
):
    """Params for the dilated stack. ``dilations`` e.g. ((1, 2), (4, 8))."""
    params = []
    in_size = input_size
    for block in dilations:
        block_params = []
        for _d in block:
            key, sub = jax.random.split(key)
            block_params.append(_cell_init(sub, in_size, hidden_size, dtype))
            in_size = hidden_size
        params.append(block_params)
    return params


# ---------------------------------------------------------------------------
# interleaved (production) formulation
# ---------------------------------------------------------------------------


def _dilated_layer(cell, xs, d: int, *, use_pallas: bool):
    """One dilation-d LSTM layer over xs (B, T, F) via stride-d interleave."""
    b, t, f = xs.shape
    hidden = cell["wh"].shape[0]
    if d == 1:
        xr = xs
        bd = b
    else:
        pad = (-t) % d
        xp = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        tp = xp.shape[1]
        # (B, T/d, d, F) -> (B, d, T/d, F) -> (B*d, T/d, F): row j is the
        # stride-d sub-sequence starting at offset j -- an independent chain.
        xr = (xp.reshape(b, tp // d, d, f).transpose(0, 2, 1, 3)
              .reshape(b * d, tp // d, f))
        bd = b * d

    # zero carries derived from the data, so that inside shard_map they vary
    # over the same mesh axes as the per-step outputs
    h0 = jnp.broadcast_to(jnp.zeros_like(xr[:, :1, 0]), (bd, hidden))
    c0 = h0

    def step(carry, x_t):
        h, c = carry
        h, c = lstm_cell(cell, x_t, h, c, use_pallas=use_pallas)
        return (h, c), (h, c)

    (_, _), (hs, cs) = jax.lax.scan(step, (h0, c0), jnp.swapaxes(xr, 0, 1))
    hs = jnp.swapaxes(hs, 0, 1)                       # (B*d, T/d, H)
    cs = jnp.swapaxes(cs, 0, 1)
    if d > 1:
        tp = hs.shape[1] * d
        hs = (hs.reshape(b, d, tp // d, hidden).transpose(0, 2, 1, 3)
              .reshape(b, tp, hidden))[:, :t]
        cs = (cs.reshape(b, d, tp // d, hidden).transpose(0, 2, 1, 3)
              .reshape(b, tp, hidden))[:, :t]
    return hs, cs


@partial(jax.jit, static_argnames=("dilations", "use_pallas"))
def drnn_apply(
    params,
    xs: jax.Array,
    *,
    dilations: Tuple[Tuple[int, ...], ...],
    use_pallas: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Run the stack over a sequence.

    Args:
      params: from :func:`drnn_init`.
      xs: ``(B, T, input_size)``.

    Returns:
      outputs ``(B, T, hidden)`` and mean squared cell-state magnitude of the
      *first layer of each block* (scalar) -- the section 8.4 Krueger &
      Memisevic stabilization penalty term.
    """
    inp = xs
    cstate_sq = jnp.zeros((), jnp.float32)
    n_terms = 0
    for bi, (block, bparams) in enumerate(zip(dilations, params)):
        block_in = inp
        for li, (d, cell) in enumerate(zip(block, bparams)):
            inp, cs = _dilated_layer(cell, inp, d, use_pallas=use_pallas)
            if li == 0:
                cstate_sq = cstate_sq + jnp.mean(jnp.square(cs.astype(jnp.float32)))
                n_terms += 1
        if bi > 0:  # residual between blocks (dims match at hidden)
            inp = inp + block_in
    return inp, cstate_sq / max(n_terms, 1)


# ---------------------------------------------------------------------------
# ring-buffer reference (numerical oracle for the interleaved path)
# ---------------------------------------------------------------------------


def drnn_apply_reference(
    params,
    xs: jax.Array,
    *,
    dilations: Tuple[Tuple[int, ...], ...],
    use_pallas: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Direct formulation: per-layer (d, B, H) state rings, dynamic slots."""
    b = xs.shape[0]
    hidden = params[0][0]["wh"].shape[0]
    dtype = xs.dtype

    rings = []
    for block in dilations:
        for d in block:
            rings.append(
                (jnp.zeros((d, b, hidden), dtype), jnp.zeros((d, b, hidden), dtype))
            )

    flat_cells = [cp for blk in params for cp in blk]
    layer_dils = [d for blk in dilations for d in blk]
    block_sizes = [len(blk) for blk in dilations]
    first_layer_idx = []
    acc = 0
    for s in block_sizes:
        first_layer_idx.append(acc)
        acc += s

    def step(carry, x_t):
        rings, t = carry
        new_rings = []
        inp = x_t
        cstate_sq = jnp.zeros((), jnp.float32)
        li = 0
        for bi, nblk in enumerate(block_sizes):
            block_in = inp
            for _ in range(nblk):
                d = layer_dils[li]
                h_ring, c_ring = rings[li]
                slot = jnp.mod(t, d)
                h_prev = jax.lax.dynamic_index_in_dim(h_ring, slot, 0, keepdims=False)
                c_prev = jax.lax.dynamic_index_in_dim(c_ring, slot, 0, keepdims=False)
                h, c = lstm_cell(flat_cells[li], inp, h_prev, c_prev, use_pallas=use_pallas)
                h_ring = jax.lax.dynamic_update_index_in_dim(h_ring, h, slot, 0)
                c_ring = jax.lax.dynamic_update_index_in_dim(c_ring, c, slot, 0)
                new_rings.append((h_ring, c_ring))
                if li == first_layer_idx[bi]:
                    cstate_sq = cstate_sq + jnp.mean(jnp.square(c.astype(jnp.float32)))
                inp = h
                li += 1
            if bi > 0:
                inp = inp + block_in
        return (new_rings, t + 1), (inp, cstate_sq)

    (_, _), (outs, cstate_sqs) = jax.lax.scan(
        step, (rings, jnp.zeros((), jnp.int32)), jnp.swapaxes(xs, 0, 1)
    )
    return jnp.swapaxes(outs, 0, 1), jnp.mean(cstate_sqs) / max(len(block_sizes), 1)
