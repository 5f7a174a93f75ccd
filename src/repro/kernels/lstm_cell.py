"""Pallas TPU kernel: fused LSTM cell (gates GEMM + elementwise, one pass).

One step of the dilated LSTM (paper Fig. 1). The fusion target on TPU is:
both gate matmuls hit the MXU from a single VMEM residency of ``x``/``h``,
and the gate nonlinearities + state update run on the VPU without the
``(B, 4H)`` gates tensor ever round-tripping to HBM.

Blocking: grid over batch tiles; weights are small for the paper's sizes
(H <= 50 padded to 128) and live fully in VMEM per block. ops.py pads
(B -> 8k, I/H -> 128k) and strips.

Training path: ``lstm_cell_padded`` carries a :func:`jax.custom_vjp`. Its
forward rule runs an extended kernel that additionally emits the gate
activations ``[sigmoid(i) | sigmoid(f) | tanh(g) | sigmoid(o)]`` as one
``(B, 4H)`` residual; the backward rule is a second fused kernel that turns
``(dh, dc)`` into the pre-activation gate cotangents on the VPU and runs all
four transposed GEMMs (``dx``, ``dh_prev`` and the weight gradients) from the
same VMEM residency. Weight/bias gradients accumulate across batch-grid
steps into a single revisited output block (grid is sequential on TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.sharding import vma

BLOCK_B = 128


def block_b_for(dtype) -> int:
    """Batch-tile rows per grid step, by stream dtype.

    The roofline report (``repro.roofline.esrnn`` / BENCH_PR10) puts the
    fused train step deep in the memory-bound regime (arithmetic intensity
    far below the TPU ridge point), so the tile size is bandwidth-driven:
    a bf16 stream halves every per-row VMEM tile (x/h/c plus the (B, 4H)
    activation residual), which lets a 2-byte dtype double the batch rows
    per grid step inside the same VMEM budget -- half the grid dispatches,
    and each gate GEMM sees an MXU-shaped 256-row operand. fp32 keeps the
    tuned 128.
    """
    return 2 * BLOCK_B if jnp.dtype(dtype).itemsize <= 2 else BLOCK_B


def _gates(wx_ref, wh_ref, b_ref, x, h):
    return (
        jnp.dot(x, wx_ref[...], preferred_element_type=jnp.float32)
        + jnp.dot(h, wh_ref[...], preferred_element_type=jnp.float32)
        + b_ref[0, :][None, :].astype(jnp.float32)
    )


def _lstm_kernel(wx_ref, wh_ref, b_ref, x_ref, h_ref, c_ref, h_out_ref, c_out_ref,
                 *, hidden: int):
    x = x_ref[...]
    h = h_ref[...]
    c = c_ref[...]
    gates = _gates(wx_ref, wh_ref, b_ref, x, h)
    i = gates[:, 0 * hidden : 1 * hidden]
    f = gates[:, 1 * hidden : 2 * hidden]
    g = gates[:, 2 * hidden : 3 * hidden]
    o = gates[:, 3 * hidden : 4 * hidden]
    c_new = jax.nn.sigmoid(f) * c.astype(jnp.float32) + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)


def _lstm_fwd_kernel(wx_ref, wh_ref, b_ref, x_ref, h_ref, c_ref,
                     h_out_ref, c_out_ref, act_ref, *, hidden: int):
    """Forward that also emits the gate activations as backward residuals."""
    x = x_ref[...]
    h = h_ref[...]
    c = c_ref[...]
    gates = _gates(wx_ref, wh_ref, b_ref, x, h)
    si = jax.nn.sigmoid(gates[:, 0 * hidden : 1 * hidden])
    sf = jax.nn.sigmoid(gates[:, 1 * hidden : 2 * hidden])
    tg = jnp.tanh(gates[:, 2 * hidden : 3 * hidden])
    so = jax.nn.sigmoid(gates[:, 3 * hidden : 4 * hidden])
    c_new = sf * c.astype(jnp.float32) + si * tg
    h_new = so * jnp.tanh(c_new)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)
    act_ref[...] = jnp.concatenate([si, sf, tg, so], axis=1).astype(act_ref.dtype)


def _lstm_bwd_kernel(wx_ref, wh_ref, x_ref, h_ref, c_ref, c_new_ref, act_ref,
                     dh_ref, dc_ref,
                     dx_ref, dhp_ref, dcp_ref, dwx_ref, dwh_ref, db_ref,
                     *, hidden: int):
    """Fused backward: (dh, dc) -> (dx, dh_prev, dc_prev, dwx, dwh, db)."""
    act = act_ref[...].astype(jnp.float32)
    si = act[:, 0 * hidden : 1 * hidden]
    sf = act[:, 1 * hidden : 2 * hidden]
    tg = act[:, 2 * hidden : 3 * hidden]
    so = act[:, 3 * hidden : 4 * hidden]
    c = c_ref[...].astype(jnp.float32)
    tc = jnp.tanh(c_new_ref[...].astype(jnp.float32))
    dh = dh_ref[...].astype(jnp.float32)
    dc = dc_ref[...].astype(jnp.float32)

    # h = so * tanh(c_new); c_new = sf * c + si * tg
    do_pre = dh * tc * so * (1.0 - so)
    dct = dc + dh * so * (1.0 - tc * tc)
    df_pre = dct * c * sf * (1.0 - sf)
    di_pre = dct * tg * si * (1.0 - si)
    dg_pre = dct * si * (1.0 - tg * tg)
    dgates = jnp.concatenate([di_pre, df_pre, dg_pre, do_pre], axis=1)  # (B,4H)

    # contract the 4H axis without materializing transposed weights
    contract_4h = (((1,), (1,)), ((), ()))
    dx_ref[...] = jax.lax.dot_general(
        dgates, wx_ref[...], contract_4h,
        preferred_element_type=jnp.float32).astype(dx_ref.dtype)
    dhp_ref[...] = jax.lax.dot_general(
        dgates, wh_ref[...], contract_4h,
        preferred_element_type=jnp.float32).astype(dhp_ref.dtype)
    dcp_ref[...] = (dct * sf).astype(dcp_ref.dtype)

    # weight/bias grads sum over the whole batch: every grid step maps to the
    # same output block, so zero it on the first step and accumulate after.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dwx_ref[...] = jnp.zeros_like(dwx_ref)
        dwh_ref[...] = jnp.zeros_like(dwh_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    contract_b = (((0,), (0,)), ((), ()))
    dwx_ref[...] += jax.lax.dot_general(
        x_ref[...], dgates, contract_b,
        preferred_element_type=jnp.float32).astype(dwx_ref.dtype)
    dwh_ref[...] += jax.lax.dot_general(
        h_ref[...], dgates, contract_b,
        preferred_element_type=jnp.float32).astype(dwh_ref.dtype)
    db_ref[...] += jnp.sum(dgates, axis=0)[None, :].astype(db_ref.dtype)


def _lstm_call_specs(block_b: int):
    full = lambda rows, cols: pl.BlockSpec((rows, cols), lambda i: (0, 0))
    tile = lambda cols: pl.BlockSpec((block_b, cols), lambda i: (i, 0))
    return full, tile


def _lstm_fwd_call(wx, wh, b, x, h, c, *, interpret: bool, with_acts: bool,
                   block_b: int = BLOCK_B):
    bsz, input_size = x.shape
    hidden = h.shape[1]
    dtype = x.dtype
    grid = (bsz // block_b,)
    full, tile = _lstm_call_specs(block_b)
    out = vma.out_shape(wx, wh, b, x, h, c)
    in_specs = [
        full(input_size, 4 * hidden),
        full(hidden, 4 * hidden),
        full(1, 4 * hidden),
        tile(input_size),
        tile(hidden),
        tile(hidden),
    ]
    out_specs = [tile(hidden), tile(hidden)]
    out_shape = [
        out((bsz, hidden), dtype),
        out((bsz, hidden), dtype),
    ]
    if with_acts:
        kernel = functools.partial(_lstm_fwd_kernel, hidden=hidden)
        out_specs = out_specs + [tile(4 * hidden)]
        out_shape = out_shape + [out((bsz, 4 * hidden), dtype)]
    else:
        kernel = functools.partial(_lstm_kernel, hidden=hidden)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
    )(wx, wh, b[None, :], x, h, c)


def _lstm_bwd_call(wx, wh, x, h, c, c_new, act, dh, dc, *, interpret: bool,
                   block_b: int = BLOCK_B):
    bsz, input_size = x.shape
    hidden = h.shape[1]
    dtype = x.dtype
    grid = (bsz // block_b,)
    full, tile = _lstm_call_specs(block_b)
    out = vma.out_shape(wx, wh, x, h, c, c_new, act, dh, dc)
    kernel = functools.partial(_lstm_bwd_kernel, hidden=hidden)
    dx, dhp, dcp, dwx, dwh, db = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            full(input_size, 4 * hidden),
            full(hidden, 4 * hidden),
            tile(input_size),          # x
            tile(hidden),              # h
            tile(hidden),              # c
            tile(hidden),              # c_new
            tile(4 * hidden),          # gate activations
            tile(hidden),              # dh
            tile(hidden),              # dc
        ],
        out_specs=[
            tile(input_size),
            tile(hidden),
            tile(hidden),
            full(input_size, 4 * hidden),
            full(hidden, 4 * hidden),
            full(1, 4 * hidden),
        ],
        out_shape=[
            out((bsz, input_size), dtype),
            out((bsz, hidden), dtype),
            out((bsz, hidden), dtype),
            # weight/bias grads accumulate across the sequential batch-grid
            # steps: always fp32, or a bf16 stream would round the running
            # sum at every revisit (the bf16-policy failure mode this
            # kernel exists to avoid). Cast back to the param dtype happens
            # in the vjp wrapper, after the sum is complete.
            out((input_size, 4 * hidden), jnp.float32),
            out((hidden, 4 * hidden), jnp.float32),
            out((1, 4 * hidden), jnp.float32),
        ],
        interpret=interpret,
    )(wx, wh, x, h, c, c_new, act, dh, dc)
    return dwx, dwh, db[0], dx, dhp, dcp


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lstm_cell_padded(interpret, block_b, wx, wh, b, x, h, c):
    return _lstm_fwd_call(wx, wh, b, x, h, c, interpret=interpret,
                          with_acts=False, block_b=block_b)


def _lstm_cell_padded_fwd(interpret, block_b, wx, wh, b, x, h, c):
    h_new, c_new, act = _lstm_fwd_call(wx, wh, b, x, h, c, interpret=interpret,
                                       with_acts=True, block_b=block_b)
    return (h_new, c_new), (wx, wh, x, h, c, c_new, act)


def _lstm_cell_padded_bwd(interpret, block_b, res, cotangents):
    wx, wh, x, h, c, c_new, act = res
    dh, dc = cotangents
    dwx, dwh, db, dx, dhp, dcp = _lstm_bwd_call(
        wx, wh, x, h, c, c_new, act,
        jnp.asarray(dh, x.dtype), jnp.asarray(dc, x.dtype),
        interpret=interpret, block_b=block_b)
    # the kernel accumulates weight grads in fp32; drop to the (possibly
    # bf16) weight dtype only once, after the full-batch sum
    return (dwx.astype(wx.dtype), dwh.astype(wh.dtype),
            db.astype(wx.dtype), dx, dhp, dcp)


_lstm_cell_padded.defvjp(_lstm_cell_padded_fwd, _lstm_cell_padded_bwd)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def lstm_cell_padded(wx, wh, b, x, h, c, *, interpret: bool = False,
                     block_b: int = BLOCK_B):
    """Padded entry: B % block_b == 0; I, H already lane-aligned by ops.py.

    Differentiable end-to-end: the custom_vjp's backward is the fused
    gradient kernel (see module docstring). ``block_b`` is the batch tile
    per grid step (:func:`block_b_for` picks it from the stream dtype).
    """
    return _lstm_cell_padded(interpret, block_b,
                             *vma.match_vma(wx, wh, b, x, h, c))
