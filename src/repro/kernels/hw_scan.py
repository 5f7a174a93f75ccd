"""Pallas TPU kernel: batched Holt-Winters exponential smoothing scan.

This is the paper's hot spot adapted to the TPU memory hierarchy. The GPU
implementation parallelizes series across CUDA threads; the TPU-native
schedule is:

* series tiled onto the **lane** dimension (128-wide VPU vectors) --
  time-major layout ``(T, N)`` so each time step is one vector op row;
* the sequential time recurrence runs as an in-kernel ``fori_loop`` with all
  state (level vector, M-row seasonality ring) resident in **VMEM** -- zero
  HBM traffic inside the loop beyond the streamed y rows and emitted outputs;
* grid over series blocks: each grid step owns a ``(T, BN)`` tile.

The seasonality ring holds rows ``s`` for times ``t === row (mod M)``; at step
``t`` slot ``t mod M`` is read (s_t) and overwritten with ``s_{t+M}``, exactly
Eq. 3 with multiplicative seasonality and no trend (Smyl variant).

Differentiation (the paper's actual workload is *training*): ``hw_scan_tm``
carries a :func:`jax.custom_vjp` whose backward pass is a second Pallas
kernel running the adjoint recurrence time-reversed. The forward already
emits the ``(levels, seas)`` residuals the adjoint needs, so nothing extra is
saved beyond the inputs. With ``lam_t`` the level cotangent and ``sig_t`` the
seasonality cotangent, reversing

    l_t     = alpha * y_t / s_t + (1 - alpha) * l_{t-1}
    s_{t+m} = gamma * y_t / l_t + (1 - gamma) * s_t

gives, for t = T-1 .. 0 (``dl``/``ds`` are the output cotangents):

    lam_t = dl_t + (1 - alpha) * lam_{t+1} - sig_{t+m} * gamma * y_t / l_t^2
    sig_t = ds_t + (1 - gamma) * sig_{t+m} - lam_t * alpha * y_t / s_t^2
    dy_t    = lam_t * alpha / s_t + sig_{t+m} * gamma / l_t
    dalpha += lam_t * (y_t / s_t - l_{t-1})
    dgamma += sig_{t+m} * (y_t / l_t - s_t)

The ``sig`` values live in the same M-row VMEM ring as the forward (slot
``t mod m`` holds ``sig_{t+m}`` before step t and ``sig_t`` after), seeded
with the trailing future-factor cotangents ``ds_{T..T+M-1}``; after the loop
the ring *is* ``d init_seas`` (slot k holds ``sig_k``). The synthetic initial
level ``l_{-1} = y_0 / s_0`` closes the recurrence: its cotangent
``(1 - alpha) * lam_0`` routes to ``y_0`` and ring slot 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sharding import vma

# Series-per-block: one full lane row. Sublane dim is time (streamed).
BLOCK_N = 128


def _row(ref, t):
    """Row ``t`` of a ``(rows, BN)`` ref as a ``(1, BN)`` tile.

    Every value in both kernels is a 2-D ``(1, BN)`` row: Mosaic lays out
    vectors of rank 2 and up, and a dynamic single-row access at ``t`` is
    a sublane-offset load or store on an fp32 tile.
    """
    return ref[pl.ds(t, 1), :]


def _hw_scan_kernel(y_ref, a_ref, g_ref, s0_ref, lev_ref, seas_ref, ring_ref,
                    *, t_len: int, m: int):
    alpha = a_ref[...]                      # (1, BN)
    gamma = g_ref[...]

    # init the seasonality ring in VMEM scratch
    ring_ref[...] = s0_ref[...]

    def body(t, l_prev):
        slot = jax.lax.rem(t, m)
        y_t = _row(y_ref, t)
        s_t = _row(ring_ref, slot)
        l_t = alpha * y_t / s_t + (1.0 - alpha) * l_prev
        s_new = gamma * y_t / l_t + (1.0 - gamma) * s_t
        ring_ref[pl.ds(slot, 1), :] = s_new
        lev_ref[pl.ds(t, 1), :] = l_t
        seas_ref[pl.ds(t, 1), :] = s_t
        return l_t

    l0 = _row(y_ref, 0) / _row(s0_ref, 0)
    jax.lax.fori_loop(0, t_len, body, l0)

    # trailing future factors s_T .. s_{T+M-1} live in ring slots (T+k) mod M
    for k in range(m):  # m is static and small (<= 24)
        seas_ref[pl.ds(t_len + k, 1), :] = _row(ring_ref, (t_len + k) % m)


def _hw_scan_bwd_kernel(y_ref, a_ref, g_ref, lev_ref, seas_ref,
                        dlev_ref, dseas_ref,
                        dy_ref, da_ref, dg_ref, ds0_ref, ring_ref,
                        *, t_len: int, m: int):
    """Adjoint recurrence, time-reversed, same (T, BN) lane layout.

    The sigma ring mirrors the forward's seasonality ring: before reverse
    step t, slot ``t mod m`` holds ``sig_{t+m}`` (the fully-accumulated
    cotangent of s_{t+m}); the step overwrites it with ``sig_t``.
    """
    alpha = a_ref[...]                      # (1, BN)
    gamma = g_ref[...]
    # s_0 == init_seas_0: the forward emits it as seas row 0, so the
    # init_seas array itself need not be streamed into the backward.
    s00 = _row(seas_ref, 0)
    y0 = _row(y_ref, 0)

    # seed: the trailing future factors s_T .. s_{T+M-1} are pure outputs,
    # so their cotangents are exactly the incoming dseas rows.
    for k in range(m):
        ring_ref[pl.ds((t_len + k) % m, 1), :] = _row(dseas_ref, t_len + k)

    zeros = jnp.zeros_like(alpha)

    def body(i, carry):
        lam_next, da, dg = carry
        t = t_len - 1 - i
        slot = jax.lax.rem(t, m)
        y_t = _row(y_ref, t)
        l_t = _row(lev_ref, t)
        s_t = _row(seas_ref, t)
        # l_{t-1}: levels row t-1 for t > 0, else the primer l_{-1} = y_0/s_0
        l_prev = jnp.where(t > 0, _row(lev_ref, jnp.maximum(t - 1, 0)),
                           y0 / s00)
        sig_tpm = _row(ring_ref, slot)

        lam_t = (_row(dlev_ref, t)
                 + (1.0 - alpha) * lam_next
                 - sig_tpm * gamma * y_t / (l_t * l_t))
        sig_t = (_row(dseas_ref, t)
                 + (1.0 - gamma) * sig_tpm
                 - lam_t * alpha * y_t / (s_t * s_t))
        ring_ref[pl.ds(slot, 1), :] = sig_t

        dy_t = lam_t * alpha / s_t + sig_tpm * gamma / l_t
        # l_{-1} = y_0 / s_0 adds (1-alpha)*lam_0 / s_0 to dy_0
        dy_t = dy_t + jnp.where(t == 0, (1.0 - alpha) * lam_t / s00, 0.0)
        dy_ref[pl.ds(t, 1), :] = dy_t

        da = da + lam_t * (y_t / s_t - l_prev)
        dg = dg + sig_tpm * (y_t / l_t - s_t)
        return lam_t, da, dg

    lam0, da, dg = jax.lax.fori_loop(0, t_len, body, (zeros, zeros, zeros))

    da_ref[...] = da
    dg_ref[...] = dg
    # after the loop, ring slot k holds sig_k == d loss / d init_seas_k
    ds0_ref[...] = ring_ref[...]
    # ... minus the primer-level term through l_{-1} = y_0 / s_0 on slot 0
    corr = (1.0 - alpha) * lam0 * y0 / (s00 * s00)
    ds0_ref[pl.ds(0, 1), :] = _row(ds0_ref, 0) - corr


def _hw_scan_fwd_call(y_tm, alpha, gamma, init_seas_tm, *, interpret: bool):
    t_len, n = y_tm.shape
    m = init_seas_tm.shape[0]
    # y arrives already widened to the param (state) dtype, which every
    # output and the VMEM ring carry (see hw_scan_tm)
    dtype = alpha.dtype
    grid = (n // BLOCK_N,)
    out = vma.out_shape(y_tm, alpha, gamma, init_seas_tm)

    kernel = functools.partial(_hw_scan_kernel, t_len=t_len, m=m)
    levels, seas = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_len, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((m, BLOCK_N), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((t_len, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((t_len + m, BLOCK_N), lambda i: (0, i)),
        ],
        out_shape=[
            out((t_len, n), dtype),
            out((t_len + m, n), dtype),
        ],
        scratch_shapes=[pltpu.VMEM((m, BLOCK_N), dtype)],
        interpret=interpret,
    )(y_tm, alpha[None, :], gamma[None, :], init_seas_tm)
    return levels, seas


def _hw_scan_bwd_call(y_tm, alpha, gamma, levels, seas, dlev, dseas, *,
                      m: int, interpret: bool):
    t_len, n = y_tm.shape
    dtype = alpha.dtype
    grid = (n // BLOCK_N,)
    out = vma.out_shape(y_tm, alpha, gamma, levels, seas, dlev, dseas)

    kernel = functools.partial(_hw_scan_bwd_kernel, t_len=t_len, m=m)
    col = lambda rows: pl.BlockSpec((rows, BLOCK_N), lambda i: (0, i))
    dy, da, dg, ds0 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            col(t_len),              # y
            col(1),                  # alpha
            col(1),                  # gamma
            col(t_len),              # levels
            col(t_len + m),          # seas
            col(t_len),              # dlevels
            col(t_len + m),          # dseas
        ],
        out_specs=[col(t_len), col(1), col(1), col(m)],
        out_shape=[
            out((t_len, n), dtype),
            out((1, n), dtype),
            out((1, n), dtype),
            out((m, n), dtype),
        ],
        scratch_shapes=[pltpu.VMEM((m, BLOCK_N), dtype)],
        interpret=interpret,
    )(y_tm, alpha[None, :], gamma[None, :], levels, seas, dlev, dseas)
    return dy, da[0], dg[0], ds0


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _hw_scan_tm(interpret, y_tm, alpha, gamma, init_seas_tm):
    return _hw_scan_fwd_call(y_tm, alpha, gamma, init_seas_tm,
                             interpret=interpret)


def _hw_scan_tm_fwd(interpret, y_tm, alpha, gamma, init_seas_tm):
    levels, seas = _hw_scan_fwd_call(y_tm, alpha, gamma, init_seas_tm,
                                     interpret=interpret)
    # residuals: the inputs plus the (levels, seas) the forward already
    # emits (seas row 0 covers init_seas_0, so the ring itself is not saved)
    return (levels, seas), (y_tm, alpha, gamma, levels, seas)


def _hw_scan_tm_bwd(interpret, res, cotangents):
    y_tm, alpha, gamma, levels, seas = res
    dlev, dseas = cotangents
    dy, da, dg, ds0 = _hw_scan_bwd_call(
        y_tm, alpha, gamma, levels, seas,
        jnp.asarray(dlev, levels.dtype), jnp.asarray(dseas, seas.dtype),
        m=seas.shape[0] - y_tm.shape[0], interpret=interpret)
    return dy, da, dg, ds0


_hw_scan_tm.defvjp(_hw_scan_tm_fwd, _hw_scan_tm_bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hw_scan_tm(y_tm, alpha, gamma, init_seas_tm, *, interpret: bool = False):
    """Time-major entry. y_tm: (T, N); alpha/gamma: (N,); init_seas_tm: (M, N).

    N must be a multiple of BLOCK_N (ops.py pads). Returns levels_tm (T, N)
    and seas_tm (T+M, N). Differentiable: carries a custom_vjp whose backward
    is the time-reversed adjoint kernel (see module docstring).

    Precision policy: y may be bf16, but the level/seasonality recurrence
    runs in the param dtype (fp32). y is widened here, before the kernel,
    so the kernel only ever reads and writes rows of the state dtype: a
    dynamic single-row access into a packed bf16 tile is not a layout the
    TPU compiler accepts. The widening's transpose returns dy in y's dtype.
    """
    y_tm = y_tm.astype(alpha.dtype)
    return _hw_scan_tm(interpret, y_tm, alpha, gamma, init_seas_tm)
