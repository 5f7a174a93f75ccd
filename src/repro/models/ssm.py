"""Mamba2 block via SSD (state-space duality), Dao & Gu 2024 [arXiv:2405.21060].

Chunked algorithm: within a chunk the token mixing is a masked quadratic
(attention-like) einsum; across chunks a first-order recurrence carries the
(H, P, N) state. That recurrence is *structurally the Holt-Winters level
update* (h_t = a_t * h_{t-1} + b_t) -- the same series-on-lanes/time-in-VMEM
schedule as kernels/hw_scan.py applies (DESIGN.md section 5).

Decode is the O(1) recurrent step on a persistent (B, H, P, N) state plus a
(B, K-1, conv_dim) causal-conv tail cache.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import dense_init, rms_norm


class SSMCache(NamedTuple):
    state: jax.Array      # (B, H, P, N)
    conv: jax.Array       # (B, K-1, conv_dim) last inputs, time-major


def ssm_init(key, cfg: ArchConfig, dtype):
    d = cfg.d_model
    di = cfg.d_inner
    h = cfg.ssm_nheads
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    conv_dim = di + 2 * g * n
    ks = jax.random.split(key, 4)
    return {
        # order: [z (di), x (di), B (g*n), C (g*n), dt (h)]
        "w_in": dense_init(ks[0], d, 2 * di + 2 * g * n + h, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_dim), jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.zeros((h,), jnp.float32),          # A = -exp(a_log)
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "out_norm": jnp.ones((di,), dtype),
        "w_out": dense_init(ks[2], di, d, dtype),
    }


def _split_proj(cfg: ArchConfig, zxbcdt):
    di = cfg.d_inner
    g, n, h = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    z = zxbcdt[..., :di]
    x = zxbcdt[..., di : 2 * di]
    bb = zxbcdt[..., 2 * di : 2 * di + g * n]
    cc = zxbcdt[..., 2 * di + g * n : 2 * di + 2 * g * n]
    dt = zxbcdt[..., 2 * di + 2 * g * n :]
    return z, x, bb, cc, dt


def _causal_conv(u, w, b, *, tail: Optional[jax.Array] = None):
    """Depthwise causal conv1d. u: (B, T, C); w: (K, C). Returns same shape
    plus the new (K-1)-tail for caches."""
    k = w.shape[0]
    if tail is None:
        pad = jnp.zeros((u.shape[0], k - 1, u.shape[2]), u.dtype)
    else:
        pad = tail.astype(u.dtype)
    up = jnp.concatenate([pad, u], axis=1)             # (B, T+K-1, C)
    out = sum(up[:, i : i + u.shape[1], :] * w[i][None, None, :] for i in range(k))
    new_tail = up[:, -(k - 1) :, :] if k > 1 else None
    return jax.nn.silu(out + b), new_tail


def _segsum(a):
    """Lower-triangular segment sums: out[i, j] = sum_{j < l <= i} a[l].

    a: (..., Q). Returns (..., Q, Q) with -inf above the diagonal."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # sum_{j<l<=i}
    i = jnp.arange(q)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, a, bb, cc, *, chunk: int):
    """SSD forward. x: (B,T,H,P); dt: (B,T,H); a: (H,) negative;
    bb, cc: (B,T,G,N). Returns y: (B,T,H,P) and final state (B,H,P,N)."""
    b, t, h, p = x.shape
    g, n = bb.shape[2], bb.shape[3]
    q = min(chunk, t)
    nc = t // q
    assert nc * q == t, "T must be a multiple of the SSD chunk"
    rep = h // g

    xc = x.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h)
    bc = bb.reshape(b, nc, q, g, n)
    cc_ = cc.reshape(b, nc, q, g, n)

    # decay math stays fp32 (exp of cumsums); the *large* einsum operands
    # and outputs run in the input dtype -- on a bf16 pod this halves the
    # dominant memory-roofline traffic AND keeps every gradient tensor bf16
    # (Perf hillclimb 2, iteration 1: fp32 intermediates forced f32 grads
    # through the whole backward).
    cdt = x.dtype
    da = dtc * a[None, None, None, :]                   # (B,NC,Q,H) negative
    cum = jnp.cumsum(da, axis=2)

    # intra-chunk (diagonal) term
    # exp/segsum in fp32, then the (Q, Q) product chain in compute dtype
    # (iteration 2: the three (B,NC,H,Q,Q) L-chain tensors were still f32)
    l_mat = jnp.exp(_segsum(jnp.moveaxis(da, 3, 2))).astype(cdt)  # (B,NC,H,Q,Q)
    cb = jnp.einsum("bcqgn,bckgn->bcgqk", cc_, bc)      # (B,NC,G,Q,Q)
    cb = jnp.repeat(cb, rep, axis=2)                    # (B,NC,H,Q,Q)
    scores = cb * l_mat * jnp.moveaxis(dtc, 3, 2).astype(cdt)[:, :, :, None, :]
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", scores, xc)

    # chunk-final states: sum_k exp(cum_end - cum_k) * dt_k * B_k x_k
    decay = jnp.exp(cum[:, :, -1:, :] - cum)            # (B,NC,Q,H)
    xw = xc * (dtc * decay).astype(cdt)[..., None]      # (B,NC,Q,H,P)
    bh = jnp.repeat(bc, rep, axis=3)                    # (B,NC,Q,H,N) -- G->H
    states = jnp.einsum("bcqhn,bcqhp->bchpn", bh.astype(cdt), xw).astype(jnp.float32)

    # inter-chunk recurrence over NC: S_c = exp(sum da_c) * S_{c-1} + states_c
    # (carried in fp32: it is the long recurrence)
    chunk_decay = jnp.exp(cum[:, :, -1, :])             # (B,NC,H)

    def step(s_prev, inp):
        dec, st = inp
        s_new = s_prev * dec[:, :, None, None] + st
        return s_new, s_prev                            # emit state *entering* chunk

    # derived from the data, so that inside shard_map the carry varies over
    # the same mesh axes as the per-chunk states
    s0 = jnp.zeros_like(states[:, 0])
    s_final, s_in = jax.lax.scan(
        step,
        s0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)),
    )
    s_in = jnp.moveaxis(s_in, 0, 1)                     # (B,NC,H,P,N)

    # inter-chunk contribution: C_t . (decay-to-t * S_in)
    in_decay = jnp.exp(cum)                             # (B,NC,Q,H)
    ch = jnp.repeat(cc_, rep, axis=3)                   # (B,NC,Q,H,N)
    y_off = jnp.einsum("bcqhn,bchpn->bcqhp", ch.astype(cdt),
                       s_in.astype(cdt)) * in_decay.astype(cdt)[..., None]

    y = (y_diag + y_off).reshape(b, t, h, p)
    return y, s_final


def ssm_apply(p, cfg: ArchConfig, u, *, cache: Optional[SSMCache] = None):
    """u: (B, T, d). Train/prefill (cache None -> chunked SSD) or decode
    (cache set, T == 1 recurrent step). Returns (out, new_cache)."""
    b, t, d = u.shape
    di, h, pp = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim
    g, n = cfg.ssm_ngroups, cfg.ssm_state

    zxbcdt = u @ p["w_in"]
    z, x, bb, cc, dt = _split_proj(cfg, zxbcdt)
    conv_in = jnp.concatenate([x, bb, cc], axis=-1)
    a = -jnp.exp(p["a_log"])                             # (H,)
    dt_act = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,T,H)

    if cache is None:
        conv_out, tail = _causal_conv(conv_in, p["conv_w"], p["conv_b"])
        x_, bb_, cc_ = (conv_out[..., :di],
                        conv_out[..., di : di + g * n],
                        conv_out[..., di + g * n :])
        xh = x_.reshape(b, t, h, pp)
        bbr = bb_.reshape(b, t, g, n)
        ccr = cc_.reshape(b, t, g, n)
        dtr = dt_act
        # pad T to a chunk multiple: dt == 0 on padding makes the recurrence
        # a no-op (decay exp(0) = 1, update 0), so the final state is exact.
        q = min(cfg.ssm_chunk, t)
        pad = (-t) % q
        if pad:
            padt = lambda z: jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
            xh, bbr, ccr, dtr = padt(xh), padt(bbr), padt(ccr), padt(dtr)
        y, s_final = ssd_chunked(xh, dtr, a, bbr, ccr, chunk=q)
        y = y[:, :t]
        new_cache = SSMCache(state=s_final, conv=tail) if tail is not None else None
    else:
        # decode: conv over cached tail + this step
        conv_out, tail = _causal_conv(conv_in, p["conv_w"], p["conv_b"], tail=cache.conv)
        x_, bb_, cc_ = (conv_out[..., :di],
                        conv_out[..., di : di + g * n],
                        conv_out[..., di + g * n :])
        xh = x_.reshape(b, t, h, pp)[:, -1]              # (B,H,P)
        bt = bb_.reshape(b, t, g, n)[:, -1]              # (B,G,N)
        ct = cc_.reshape(b, t, g, n)[:, -1]
        dt1 = dt_act[:, -1]                              # (B,H)
        da = jnp.exp(dt1 * a[None, :])                   # (B,H)
        rep = h // g
        bh = jnp.repeat(bt, rep, axis=1)                 # (B,H,N)
        ch = jnp.repeat(ct, rep, axis=1)
        upd = jnp.einsum("bhp,bhn->bhpn", xh * dt1[..., None], bh.astype(jnp.float32))
        state = cache.state * da[:, :, None, None] + upd
        yt = jnp.einsum("bhpn,bhn->bhp", state, ch.astype(jnp.float32))
        y = yt[:, None].reshape(b, 1, h, pp)
        new_cache = SSMCache(state=state, conv=tail)

    # D skip on the post-conv SSM input (compute dtype)
    y = y.astype(u.dtype) + (p["d_skip"].astype(u.dtype)[None, None, :, None]
                             * x_.reshape(b, t, h, pp).astype(u.dtype))
    y = y.reshape(b, t, di)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"])
    return y @ p["w_out"], new_cache


def make_ssm_cache(cfg: ArchConfig, batch: int, dtype) -> SSMCache:
    conv_dim = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return SSMCache(
        state=jnp.zeros((batch, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32),
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
    )
