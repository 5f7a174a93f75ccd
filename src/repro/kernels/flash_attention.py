"""Pallas TPU kernel: chunked online-softmax (flash) attention, GQA-aware.

Beyond-paper kernel used by the LM architecture stack. IO-aware schedule for
the TPU memory hierarchy: ``(BQ, D)`` query tiles stay resident in VMEM while
``(BK, D)`` key/value tiles stream; the softmax is computed online with
running (max, sum) carried in VMEM scratch across the sequential innermost
grid dimension, so the ``(Tq, Tk)`` score matrix never exists in HBM.

Grid: ``(batch*heads, Tq/BQ, Tk/BK)`` -- the last dimension is sequential on
TPU, which is what makes the scratch-carried accumulator pattern valid.
GQA is expressed in the BlockSpec index maps (q head -> kv head), no
materialized head broadcast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, q_offset: int,
                  block_q: int, block_k: int, num_k_blocks: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                  # (BQ, D)
    k = k_ref[0]                                  # (BK, D)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (BQ,BK)

    if causal:
        qi = pl.program_id(1)
        q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
        k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_ids <= q_ids, s, NEG_INF)

    m_prev = m_ref[...]                           # (BQ, 1)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                        # (BQ, BK)
    corr = jnp.exp(m_prev - m_new)                # (BQ, 1)
    l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *, causal: bool = True,
    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
    interpret: bool = False,
):
    """q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D). Tq % BQ == Tk % BK == 0.

    Causal mask aligns the ends of q and k (prefill: Tq == Tk; decode-append:
    Tq < Tk means queries sit at the end of the key timeline).
    """
    b, hq, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = 1.0 / (d ** 0.5)
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    num_k_blocks = tk // bk
    q_offset = tk - tq

    qr = q.reshape(b * hq, tq, d)
    kr = k.reshape(b * hkv, tk, d)
    vr = v.reshape(b * hkv, tk, d)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        # GQA: query head bh -> kv head (bh // group) within the same batch
        batch = bh // hq
        head = (bh % hq) // group
        return (batch * hkv + head, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, q_offset=q_offset,
        block_q=bq, block_k=bk, num_k_blocks=num_k_blocks,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, tq // bq, num_k_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, tq, d)

