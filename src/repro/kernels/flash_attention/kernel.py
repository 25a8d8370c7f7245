"""Blockwise (flash) causal attention as a Pallas TPU kernel.

Grid (B, KV, G, nQ, nK) — nK innermost so the (m, l, acc) online-softmax
state lives in VMEM scratch across the kv sweep for one q block:

  kj == 0      : init scratch
  every kj     : s = q k^T (MXU), online-softmax update (VPU)
  kj == nK - 1 : normalize and write the output block

Causal block skipping: kv blocks strictly above the diagonal contribute
nothing; @pl.when guards the compute so the MXU work matches the
triangular FLOP count (the XLA fallback in repro.models.layers pays the
same schedule via the triangular pair scan).  Block shapes default to
(128, 128) — MXU-aligned on the (sublane, lane) dims.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NEG_INF = -1e30


def _body(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
          scale, causal, blk_q, blk_k, n_k):
    qi = pl.program_id(3)
    kj = pl.program_id(4)

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _compute():
        q = q_ref[0, 0, 0].astype(jnp.float32)    # (blk_q, dh)
        k = k_ref[0, 0].astype(jnp.float32)       # (blk_k, dh)
        v = v_ref[0, 0].astype(jnp.float32)       # (blk_k, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kj * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + p.sum(axis=1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    if causal:
        # skip kv blocks strictly above the causal diagonal
        pl.when(kj * blk_k <= qi * blk_q + blk_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kj == n_k - 1)
    def _flush():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal=True, blk_q=128, blk_k=128):
    """q: (B, Sq, H, dh); k/v: (B, Sk, KV, dh/dv); GQA via H = KV * G."""
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kvh
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, sk)
    assert sq % blk_q == 0 and sk % blk_k == 0
    n_q, n_k = sq // blk_q, sk // blk_k
    scale = 1.0 / math.sqrt(dh)

    # layout: (B, KV, G, S, d)
    qr = q.reshape(b, sq, kvh, g, dh).transpose(0, 2, 3, 1, 4)
    kr = k.transpose(0, 2, 1, 3)          # (b, kv, sk, dh)
    vr = v.transpose(0, 2, 1, 3)

    out = pallas_call(
        functools.partial(_body, scale=scale, causal=causal, blk_q=blk_q,
                          blk_k=blk_k, n_k=n_k),
        grid=(b, kvh, g, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, 1, blk_q, dh),
                         lambda b, h, g, qi, kj: (b, h, g, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, dh),
                         lambda b, h, g, qi, kj: (b, h, kj, 0)),
            pl.BlockSpec((1, 1, blk_k, dv),
                         lambda b, h, g, qi, kj: (b, h, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, blk_q, dv),
                               lambda b, h, g, qi, kj: (b, h, g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, n_q * blk_q, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, dv), jnp.float32),
        ],
    )(qr, kr, vr)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)
