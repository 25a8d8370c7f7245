"""Mamba-2 SSD intra-chunk kernel (Pallas TPU).

The chunked SSD computation splits into (a) a quadratic *intra-chunk* part
— attention-like (q x q) masked products, MXU-friendly — and (b) a tiny
sequential inter-chunk state recurrence.  The kernel computes (a) per
(batch, chunk, head) grid cell:

    L    = exp(cs_i - cs_j)  (causal-masked)        VPU
    cb   = C B^T                                    MXU
    y    = (cb * L * dt_j) x                        MXU
    S    = (B * exp(cs_last - cs) * dt)^T x         MXU  (chunk state)

The log-decay cumsum ``cs`` is precomputed in XLA (cheap, elementwise); the
inter-chunk recurrence stays a lax.scan in ops.py — the TPU-native split of
the paper's GPU algorithm (DESIGN.md: adapt, don't port).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call


def _body(x_ref, b_ref, c_ref, cs_ref, dt_ref, y_ref, s_ref, *, chunk):
    x = x_ref[0, 0, 0].astype(jnp.float32)          # (q, p)
    B = b_ref[0, 0].astype(jnp.float32)             # (q, n)
    C = c_ref[0, 0].astype(jnp.float32)             # (q, n)
    cs = cs_ref[0, 0, 0].astype(jnp.float32)        # (1, q) — lane-major row
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (1, q)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = ii == jj

    def column(row):
        # (1, q) -> (q, 1): each row of where(eye, row, 0) holds one
        # nonzero, so the lane sum is exact
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    decay = column(cs) - cs                         # (q, q)
    L = jnp.exp(jnp.where(ii >= jj, decay, -jnp.inf))
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    att = cb * L * dt
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    w = jnp.exp(cs[:, chunk - 1:] - cs) * dt        # (1, q)
    s = jax.lax.dot_general(x, B * column(w), (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (p, n)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    s_ref[0, 0, 0] = s.astype(s_ref.dtype)


def ssd_chunk_kernel(x, B, C, cs, dt):
    """x: (b, nc, h, q, p); B/C: (b, nc, q, n); cs/dt: (b, nc, h, q).
    Returns y_intra (b, nc, h, q, p) and chunk states S (b, nc, h, p, n).

    cs/dt go in as (b, nc, h, 1, q), so that the last two dims of their
    (1, 1, 1, 1, q) blocks equal the array's, as Mosaic requires."""
    b, nc, h, q, p = x.shape
    n = B.shape[-1]
    cs = cs.reshape(b, nc, h, 1, q)
    dt = dt.reshape(b, nc, h, 1, q)
    return pallas_call(
        functools.partial(_body, chunk=q),
        grid=(b, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, q), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, q), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
    )(x, B, C, cs, dt)
