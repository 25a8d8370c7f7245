"""Jitted public wrapper for the Pallas flash-attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k"))
def flash_attention(q, k, v, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128):
    return flash_attention_kernel(q, k, v, causal=causal, blk_q=blk_q,
                                  blk_k=blk_k)
