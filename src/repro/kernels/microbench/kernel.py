"""The paper's artificial iterative workload as a Pallas TPU kernel.

"The same arithmetic instruction repeated multiple times in each performed
iteration" (§V), adapted to the TPU: one grid program per core stand-in
(CUDA SM -> grid cell), each running `n_iters` iterations of an unrolled
FMA chain on a VPU-aligned (8, 128) VMEM tile.  The chain is sequentially
dependent (a = a*c1 + c2), so runtime tracks clock frequency rather than
memory bandwidth — the property the methodology needs from its workload.

On real hardware the per-iteration timestamps come from the host bracketing
kernel launches (TPU exposes no in-kernel global timer); the simulator
provides the timeline for the measurement pipeline.  The kernel is compiled
by Mosaic on a TPU and interpreted on the CPU, where the tests check it
against ref.py bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call

TILE = (8, 128)          # float32 VPU tile


def _body(x_ref, o_ref, *, n_iters, unroll):
    a = x_ref[...]
    c1 = jnp.float32(1.000000119)          # keeps the chain bounded
    c2 = jnp.float32(1e-7)

    def iter_fn(_, a):
        for _ in range(unroll):            # unrolled FMA chain
            a = a * c1 + c2
        return a

    a = jax.lax.fori_loop(0, n_iters, iter_fn, a)
    o_ref[...] = a


def microbench_kernel(x: jax.Array, *, n_iters: int = 64,
                      unroll: int = 32) -> jax.Array:
    """x: (cores * 8, 128) float32 — one (8,128) tile per core."""
    cores = x.shape[0] // TILE[0]
    return pallas_call(
        functools.partial(_body, n_iters=n_iters, unroll=unroll),
        grid=(cores,),
        in_specs=[pl.BlockSpec(TILE, lambda i: (i, 0))],
        out_specs=pl.BlockSpec(TILE, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)
