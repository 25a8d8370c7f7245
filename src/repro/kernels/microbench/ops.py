"""Jitted public wrapper for the microbench workload."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.microbench.kernel import TILE, microbench_kernel


@functools.partial(jax.jit, static_argnames=("n_iters", "unroll"))
def microbench(x: jax.Array, n_iters: int = 64, unroll: int = 32) -> jax.Array:
    return microbench_kernel(x, n_iters=n_iters, unroll=unroll)


def make_input(cores: int, seed: int = 0) -> jax.Array:
    k = jax.random.PRNGKey(seed)
    return jax.random.uniform(k, (cores * TILE[0], TILE[1]), jnp.float32)


def flops_per_core(n_iters: int, unroll: int) -> float:
    """2 flops (mul+add) per element per chain step."""
    return 2.0 * n_iters * unroll * TILE[0] * TILE[1]
