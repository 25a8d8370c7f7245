"""The Pallas kernels compile for a TPU v5e at the widths the main path uses.

Nothing runs: each test compiles one kernel for a chip described by
``jax.experimental.topologies`` (the TPU compiler is installed with JAX),
which refuses what interpret mode accepts — misaligned blocks, too much
VMEM.  The topology is described inside a module-scoped fixture, never at
import, so every pytest-xdist worker collects the same tests and only the
worker that runs this file loads the TPU library.  Keep all such compiles
in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.microbench.kernel import TILE, microbench_kernel
from repro.kernels.ssd.kernel import ssd_chunk_kernel


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_microbench_compiles_for_v5e(one_chip):
    cores = 16
    hlo = _compile(functools.partial(microbench_kernel, n_iters=64, unroll=32),
                   ((cores * TILE[0], TILE[1]), jnp.float32),
                   sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_for_v5e_at_hymba_heads(one_chip):
    cfg = get_config("hymba-1.5b")
    s, dh = 1024, cfg.head_dim
    hlo = _compile(flash_attention_kernel,
                   ((1, s, cfg.n_heads, dh), jnp.bfloat16),
                   ((1, s, cfg.n_kv, dh), jnp.bfloat16),
                   ((1, s, cfg.n_kv, dh), jnp.bfloat16),
                   sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_ssd_compiles_for_v5e_at_mamba2_widths(one_chip):
    ssm = get_config("mamba2-130m").ssm
    h, p, n, q = ssm.d_inner // ssm.headdim, ssm.headdim, ssm.n_state, ssm.chunk
    b, nc = 1, 2048 // q
    hlo = _compile(ssd_chunk_kernel,
                   ((b, nc, h, q, p), jnp.float32),
                   ((b, nc, q, n), jnp.float32),
                   ((b, nc, q, n), jnp.float32),
                   ((b, nc, h, q), jnp.float32),
                   ((b, nc, h, q), jnp.float32),
                   sharding=one_chip)
    assert "tpu_custom_call" in hlo
