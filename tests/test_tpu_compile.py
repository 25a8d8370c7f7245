"""The Pallas kernels compile for a TPU v5e at the widths the main path uses,
and the serving decode step compiles there without copying its caches.

Nothing runs: each test compiles one kernel, or one decode step, for a chip
described by ``jax.experimental.topologies`` (the TPU compiler is installed
with JAX), which refuses what interpret mode accepts — misaligned blocks,
too much VMEM — and shows the copies XLA:TPU inserts, which XLA:CPU does not
predict.  The topology is described inside a module-scoped fixture, never at
import, so every pytest-xdist worker collects the same tests and only the
worker that runs this file loads the TPU library.  Keep all such compiles
in this one file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.microbench.kernel import TILE, microbench_kernel
from repro.kernels.ssd.kernel import ssd_chunk_kernel
from repro.models import decode, lm
from repro.parallel.sharding import make_env
from repro.runtime import serve_loop


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


# the serving benchmark's decode shapes: (arch, batch, prompt + new tokens)
DECODE_CASES = [("mamba2-130m", 64, 512 + 256), ("hymba-1.5b", 16, 1024 + 256)]
_HLO_TYPES = {"float32": "f32", "bfloat16": "bf16"}


def _decode_step_hlo(arch, batch, max_len, sharding=None):
    """``serve_loop._decode_step`` (cache donated) compiled at full width;
    returns its HLO text and the cache's shapes and logical axes."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda k: lm.init(k, cfg)[0], jax.random.PRNGKey(0))
    cache, axes = decode.cache_spec(cfg, batch, max_len)

    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=sharding)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    compiled = serve_loop._decode_step.lower(
        spec(params), spec(cache), tok, pos, cfg, make_env(cfg, None)).compile()
    return compiled.as_text(), cache, axes


def _stack_copies(hlo, cache, axes):
    """Names of the copies in compiled HLO text of a layer-stacked cache leaf
    whole, anywhere, or of one layer's slice of one inside a loop.  (The
    hybrid family's global layers keep unstacked conv states of a few
    hundred KB, which the entry computation copies: their new state is the
    old one shifted, written into the same donated buffer.)"""
    stacks = [(_HLO_TYPES[np.dtype(a.dtype).name], tuple(a.shape))
              for name, a in cache.items() if axes[name][0] is None]
    whole = set(stacks)
    layer = {(t, shape[1:]) for t, shape in stacks}
    found, comp = [], ""
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = line
        m = re.match(r"\s*(?:ROOT )?%([\w.-]+) = \(?(\w+)\[([\d,]*)\]", line)
        if m is None or not re.search(r"[)}] copy(-start)?\(", line):
            continue
        key = (m[2], tuple(int(d) for d in m[3].split(",") if d))
        if key in whole or (key in layer and not comp.startswith("ENTRY")):
            found.append(m[1])
    return found


@pytest.mark.parametrize("arch,batch,max_len", DECODE_CASES)
def test_decode_step_updates_cache_stacks_in_place_for_v5e(
        one_chip, arch, batch, max_len):
    """No layer-stacked cache is copied whole, nor a layer of one inside the
    layer loop: the donated cache is updated where it lies."""
    hlo, cache, axes = _decode_step_hlo(arch, batch, max_len, one_chip)
    assert any(axes[n][0] is None for n in cache)
    assert _stack_copies(hlo, cache, axes) == []


@pytest.mark.parametrize("arch,batch,max_len", DECODE_CASES)
def test_decode_step_updates_cache_stacks_in_place_on_the_chip(
        arch, batch, max_len):
    """The same, compiled for the TPU this process holds."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU")
    hlo, cache, axes = _decode_step_hlo(arch, batch, max_len)
    assert _stack_copies(hlo, cache, axes) == []
