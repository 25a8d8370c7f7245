"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (on the CPU
the kernels run in interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.microbench import microbench, microbench_ref
from repro.kernels.microbench.ops import make_input
from repro.kernels.ssd.ops import ssd_pallas
from repro.models.ssm import ssd_ref


@pytest.mark.parametrize("cores", [1, 4, 16])
@pytest.mark.parametrize("n_iters,unroll", [(8, 4), (32, 16)])
def test_microbench_matches_ref(cores, n_iters, unroll):
    x = make_input(cores, seed=cores)
    a = microbench(x, n_iters=n_iters, unroll=unroll)
    b = microbench_ref(x, n_iters=n_iters, unroll=unroll)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kv,dh,dv,causal,blk",
    [(2, 64, 4, 2, 16, 16, True, 32),
     (1, 128, 8, 8, 32, 32, False, 64),
     (2, 64, 4, 1, 16, 8, True, 16),
     (1, 96, 6, 3, 8, 8, True, 32)])
def test_flash_attention_matches_oracle(b, s, h, kv, dh, dv, causal, blk, dtype):
    ks = [jax.random.PRNGKey(i) for i in range(3)]
    q = jax.random.normal(ks[0], (b, s, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, dv), dtype)
    out = flash_attention(q, k, v, causal=causal, blk_q=blk, blk_k=blk)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("b,l,h,p,n,chunk",
                         [(1, 32, 2, 8, 8, 16), (2, 64, 3, 8, 16, 16),
                          (1, 128, 4, 16, 32, 32)])
def test_ssd_pallas_matches_model_ref(b, l, h, p, n, chunk):
    ks = [jax.random.PRNGKey(i) for i in range(5)]
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n))
    C = jax.random.normal(ks[4], (b, l, n))
    y1, h1 = ssd_pallas(x, dt, A, B, C, chunk)
    y2, h2 = ssd_ref(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=2e-4, rtol=2e-4)
