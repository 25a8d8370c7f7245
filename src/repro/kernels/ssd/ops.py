"""Full SSD via the Pallas intra-chunk kernel + XLA inter-chunk recurrence.

Drop-in equivalent of repro.models.ssm.ssd_ref (same (y, final_state)
contract) for seq lengths divisible by the chunk size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_chunk_kernel


ssd_chunk = jax.jit(ssd_chunk_kernel)


def ssd_pallas(x, dt, A, B, C, chunk: int):
    """x: (b, l, h, p); dt: (b, l, h); A: (h,); B/C: (b, l, n).
    Returns (y (b,l,h,p) fp32, final state (b,h,p,n) fp32)."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    assert l % chunk == 0, "pallas path requires l % chunk == 0"
    nc = l // chunk
    xr = x.astype(jnp.float32).reshape(b, nc, chunk, h, p).transpose(0, 1, 3, 2, 4)
    Br = B.astype(jnp.float32).reshape(b, nc, chunk, n)
    Cr = C.astype(jnp.float32).reshape(b, nc, chunk, n)
    dtr = dt.astype(jnp.float32).reshape(b, nc, chunk, h).transpose(0, 1, 3, 2)
    dA = dtr * A[None, None, :, None]                    # (b,nc,h,q)
    cs = jnp.cumsum(dA, axis=-1)

    y_intra, S = ssd_chunk(xr, Br, Cr, cs, dtr)

    # inter-chunk recurrence (tiny sequential scan, stays in XLA)
    dA_chunk = jnp.exp(cs[..., -1])                      # (b,nc,h)

    def step(hstate, inp):
        S_c, dA_c = inp
        out = hstate
        return hstate * dA_c[..., None, None] + S_c, out

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    hfinal, h_in = jax.lax.scan(
        step, h0, (S.transpose(1, 0, 2, 3, 4), dA_chunk.transpose(1, 0, 2)))
    h_in = h_in.transpose(1, 0, 2, 3, 4)                 # (b,nc,h,p,n)

    y_inter = jnp.einsum("bcqn,bchpn->bchqp", Cr, h_in) * jnp.exp(cs)[..., None]
    y = (y_intra + y_inter).transpose(0, 1, 3, 2, 4).reshape(b, l, h, p)
    return y, hfinal
