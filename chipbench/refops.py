"""Plain float32 building blocks of the benchmark's reference models.

Nothing here imports the program under test.  Every matrix product goes
through :func:`mm`, which runs at ``highest`` precision (on a TPU a float32
product is otherwise done in bfloat16 passes) and, given a quantizer, first
rounds both operands through it: that is how the lower-precision control of
``correct`` is computed from the same code.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FP8_MAX = 448.0   # largest finite float8_e4m3fn


def _round_fp8(x):
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


@jax.custom_vjp
def fp8_qdq(x):
    """Round ``x`` through float8 e4m3 with one scale per tensor (its largest
    magnitude maps to the format's largest finite value), back to float32.
    The gradient flowing back is rounded the same way, with its own scale."""
    return _round_fp8(x)


fp8_qdq.defvjp(lambda x: (_round_fp8(x), None),
               lambda _, ct: (_round_fp8(ct),))


QUANTIZERS = {None: None, "fp8": fp8_qdq}


class Static(dict):
    """A configuration dict usable as a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def mm(eq, a, b, q=None):
    """float32 einsum at highest precision; ``q`` rounds both operands."""
    a, b = a.astype(F32), b.astype(F32)
    if q is not None:
        a, b = q(a), q(b)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


# --------------------------------------------------------------------------- #
# weights: the draws the served and trained models are initialised with
# --------------------------------------------------------------------------- #
def dense(key, shape, dtype, scale=None):
    """Normal draw scaled by 1/sqrt(shape[0]), stored in ``dtype``."""
    if scale is None:
        scale = 1.0 / math.sqrt(max(1, shape[0]))
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def stack(key, n, init_one):
    """``n`` layers' weights stacked on a leading axis: the draws of one
    ``init_one`` call per split key, made by one vmapped call so that the
    program traced and compiled holds the layer's init once, not ``n``
    times."""
    return jax.vmap(init_one)(jax.random.split(key, n))


def padded(vocab, pad_to):
    return (vocab + pad_to - 1) // pad_to * pad_to


def mamba2_init(key, spec, dtype):
    """Mamba-2 mixer with separate z/x/B/C/dt input projections."""
    d, s = spec["d_model"], spec["ssm"]
    di, n, w = s["d_inner"], s["n_state"], s["conv_width"]
    h = di // s["headdim"]
    ks = jax.random.split(key, 8)
    conv = lambda width: (jax.random.normal(ks[5], (w, width), F32)
                          * 0.1).astype(dtype)
    return {
        "w_z": dense(ks[0], (d, di), dtype), "w_x": dense(ks[1], (d, di), dtype),
        "w_B": dense(ks[2], (d, n), dtype), "w_C": dense(ks[3], (d, n), dtype),
        "w_dt": dense(ks[4], (d, h), dtype),
        "conv_x": conv(di), "conv_B": conv(n), "conv_C": conv(n),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h, dtype=F32)),
        "D": jnp.ones((h,), F32), "dt_bias": jnp.zeros((h,), F32),
        "norm": jnp.ones((di,), dtype),
        "w_out": dense(ks[6], (di, d), dtype),
    }


# --------------------------------------------------------------------------- #
# layers
# --------------------------------------------------------------------------- #
def rms_norm(x, w, eps=1e-6):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, pos, theta):
    """Rotary embedding on the last axis, halves rotated. x: (T, H, dh)."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(F32), 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(p, x, q=None):
    g = mm("td,df->tf", x, p["wg"], q)
    u = mm("td,df->tf", x, p["wu"], q)
    return mm("tf,fd->td", jax.nn.silu(g) * u, p["wd"], q)


def causal_conv(x, w):
    """Depthwise causal convolution then SiLU. x: (T, C); w: (W, C)."""
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), F32), x], 0)
    y = sum(xp[i: i + x.shape[0]] * w[i].astype(F32) for i in range(k))
    return jax.nn.silu(y)


def segsum(a):
    """a: (..., L) -> (..., L, L) with out[i, j] = sum(a[j+1..i]) for j <= i,
    -inf above the diagonal."""
    L = a.shape[-1]
    cs = jnp.cumsum(a, -1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd(x, dA, B, C, chunk, q=None):
    """State-space dual scan, the minimal chunked form of arXiv:2405.21060
    (Listing 1), one shared B/C group.

    x: (T, H, P) already scaled by dt; dA: (T, H) = dt * A; B, C: (T, N).
    T is padded to a multiple of ``chunk`` with zeros, which changes no
    output.  Returns y: (T, H, P)."""
    T = x.shape[0]
    pad = (-T) % chunk
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        dA = jnp.pad(dA, ((0, pad), (0, 0)))
        B = jnp.pad(B, ((0, pad), (0, 0)))
        C = jnp.pad(C, ((0, pad), (0, 0)))
    nc = x.shape[0] // chunk
    x = x.reshape(nc, chunk, *x.shape[1:])
    B = B.reshape(nc, chunk, -1)
    C = C.reshape(nc, chunk, -1)
    A = dA.reshape(nc, chunk, -1).transpose(2, 0, 1)            # (H, c, l)
    A_cs = jnp.cumsum(A, -1)
    Lm = jnp.exp(segsum(A))                                       # (H, c, l, l)
    cb = mm("cln,csn->cls", C, B, q)
    y_diag = mm("hcls,cshp->clhp", Lm * cb[None], x, q)
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)                # (H, c, l)
    states = mm("clhp,cln->chpn", x * decay_states.transpose(1, 2, 0)[..., None],
                B, q)
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states], 0)
    decay_chunk = jnp.exp(segsum(jnp.pad(A_cs[..., -1], ((0, 0), (1, 0)))))
    new_states = mm("hzc,chpn->zhpn", decay_chunk, states, q)[:-1]
    y_off = mm("cln,chpn->clhp", C, new_states, q) \
        * jnp.exp(A_cs).transpose(1, 2, 0)[..., None]
    return (y_diag + y_off).reshape(nc * chunk, *y_diag.shape[2:])[:T]


def mamba2_mixer(p, h, spec, q=None):
    """Mamba-2 mixer over one sequence. h: (T, D) normalised input."""
    s = spec["ssm"]
    z = mm("td,dk->tk", h, p["w_z"], q)
    xs = causal_conv(mm("td,dk->tk", h, p["w_x"], q), p["conv_x"])
    B = causal_conv(mm("td,dk->tk", h, p["w_B"], q), p["conv_B"])
    C = causal_conv(mm("td,dk->tk", h, p["w_C"], q), p["conv_C"])
    dt = jax.nn.softplus(mm("td,dk->tk", h, p["w_dt"], q) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(xs.shape[0], -1, s["headdim"])
    y = ssd(xh * dt[..., None], dt * A, B, C, s["chunk"], q)
    y = y + xh * p["D"][:, None]
    y = y.reshape(xs.shape) * jax.nn.silu(z)
    return mm("tk,kd->td", rms_norm(y, p["norm"]), p["w_out"], q)


def unembed(embed, x, vocab, q=None):
    """Tied unembedding over the real vocabulary (padding rows dropped)."""
    return mm("td,vd->tv", x, embed[:vocab], q)
