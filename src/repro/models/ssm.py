"""Mamba-2 (SSD — state-space duality) block, chunked, channel-parallel.

With n_groups=1, B/C are shared across heads and the per-channel recurrence
  h[t] = exp(dt[t]*A_head) * h[t-1] + dt[t] * B[t] * x[t]
  y[t] = C[t] . h[t] + D_head * x[t]
is independent per d_inner channel, so state (B, d_inner, N) shards cleanly
on the mesh "model" axis (logical axis "dinner") — the TPU-native layout
(DESIGN.md #4).  The chunked SSD form computes intra-chunk interactions as a
masked quadratic attention-like product and carries inter-chunk states with
a lax.scan; ``repro.kernels.ssd`` provides the Pallas intra-chunk kernel and
reuses ``ssd_ref`` below as its oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def ssm_init(key, cfg):
    s = cfg.ssm
    d, di, n = cfg.d_model, s.d_inner, s.n_state
    h = di // s.headdim
    dt = cfg.param_dtype
    ks = layers.split(key, 8)
    params, axes = {}, {}
    # separate projections (vs the fused w_in of the reference impl): each
    # output dim shards independently on "model" ("dinner"), keeping TP clean
    params["w_z"], axes["w_z"] = layers.dense_init(ks[0], (d, di), ("embed", "dinner"), dt)
    params["w_x"], axes["w_x"] = layers.dense_init(ks[1], (d, di), ("embed", "dinner"), dt)
    params["w_B"], axes["w_B"] = layers.dense_init(ks[2], (d, n), ("embed", None), dt)
    params["w_C"], axes["w_C"] = layers.dense_init(ks[3], (d, n), ("embed", None), dt)
    params["w_dt"], axes["w_dt"] = layers.dense_init(ks[4], (d, h), ("embed", None), dt)
    for nm, width in (("conv_x", di), ("conv_B", n), ("conv_C", n)):
        params[nm] = (jax.random.normal(ks[5], (s.conv_width, width), jnp.float32)
                      * 0.1).astype(dt)
        axes[nm] = (None, "dinner" if nm == "conv_x" else None)
    params["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32))
    axes["A_log"] = (None,)
    params["D"] = jnp.ones((h,), jnp.float32); axes["D"] = (None,)
    params["dt_bias"] = jnp.zeros((h,), jnp.float32); axes["dt_bias"] = (None,)
    params["norm"] = jnp.ones((di,), dt); axes["norm"] = ("dinner",)
    params["w_out"], axes["w_out"] = layers.dense_init(ks[6], (di, d), ("dinner", "embed"), dt)
    return params, axes


def _causal_conv(xbc, conv_w, conv_state=None):
    """Depthwise causal conv, width W.  xbc: (B,L,C); conv_w: (W,C).

    conv_state (B,W-1,C) carries history for decode; returns (y, new_state)."""
    w = conv_w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((xbc.shape[0], w - 1, xbc.shape[-1]), xbc.dtype)
    else:
        pad = conv_state
    xp = jnp.concatenate([pad, xbc], axis=1)
    new_state = xp[:, -(w - 1):] if w > 1 else pad
    y = sum(xp[:, i: i + xbc.shape[1]] * conv_w[i][None, None] for i in range(w))
    return jax.nn.silu(y), new_state


# --------------------------------------------------------------------------- #
# chunked SSD forward (reference semantics; also the kernel oracle)
# --------------------------------------------------------------------------- #
def ssd_ref(x, dt, A, B, C, chunk):
    """SSD scan.

    x: (b, l, h, p); dt: (b, l, h) (softplus already applied);
    A: (h,) negative decay rates; B, C: (b, l, n)  [n_groups == 1].
    Returns y: (b, l, h, p) and final state (b, h, p, n), fp32.
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    rem = l % chunk
    if rem:
        # process the trailing partial chunk separately (exact, causal)
        y_main, h_main = ssd_ref(x[:, : l - rem], dt[:, : l - rem], A,
                                 B[:, : l - rem], C[:, : l - rem], chunk)
        y_tail, h_tail = _ssd_one_chunk(
            x[:, l - rem:], dt[:, l - rem:], A, B[:, l - rem:], C[:, l - rem:],
            h_main)
        return jnp.concatenate([y_main, y_tail], axis=1), h_tail
    if l == 0:
        return (jnp.zeros_like(x, dtype=jnp.float32),
                jnp.zeros((b, h, p, n), jnp.float32))
    nc = l // chunk
    # scan over chunks: peak score memory is O(b * chunk^2 * h) per step
    xf = x.astype(jnp.float32).reshape(b, nc, chunk, h, p).transpose(1, 0, 2, 3, 4)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, h).transpose(1, 0, 2, 3)
    Bf = B.astype(jnp.float32).reshape(b, nc, chunk, n).transpose(1, 0, 2, 3)
    Cf = C.astype(jnp.float32).reshape(b, nc, chunk, n).transpose(1, 0, 2, 3)
    idx = jnp.arange(chunk)
    causal = (idx[:, None] >= idx[None, :])[None, :, :, None]     # (1,i,j,1)

    def step(hstate, inp):
        xc, dtc, Bc, Cc = inp                                     # per-chunk slices
        dA = dtc * A[None, None, :]                               # (b,q,h)
        cs = jnp.cumsum(dA, axis=1)                               # inclusive
        # intra-chunk: y[i] += sum_{j<=i} C_i.B_j exp(cs_i-cs_j) dt_j x_j
        # mask INSIDE the exp: masked (j>i) entries have decay>0 and would
        # overflow to inf, poisoning gradients through the where
        decay = jnp.where(causal, cs[:, :, None, :] - cs[:, None, :, :],
                          -jnp.inf)                               # (b,i,j,h)
        L = jnp.exp(decay)
        cb = jnp.einsum("bin,bjn->bij", Cc, Bc)
        att = cb[..., None] * L * dtc[:, None, :, :]              # (b,i,j,h)
        y = jnp.einsum("bijh,bjhp->bihp", att, xc)
        # inter-chunk: contribution of the state entering this chunk
        y = y + jnp.einsum("bin,bhpn->bihp", Cc, hstate) * jnp.exp(cs)[..., None]
        # state update
        last = cs[:, -1, :]                                       # (b,h)
        w = jnp.exp(last[:, None, :] - cs) * dtc                  # (b,q,h)
        S = jnp.einsum("bjh,bjn,bjhp->bhpn", w, Bc, xc)
        hstate = hstate * jnp.exp(last)[..., None, None] + S
        return hstate, y

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    hfinal, ys = jax.lax.scan(step, h0, (xf, dtf, Bf, Cf))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, l, h, p)
    return y, hfinal


def _ssd_one_chunk(x, dt, A, B, C, h0):
    """Single (possibly partial) chunk with an incoming state h0."""
    b, q, h, p = x.shape
    xc = x.astype(jnp.float32)
    dtc = dt.astype(jnp.float32)
    Bc = B.astype(jnp.float32)
    Cc = C.astype(jnp.float32)
    dA = dtc * A[None, None, :]
    cs = jnp.cumsum(dA, axis=1)
    idx = jnp.arange(q)
    causal = (idx[:, None] >= idx[None, :])[None, :, :, None]
    decay = jnp.where(causal, cs[:, :, None, :] - cs[:, None, :, :],
                      -jnp.inf)
    L = jnp.exp(decay)
    cb = jnp.einsum("bin,bjn->bij", Cc, Bc)
    att = cb[..., None] * L * dtc[:, None, :, :]
    y = jnp.einsum("bijh,bjhp->bihp", att, xc)
    y = y + jnp.einsum("bin,bhpn->bihp", Cc, h0) * jnp.exp(cs)[..., None]
    last = cs[:, -1, :]
    w = jnp.exp(last[:, None, :] - cs) * dtc
    S = jnp.einsum("bjh,bjn,bjhp->bhpn", w, Bc, xc)
    hfinal = h0 * jnp.exp(last)[..., None, None] + S
    return y, hfinal


def _project(p, x, cfg, conv_state=None):
    """Shared projection + causal conv.  conv_state: None or dict(x,B,C)."""
    s = cfg.ssm
    cd = cfg.compute_dtype
    z = jnp.einsum("bld,dk->blk", x, p["w_z"].astype(cd))
    xs = jnp.einsum("bld,dk->blk", x, p["w_x"].astype(cd))
    B = jnp.einsum("bld,dk->blk", x, p["w_B"].astype(cd))
    C = jnp.einsum("bld,dk->blk", x, p["w_C"].astype(cd))
    dtr = jnp.einsum("bld,dk->blk", x, p["w_dt"].astype(cd))
    cs = conv_state or {}
    xs, ncx = _causal_conv(xs, p["conv_x"].astype(cd), cs.get("x"))
    B, ncb = _causal_conv(B, p["conv_B"].astype(cd), cs.get("B"))
    C, ncc = _causal_conv(C, p["conv_C"].astype(cd), cs.get("C"))
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"][None, None])
    return z, xs, B, C, dt, {"x": ncx, "B": ncb, "C": ncc}


def ssm_forward(p, x, cfg, env, conv_state=None, ssd_state=None):
    """Full mamba2 mixer.  x: (B,L,D) -> (B,L,D).

    When conv_state/ssd_state are provided (decode continuation) they are
    threaded; for training they are None and zero-initialised."""
    s = cfg.ssm
    cd = cfg.compute_dtype
    di = s.d_inner
    h = di // s.headdim
    z, xs, B, C, dt, new_conv = _project(p, x, cfg, conv_state)
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(*xs.shape[:2], h, s.headdim)
    # channel-parallel SSD: keep headdim sharded on "model" through the scan
    xh = env.constrain(xh, ("batch", None, None, "dinner"))
    y, hfinal = ssd_ref(xh, dt, A, B, C, s.chunk)
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = env.constrain(y, ("batch", None, None, "dinner"))
    y = y.reshape(*xs.shape).astype(cd)
    y = y * jax.nn.silu(z)
    y = layers.rms_norm(y, p["norm"])
    out = jnp.einsum("bld,dk->blk", y, p["w_out"].astype(cd))
    return out, (new_conv, hfinal)


def ssm_decode(p, x, state, cfg, env):
    """Single-token recurrent step.  x: (B,1,D); state=(conv_state, h), h
    (B,H,P,N) or a layer's slice (1,B,H,P,N) of a stacked cache."""
    s = cfg.ssm
    cd = cfg.compute_dtype
    di = s.d_inner
    h = di // s.headdim
    conv_state, hstate = state
    z, xs, B, C, dt, new_conv = _project(p, x, cfg, conv_state)
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(xs.shape[0], h, s.headdim).astype(jnp.float32)
    dA = jnp.exp(dt[:, 0, :] * A[None])                          # (B,h)
    dBx = jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0], B[:, 0].astype(jnp.float32), xh)
    hnew = hstate * dA[..., None, None] + dBx
    y = jnp.einsum("bn,bhpn->bhp", C[:, 0].astype(jnp.float32),
                   hnew.reshape(hnew.shape[-4:]))
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(xs.shape[0], 1, di).astype(cd)
    y = y * jax.nn.silu(z)
    y = layers.rms_norm(y, p["norm"])
    out = jnp.einsum("bld,dk->blk", y, p["w_out"].astype(cd))
    return out, (new_conv, hnew)


def ssm_state_shape(cfg, batch):
    s = cfg.ssm
    h = s.d_inner // s.headdim
    w = s.conv_width - 1
    return {
        "conv_x": ((batch, w, s.d_inner), ("batch", None, "dinner")),
        "conv_B": ((batch, w, s.n_state), ("batch", None, None)),
        "conv_C": ((batch, w, s.n_state), ("batch", None, None)),
        "h": ((batch, h, s.headdim, s.n_state), ("batch", None, "dinner", None)),
    }
