"""hymba-1.5b: weights, plain reference and operation counts.

Every layer runs attention heads and Mamba-2 heads side by side on the same
normalised input; their outputs are each normalised, weighted by ``beta``
and averaged, then a SwiGLU MLP follows.  128 learned meta tokens are
prepended to every sequence.  Three layers (``global_layers``) attend
causally to everything; the others attend to the meta tokens plus the last
``window`` tokens.  The reference runs one sequence in float32 at highest
precision, layer by layer, with attention computed in blocks of queries.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import refops as R

Q_BLOCK = 512


def _block_init(key, spec, dt):
    d, h, kv, dh, f = (spec["d_model"], spec["n_heads"], spec["n_kv"],
                       spec["head_dim"], spec["d_ff"])
    ka, ks, kf = jax.random.split(key, 3)
    a = jax.random.split(ka, 5)
    m = jax.random.split(kf, 3)
    ones = jnp.ones((d,), dt)
    return {
        "attn": {"wq": R.dense(a[0], (d, h, dh), dt),
                 "wk": R.dense(a[1], (d, kv, dh), dt),
                 "wv": R.dense(a[2], (d, kv, dh), dt),
                 "wo": R.dense(a[3], (h, dh, d), dt)},
        "mix": R.mamba2_init(ks, spec, dt),
        "ffn": {"wg": R.dense(m[0], (d, f), dt), "wu": R.dense(m[1], (d, f), dt),
                "wd": R.dense(m[2], (f, d), dt)},
        "ln1": ones, "ln2": ones, "na": ones, "ns": ones,
        "beta": jnp.ones((2,), R.F32),
    }


def _segments(spec):
    g = spec["global_layers"]
    return g[1] - g[0] - 1, spec["n_layers"] - 3 - (g[1] - g[0] - 1)


def init_params(key, spec):
    """Seeded weights in the served layout, stored in ``param_dtype``."""
    dt = jnp.dtype(spec["param_dtype"])
    ks = jax.random.split(key, 6)
    d = spec["d_model"]
    vp = R.padded(spec["vocab"], spec["vocab_pad_to"])
    seg_a, seg_b = _segments(spec)
    # Every layer's draws from its own key, as the program splits them, in
    # one vmapped call: the three global layers, then each window segment.
    keys = jnp.concatenate([jax.random.split(ks[1], 3),
                            jax.random.split(ks[2], seg_a),
                            jax.random.split(ks[4], seg_b)])
    blocks = jax.vmap(lambda k: _block_init(k, spec, dt))(keys)
    part = lambda lo, hi: jax.tree.map(lambda x: x[lo:hi], blocks)
    params = {
        "embed": (jax.random.normal(ks[0], (vp, d), R.F32) * 0.02).astype(dt),
        "ln_f": jnp.ones((d,), dt),
        "meta": (jax.random.normal(ks[3], (spec["hybrid"]["n_meta"], d))
                 * 0.02).astype(dt),
        "win_a": part(3, 3 + seg_a),
        "win_b": part(3 + seg_a, 3 + seg_a + seg_b),
    }
    for i in range(3):
        params[f"global{i}"] = jax.tree.map(lambda x: x[i], blocks)
    return params


def layers_in_order(params, spec):
    """(layer params, index into their stack or None, is_global) for every
    layer, first to last."""
    seg_a, seg_b = _segments(spec)
    return ([(params["global0"], None, True)]
            + [(params["win_a"], i, False) for i in range(seg_a)]
            + [(params["global1"], None, True)]
            + [(params["win_b"], i, False) for i in range(seg_b)]
            + [(params["global2"], None, True)])


def _attention(p, h, is_global, spec, q):
    T = h.shape[0]
    nm, w = spec["hybrid"]["n_meta"], spec["hybrid"]["window"]
    kvh, dh = spec["n_kv"], spec["head_dim"]
    g = spec["n_heads"] // kvh
    pos = jnp.arange(T)
    qh = R.rope(R.mm("td,dhk->thk", h, p["wq"], q), pos, spec["rope_theta"])
    kh = R.rope(R.mm("td,dhk->thk", h, p["wk"], q), pos, spec["rope_theta"])
    vh = R.mm("td,dhk->thk", h, p["wv"], q)
    qh = qh.reshape(T, kvh, g, dh)
    pad = (-T) % Q_BLOCK
    qb = jnp.pad(qh, ((0, pad), (0, 0), (0, 0), (0, 0)))
    qb = qb.reshape(-1, Q_BLOCK, kvh, g, dh)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = R.mm("qhgd,khd->hgqk", qi, kh, q) / math.sqrt(dh)
        keep = (pos[None] <= qpos[:, None]) & (
            is_global | (pos[None] < nm) | (pos[None] > qpos[:, None] - w))
        s = jnp.where(keep[None, None], s, -jnp.inf)
        return R.mm("hgqk,khd->qhgd", jax.nn.softmax(s, -1), vh, q)

    o = jax.lax.map(block, (jnp.arange(qb.shape[0]), qb))
    o = o.reshape(-1, spec["n_heads"], dh)[:T]
    return R.mm("thk,hkd->td", o, p["wo"], q)


def _layer(p, x, is_global, spec, q):
    h = R.rms_norm(x, p["ln1"])
    att = _attention(p["attn"], h, is_global, spec, q)
    sso = R.mamba2_mixer(p["mix"], h, spec, q)
    b = p["beta"]
    x = x + 0.5 * (b[0] * R.rms_norm(att, p["na"]) + b[1] * R.rms_norm(sso, p["ns"]))
    return x + R.swiglu(p["ffn"], R.rms_norm(x, p["ln2"]), q)


_layer_jit = jax.jit(_layer, static_argnums=(3, 4))
_stacked_layer_jit = jax.jit(
    lambda stack, i, x, is_global, spec, q: _layer(
        jax.tree.map(lambda a: a[i], stack), x, is_global, spec, q),
    static_argnums=(4, 5))


def logits(params, tokens, spec, q=None):
    """(T,) token ids -> (T, vocab) float32 next-token logits."""
    nm = spec["hybrid"]["n_meta"]
    x = jnp.concatenate([params["meta"].astype(R.F32),
                         params["embed"][tokens].astype(R.F32)], 0)
    frozen = R.Static(spec)
    for p, i, is_global in layers_in_order(params, spec):
        flag = jnp.bool_(is_global)
        x = (_layer_jit(p, x, flag, frozen, q) if i is None else
             _stacked_layer_jit(p, jnp.int32(i), x, flag, frozen, q))
    x = R.rms_norm(x[nm:], params["ln_f"])
    return R.unembed(params["embed"], x, spec["vocab"], q)


# --------------------------------------------------------------------------- #
# operation and byte counts (embedding gather not counted)
# --------------------------------------------------------------------------- #
def proj_flops(spec):
    """Matrix-product FLOPs of one token through the projections of one
    layer: q/k/v/o, the Mamba-2 projections and the MLP."""
    d, h, kv, dh, f = (spec["d_model"], spec["n_heads"], spec["n_kv"],
                       spec["head_dim"], spec["d_ff"])
    s = spec["ssm"]
    di, n = s["d_inner"], s["n_state"]
    return (2 * d * (h + 2 * kv) * dh + 2 * h * dh * d
            + 2 * d * (2 * di + 2 * n + di // s["headdim"]) + 2 * di * d
            + 3 * 2 * d * f)


def _ssd_flops(spec, T, full_square=False):
    _, di, n, h, p, c = (spec["d_model"], spec["ssm"]["d_inner"],
                         spec["ssm"]["n_state"],
                         spec["ssm"]["d_inner"] // spec["ssm"]["headdim"],
                         spec["ssm"]["headdim"], spec["ssm"]["chunk"])
    pairs = 0
    for start in range(0, T, c):
        q = min(c, T - start)
        pairs += q * q if full_square else q * (q + 1) // 2
    return 2 * pairs * (n + h * p) + 2 * 2 * T * h * p * n


def attn_pairs(spec, T, is_global):
    """Query-key pairs a layer scores over a prefill of T positions (meta
    tokens included): causal, and within the window for window layers."""
    nm, w = spec["hybrid"]["n_meta"], spec["hybrid"]["window"]
    if is_global:
        return T * (T + 1) // 2
    meta = nm * (nm + 1) // 2
    seq = 0
    for i in range(T - nm):
        seq += nm + min(i + 1, w)
    return meta + seq


def prefill_flops(spec, prompt_len):
    """One prompt through every layer, logits of its last position."""
    T = prompt_len + spec["hybrid"]["n_meta"]
    per_pair = 2 * 2 * spec["n_heads"] * spec["head_dim"]
    g = attn_pairs(spec, T, True)
    wdw = attn_pairs(spec, T, False)
    L = spec["n_layers"]
    return (L * (T * proj_flops(spec) + _ssd_flops(spec, T))
            + per_pair * (3 * g + (L - 3) * wdw)
            + 2 * spec["d_model"] * spec["vocab"])


def decode_keys(spec, pos):
    """Keys the new token at text position ``pos`` attends to: (global,
    window) layer."""
    nm, w = spec["hybrid"]["n_meta"], spec["hybrid"]["window"]
    return nm + pos + 1, nm + min(pos + 1, w)


def decode_flops(spec, pos):
    s = spec["ssm"]
    hpn = s["d_inner"] * s["n_state"]
    kg, kw = decode_keys(spec, pos)
    per_key = 2 * 2 * spec["n_heads"] * spec["head_dim"]
    L = spec["n_layers"]
    return (L * (proj_flops(spec) + 2 * 2 * hpn)
            + per_key * (3 * kg + (L - 3) * kw)
            + 2 * spec["d_model"] * spec["vocab"])


def param_bytes(spec):
    d, h, kv, dh, f = (spec["d_model"], spec["n_heads"], spec["n_kv"],
                       spec["head_dim"], spec["d_ff"])
    s = spec["ssm"]
    di, n, w = s["d_inner"], s["n_state"], s["conv_width"]
    hs = di // s["headdim"]
    per_layer = ((h + 2 * kv) * dh * d + h * dh * d
                 + d * (2 * di + 2 * n + hs) + di * d + w * (di + 2 * n) + di
                 + 3 * d * f + 4 * d)
    size = jnp.dtype(spec["param_dtype"]).itemsize
    vp = R.padded(spec["vocab"], spec["vocab_pad_to"])
    return (size * (vp * d + d + spec["hybrid"]["n_meta"] * d
                    + spec["n_layers"] * per_layer)
            + 4 * spec["n_layers"] * (3 * hs + 2))


def decode_bytes(spec, batch, pos):
    """Bytes one decode step must move: every weight once; per sequence the
    keys and values it attends to read, the new ones written, and the conv
    and SSM state read and written."""
    s = spec["ssm"]
    act = jnp.dtype(spec["compute_dtype"]).itemsize
    kg, kw = decode_keys(spec, pos)
    kv_row = 2 * spec["n_kv"] * spec["head_dim"] * act
    L = spec["n_layers"]
    kv = kv_row * (3 * (kg + 1) + (L - 3) * (kw + 1))
    state = 2 * L * (4 * s["d_inner"] * s["n_state"]
                     + act * (s["conv_width"] - 1) * (s["d_inner"] + 2 * s["n_state"]))
    return param_bytes(spec) + batch * (kv + state)
