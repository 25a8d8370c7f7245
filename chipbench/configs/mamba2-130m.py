"""mamba2-130m: weights, plain reference and operation counts.

Layers: ``x += mixer(rms_norm(x))`` 24 times, a final norm, and the
embedding reused as the output head.  The reference runs in float32 at
highest precision over one sequence (``logits``) or a training batch
(``loss``); the state-space scan is the minimal chunked SSD of
arXiv:2405.21060.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import refops as R


def init_params(key, spec):
    """Seeded weights in the served layout, stored in ``param_dtype``."""
    dt = jnp.dtype(spec["param_dtype"])
    ks = jax.random.split(key, 6)
    d = spec["d_model"]
    vp = R.padded(spec["vocab"], spec["vocab_pad_to"])
    return {
        "embed": (jax.random.normal(ks[0], (vp, d), R.F32) * 0.02).astype(dt),
        "ln_f": jnp.ones((d,), dt),
        "blocks": R.stack(ks[1], spec["n_layers"], lambda k: {
            "mix": R.mamba2_init(k, spec, dt), "ln": jnp.ones((d,), dt)}),
    }


def _hidden(params, tokens, spec, q):
    x = params["embed"][tokens].astype(R.F32)

    @jax.checkpoint
    def layer(x, p):
        return x + R.mamba2_mixer(p["mix"], R.rms_norm(x, p["ln"]), spec, q), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return R.rms_norm(x, params["ln_f"])


def _logits(params, tokens, spec, q):
    return R.unembed(params["embed"], _hidden(params, tokens, spec, q),
                     spec["vocab"], q)


_logits_jit = jax.jit(_logits, static_argnums=(2, 3))


def logits(params, tokens, spec, q=None):
    """(T,) token ids -> (T, vocab) float32 next-token logits."""
    return _logits_jit(params, tokens, R.Static(spec), q)


def loss(params, tokens, spec, q=None):
    """Mean next-token cross-entropy of a (B, S) batch."""
    def row(t):
        h = _hidden(params, t, spec, q)[:-1]
        lg = R.unembed(params["embed"], h, spec["vocab"], q)
        gold = jnp.take_along_axis(lg, t[1:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, -1) - gold)

    total = jax.lax.map(jax.checkpoint(row), tokens)
    return jnp.sum(total) / (tokens.shape[0] * (tokens.shape[1] - 1))


# --------------------------------------------------------------------------- #
# operation and byte counts (embedding gather not counted)
# --------------------------------------------------------------------------- #
def _dims(spec):
    s = spec["ssm"]
    return (spec["d_model"], s["d_inner"], s["n_state"],
            s["d_inner"] // s["headdim"], s["headdim"], s["chunk"])


def proj_flops(spec):
    """Matrix-product FLOPs of one token through the projections of one layer."""
    d, di, n, h, _, _ = _dims(spec)
    return 2 * d * (2 * di + 2 * n + h) + 2 * di * d


def ssd_flops(spec, T, full_square=False):
    """Chunked SSD matrix products over a T-token sequence: C.B and the
    intra-chunk product (causal half unless ``full_square``), plus the chunk
    states in and out."""
    _, di, n, h, p, c = _dims(spec)
    pairs = 0
    for start in range(0, T, c):
        q = min(c, T - start)
        pairs += q * q if full_square else q * (q + 1) // 2
    return 2 * pairs * (n + h * p) + 2 * 2 * T * h * p * n


def fwd_flops(spec, T, full_square=False):
    """One sequence of T tokens through every layer and the output head."""
    return (spec["n_layers"] * (T * proj_flops(spec)
                                + ssd_flops(spec, T, full_square))
            + 2 * T * spec["d_model"] * spec["vocab"])


def decode_flops(spec, pos):
    """One new token: projections, state update and readout, output head."""
    _, _, n, h, p, _ = _dims(spec)
    return (spec["n_layers"] * (proj_flops(spec) + 2 * 2 * h * p * n)
            + 2 * spec["d_model"] * spec["vocab"])


def train_flops_per_token(spec, seq):
    """Forward plus backward (twice the forward) per token; remat not counted."""
    return 3 * fwd_flops(spec, seq) / seq


def param_bytes(spec):
    d, di, n, h, _, _ = _dims(spec)
    w = spec["ssm"]["conv_width"]
    per_layer = (d * (2 * di + 2 * n + h) + di * d + w * (di + 2 * n) + di + d)
    size = jnp.dtype(spec["param_dtype"]).itemsize
    vp = R.padded(spec["vocab"], spec["vocab_pad_to"])
    return size * (vp * d + d + spec["n_layers"] * per_layer) \
        + 4 * spec["n_layers"] * 3 * h


def decode_bytes(spec, batch, pos):
    """Bytes one decode step must move: every weight once, and each
    sequence's conv and SSM state read and written."""
    _, di, n, h, p, _ = _dims(spec)
    w = spec["ssm"]["conv_width"]
    act = jnp.dtype(spec["compute_dtype"]).itemsize
    state = 4 * h * p * n + act * (w - 1) * (di + 2 * n)
    return param_bytes(spec) + 2 * batch * spec["n_layers"] * state
