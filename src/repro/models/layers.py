"""Shared transformer building blocks (norms, RoPE, GQA attention, MLPs).

Conventions
-----------
* Parameters are nested dicts of jnp arrays.  Every init function returns
  ``(params, axes)`` where ``axes`` mirrors ``params`` with tuples of
  *logical* axis names consumed by ``repro.parallel.sharding.MeshEnv``.
* Activations flow in ``cfg.compute_dtype`` (bf16); softmax statistics and
  normalization accumulate in fp32.
* Attention is O(seq * chunk) memory via an online-softmax scan over KV
  chunks (the pure-XLA analogue of the Pallas flash kernel in
  ``repro.kernels.flash_attention`` — the kernel's ``ref.py`` reuses the
  naive oracle here).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# --------------------------------------------------------------------------- #
# init helpers
# --------------------------------------------------------------------------- #
def dense_init(key, shape, axes, dtype, scale=None):
    fan_in = shape[0] if len(shape) > 1 else 1
    if scale is None:
        scale = 1.0 / math.sqrt(max(1, fan_in))
    w = jax.random.normal(key, shape, dtype=jnp.float32) * scale
    return w.astype(dtype), axes


def split(key, n):
    return list(jax.random.split(key, n))


# --------------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------------- #
def rms_norm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------- #
# RoPE (with partial-rotary support for chatglm3's "2d" rope)
# --------------------------------------------------------------------------- #
def rope_freqs(dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, theta=10000.0, fraction=1.0):
    """x: (..., S, H, dh); positions: (..., S) int32."""
    dh = x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    freqs = rope_freqs(rot, theta)                       # (rot/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


# --------------------------------------------------------------------------- #
# attention parameter init
# --------------------------------------------------------------------------- #
def attention_init(key, cfg):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    ks = split(key, 5)
    params, axes = {}, {}
    params["wq"], axes["wq"] = dense_init(ks[0], (d, h, dh), ("embed", "heads", None), cfg.param_dtype)
    params["wk"], axes["wk"] = dense_init(ks[1], (d, kv, dh), ("embed", "kv_heads", None), cfg.param_dtype)
    params["wv"], axes["wv"] = dense_init(ks[2], (d, kv, dh), ("embed", "kv_heads", None), cfg.param_dtype)
    params["wo"], axes["wo"] = dense_init(ks[3], (h, dh, d), ("heads", None, "embed"), cfg.param_dtype)
    if cfg.qk_norm:
        params["q_norm"] = jnp.ones((dh,), cfg.param_dtype)
        params["k_norm"] = jnp.ones((dh,), cfg.param_dtype)
        axes["q_norm"] = (None,)
        axes["k_norm"] = (None,)
    return params, axes


def qkv_project(p, x, cfg, positions, env=None):
    """x: (B,S,D) -> q (B,S,H,dh), k/v (B,S,KV,dh) with rope + optional qk-norm.

    With env given, q/k/v are constrained to head-sharded layout — without
    this XLA may keep seq sharded through attention and replicate the head
    dim (observed on deepseek-v2: 128 unsharded heads in the score buffers).
    """
    cd = cfg.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cd))
    if env is not None:
        q = env.constrain(q, ("batch", None, "heads", None))
        k = env.constrain(k, ("batch", None, "kv_heads", None))
        v = env.constrain(v, ("batch", None, "kv_heads", None))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.rope_fraction > 0:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    return q, k, v


def attn_output(p, attn, cfg):
    return jnp.einsum("bshk,hkd->bsd", attn, p["wo"].astype(cfg.compute_dtype))


# --------------------------------------------------------------------------- #
# attention cores
# --------------------------------------------------------------------------- #
def naive_attention(q, k, v, *, causal=True, window=None, q_pos0=0, kv_pos0=0):
    """O(S^2)-memory oracle.  q: (B,Sq,H,dh), k/v: (B,Sk,KV,dh)."""
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores *= 1.0 / math.sqrt(dh)
    qpos = q_pos0 + jnp.arange(sq)
    kpos = kv_pos0 + jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(q.dtype), v)
    return out.reshape(b, sq, h, v.shape[-1])


def chunked_attention(q, k, v, *, causal=True, kv_chunk=512, q_pos0=0, kv_pos0=0):
    """Flash attention in pure XLA: online-softmax scan over KV chunks with a
    custom VJP that RECOMPUTES blockwise in the backward pass (saving only
    (q,k,v,out,lse)) — without it, scan-backward stacks the fp32 (m,l,acc)
    carries per chunk (observed: tens of GB/chip on deepseek-v2 train_4k).
    The Pallas kernel in repro.kernels.flash_attention is the TPU-native
    version of exactly this schedule."""
    if q_pos0 == 0 and kv_pos0 == 0:
        return _make_flash(causal, int(kv_chunk))(q, k, v)
    return _chunked_attention_core(q, k, v, causal=causal, kv_chunk=kv_chunk,
                                   q_pos0=q_pos0, kv_pos0=kv_pos0)[0]


def _chunked_attention_core(q, k, v, *, causal=True, kv_chunk=512, q_pos0=0,
                            kv_pos0=0):
    """Returns (out, lse) — shared by the flash fwd and the plain path."""
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    kv_chunk = min(kv_chunk, sk)
    if sk % kv_chunk != 0:          # pad to a multiple (masked out)
        pad = kv_chunk - sk % kv_chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        sk_p = sk + pad
    else:
        sk_p = sk
    nkv = sk_p // kv_chunk
    kc = k.reshape(b, nkv, kv_chunk, kvh, k.shape[-1]).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nkv, kv_chunk, kvh, v.shape[-1]).transpose(1, 0, 2, 3, 4)

    qg = q.reshape(b, sq, kvh, g, dh)
    qpos = (q_pos0 + jnp.arange(sq)).astype(jnp.int32)
    scale = 1.0 / math.sqrt(dh)

    def step(carry, xs):
        m, l, acc = carry
        kj, vj, j = xs
        kpos = kv_pos0 + j * kv_chunk + jnp.arange(kv_chunk, dtype=jnp.int32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kj).astype(jnp.float32) * scale
        valid = kpos[None, :] < sk + kv_pos0
        mask = valid
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    dv = v.shape[-1]
    m0 = jnp.full((b, kvh, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kc, vc, jnp.arange(nkv, dtype=jnp.int32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))                 # (b,kvh,g,sq)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv).astype(q.dtype)
    return out, lse


@functools.lru_cache(maxsize=64)
def _make_flash(causal, kv_chunk):
    """custom_vjp flash attention closed over static (causal, kv_chunk)."""

    @jax.custom_vjp
    def fa(q, k, v):
        return _chunked_attention_core(q, k, v, causal=causal,
                                       kv_chunk=kv_chunk)[0]

    def fwd(q, k, v):
        out, lse = _chunked_attention_core(q, k, v, causal=causal,
                                           kv_chunk=kv_chunk)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        b, sq, h, dh = q.shape
        sk, kvh = k.shape[1], k.shape[2]
        g = h // kvh
        dv_dim = v.shape[-1]
        c = min(kv_chunk, sk)
        pad = (-sk) % c
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        nkv = (sk + pad) // c
        kc = kp.reshape(b, nkv, c, kvh, dh).transpose(1, 0, 2, 3, 4)
        vc = vp.reshape(b, nkv, c, kvh, dv_dim).transpose(1, 0, 2, 3, 4)

        qg = q.reshape(b, sq, kvh, g, dh)
        dog = do.reshape(b, sq, kvh, g, dv_dim).astype(jnp.float32)
        og = out.reshape(b, sq, kvh, g, dv_dim).astype(jnp.float32)
        D = jnp.sum(dog * og, axis=-1).transpose(0, 2, 3, 1)   # (b,kvh,g,sq)
        qpos = jnp.arange(sq, dtype=jnp.int32)
        scale = 1.0 / math.sqrt(dh)

        def step(dq_acc, xs):
            kj, vj, j = xs
            kpos = j * c + jnp.arange(c, dtype=jnp.int32)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kj).astype(jnp.float32)
            s = s * scale
            mask = kpos[None, :] < sk
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            s = jnp.where(mask[None, None, None], s, -1e30)
            p = jnp.exp(s - lse[..., None])                    # (b,h,g,q,k)
            p = jnp.where(mask[None, None, None], p, 0.0)
            dv_j = jnp.einsum("bhgqk,bqhgd->bkhd", p, dog)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", dog.astype(q.dtype),
                            vj).astype(jnp.float32)
            ds = p * (dp - D[..., None]) * scale
            dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kj)
            dk_j = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg.astype(jnp.float32))
            return dq_acc, (dk_j, dv_j)

        dq0 = jnp.zeros((b, sq, kvh, g, dh), jnp.float32)
        dq, (dks, dvs) = jax.lax.scan(
            step, dq0, (kc, vc, jnp.arange(nkv, dtype=jnp.int32)))
        dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, sk + pad, kvh, dh)[:, :sk]
        dvv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, sk + pad, kvh, dv_dim)[:, :sk]
        return (dq.reshape(b, sq, h, dh).astype(q.dtype),
                dk.astype(k.dtype), dvv.astype(v.dtype))

    fa.defvjp(fwd, bwd)
    return fa


def windowed_attention(q, k, v, *, window, q_chunk=512, q_pos0=0,
                       prefix_kv=None):
    """Sliding-window causal attention, FLOP-proportional to the window.

    Scans over q blocks; for each, dynamic-slices the [pos-window, pos] KV
    range (front-padded so the slice is static-size).  Differentiable.
    q and k/v must share the same positions (self-attention).

    prefix_kv: optional (k_pre, v_pre) of shape (B, P, KV, dh) — globally
    visible prefix keys (hymba meta tokens) attended by every q block.
    """
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q_chunk = min(q_chunk, sq)
    if sq % q_chunk:
        raise ValueError("seq must divide q_chunk for windowed attention")
    w = (window + q_chunk - 1) // q_chunk * q_chunk   # round window up to blocks
    kp = jnp.pad(k, ((0, 0), (w, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (w, 0), (0, 0), (0, 0)))
    nq = sq // q_chunk
    qb = q.reshape(b, nq, q_chunk, h, dh).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / math.sqrt(dh)
    span = w + q_chunk
    npre = 0 if prefix_kv is None else prefix_kv[0].shape[1]
    dv = v.shape[-1]

    def step(i, qi):
        start = i * q_chunk                      # in padded coords == pos - w
        kj = jax.lax.dynamic_slice(kp, (0, start, 0, 0), (b, span, kvh, dh))
        vj = jax.lax.dynamic_slice(vp, (0, start, 0, 0), (b, span, kvh, dv))
        qpos = q_pos0 + start + jnp.arange(q_chunk)
        kpos = q_pos0 + start - w + jnp.arange(span)
        mask = (kpos[None, :] <= qpos[:, None]) \
            & (kpos[None, :] > qpos[:, None] - window) \
            & (kpos[None, :] >= q_pos0)
        if prefix_kv is not None:
            kj = jnp.concatenate([prefix_kv[0], kj], axis=1)
            vj = jnp.concatenate([prefix_kv[1], vj], axis=1)
            mask = jnp.concatenate(
                [jnp.ones((q_chunk, npre), bool), mask], axis=1)
        qg = qi.reshape(b, q_chunk, kvh, g, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kj).astype(jnp.float32) * scale
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(q.dtype), vj)
        return o.reshape(b, q_chunk, h, dv)

    out = jax.lax.map(lambda args: step(*args),
                      (jnp.arange(nq, dtype=jnp.int32), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv)


def prefill_attention(q, k, v, *, kv_chunk=1024):
    """Causal attention over a static *triangular pair schedule*: one scan of
    exactly nq*(nq+1)/2 block-pair steps — FLOP-exact (no masked-out block is
    ever computed) and statically countable by repro.costmodel (no while
    loops).  Online-softmax stats for all q blocks live in the carry and are
    updated in place per step."""
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    if sq != sk or sq % kv_chunk:
        return chunked_attention(q, k, v, causal=True, kv_chunk=kv_chunk)
    g = h // kvh
    dv = v.shape[-1]
    n = sq // kv_chunk
    c = kv_chunk
    qg = q.reshape(b, n, c, kvh, g, dh)
    scale = 1.0 / math.sqrt(dh)

    # static triangular schedule
    qi_list, kj_list = [], []
    for qi in range(n):
        for kj in range(qi + 1):
            qi_list.append(qi)
            kj_list.append(kj)
    qi_arr = jnp.asarray(qi_list, jnp.int32)
    kj_arr = jnp.asarray(kj_list, jnp.int32)
    diag = qi_arr == kj_arr
    tri = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]

    def step(carry, xs):
        m, l, acc = carry                       # (b,kvh,g,n,c[,dv])
        qi, kj, is_diag = xs
        qb = jax.lax.dynamic_slice(
            qg, (0, qi, 0, 0, 0, 0), (b, 1, c, kvh, g, dh))[:, 0]
        kb = jax.lax.dynamic_slice(k, (0, kj * c, 0, 0), (b, c, kvh, dh))
        vb = jax.lax.dynamic_slice(v, (0, kj * c, 0, 0), (b, c, kvh, dv))
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb).astype(jnp.float32) * scale
        s = jnp.where(jnp.logical_or(~is_diag, tri)[None, None, None], s, -1e30)
        m_blk = jax.lax.dynamic_slice(
            m, (0, 0, 0, qi, 0), (b, kvh, g, 1, c))[..., 0, :]
        l_blk = jax.lax.dynamic_slice(
            l, (0, 0, 0, qi, 0), (b, kvh, g, 1, c))[..., 0, :]
        a_blk = jax.lax.dynamic_slice(
            acc, (0, 0, 0, qi, 0, 0), (b, kvh, g, 1, c, dv))[..., 0, :, :]
        m_new = jnp.maximum(m_blk, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_blk - m_new)
        l_new = l_blk * corr + p.sum(axis=-1)
        a_new = a_blk * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
        m = jax.lax.dynamic_update_slice(
            m, m_new[..., None, :], (0, 0, 0, qi, 0))
        l = jax.lax.dynamic_update_slice(
            l, l_new[..., None, :], (0, 0, 0, qi, 0))
        acc = jax.lax.dynamic_update_slice(
            acc, a_new[..., None, :, :], (0, 0, 0, qi, 0, 0))
        return (m, l, acc), None

    m0 = jnp.full((b, kvh, g, n, c), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, n, c), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, n, c, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (qi_arr, kj_arr, diag))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 4, 1, 2, 5).reshape(b, sq, h, dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cur_len):
    """Single-token decode against a (replicated or head-sharded) KV cache.

    q: (B,1,H,dh); caches: (B,S,KV,dh); cur_len: () int32 — number of valid
    cache entries (the new token's KV must already be written)."""
    b, _, h, dh = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, dh)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache).astype(jnp.float32)
    scores *= 1.0 / math.sqrt(dh)
    mask = jnp.arange(s) < cur_len
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(q.dtype), v_cache)
    return out.reshape(b, 1, h, v_cache.shape[-1])


def flash_decode_shardmap(q, k_cache, v_cache, k_new, v_new, pos, env):
    """Flash-decoding: KV cache sharded over the *model* axis along sequence.

    Used when kv_heads does not divide TP (llama3/qwen3/nemotron/chatglm3/
    pixtral at TP=16).  Each model shard holds a contiguous seq slice of the
    cache, writes the new token's KV iff it owns the slot, computes partial
    attention with fp32 (m, l) statistics and combines across the axis with a
    log-sum-exp psum.  Returns (out, new_k_cache, new_v_cache).

    q: (B,1,H,dh) replicated over model; caches: (B,S,KV,dh) sharded (seq);
    k_new/v_new: (B,1,KV,dh); pos: () int32 position of the new token.
    """
    mesh = env.mesh
    axis = env.model_axis

    def body(q, kc, vc, kn, vn, pos):
        # shapes here are per-shard: batch sharded over data, cache seq
        # sharded over model, q/new-KV replicated over model
        b, _, h, dh = q.shape
        kvh = kc.shape[2]
        g = h // kvh
        idx = jax.lax.axis_index(axis)
        s_loc = kc.shape[1]
        start = idx * s_loc
        local = jnp.clip(pos - start, 0, s_loc - 1)
        owner = (pos >= start) & (pos < start + s_loc)
        kc2 = jax.lax.dynamic_update_slice(kc, kn, (0, local, 0, 0))
        vc2 = jax.lax.dynamic_update_slice(vc, vn, (0, local, 0, 0))
        kc = jnp.where(owner, kc2, kc)
        vc = jnp.where(owner, vc2, vc)

        qg = q.reshape(b, kvh, g, dh)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kc).astype(jnp.float32)
        s *= 1.0 / math.sqrt(dh)
        kpos = start + jnp.arange(s_loc)
        s = jnp.where((kpos <= pos)[None, None, None], s, -1e30)
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        o = jnp.einsum("bhgk,bkhd->bhgd", p, vc.astype(jnp.float32))
        m_g = jax.lax.pmax(m, axis)
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, axis)
        o_g = jax.lax.psum(o * w[..., None], axis)
        out = (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q.dtype)
        return out.reshape(b, 1, h, dh), kc, vc

    dspec = env.data_axes if len(env.data_axes) > 1 else env.data_axes[0]
    qs = P(dspec, None, None, None)
    cs = P(dspec, axis, None, None)
    ns = P(dspec, None, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qs, cs, cs, ns, ns, P()),
        out_specs=(qs, cs, cs),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, pos)


# --------------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------------- #
def mlp_init(key, cfg, d_ff=None, d_model=None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    dt = cfg.param_dtype
    params, axes = {}, {}
    if cfg.mlp == "swiglu":
        ks = split(key, 3)
        params["wg"], axes["wg"] = dense_init(ks[0], (d, f), ("embed", "ff"), dt)
        params["wu"], axes["wu"] = dense_init(ks[1], (d, f), ("embed", "ff"), dt)
        params["wd"], axes["wd"] = dense_init(ks[2], (f, d), ("ff", "embed"), dt)
    else:  # relu2 | gelu: two-matrix MLP
        ks = split(key, 2)
        params["wu"], axes["wu"] = dense_init(ks[0], (d, f), ("embed", "ff"), dt)
        params["wd"], axes["wd"] = dense_init(ks[1], (f, d), ("ff", "embed"), dt)
    return params, axes


def mlp_apply(p, x, cfg):
    cd = cfg.compute_dtype
    if cfg.mlp == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(cd))
        u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(cd))
        h = jax.nn.silu(g) * u
    elif cfg.mlp == "relu2":
        u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(cd))
        r = jax.nn.relu(u)
        h = r * r
    elif cfg.mlp == "gelu":
        u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(cd))
        h = jax.nn.gelu(u)
    else:
        raise ValueError(cfg.mlp)
    return jnp.einsum("bsf,fd->bsd", h, p["wd"].astype(cd))


# --------------------------------------------------------------------------- #
# embedding / unembedding
# --------------------------------------------------------------------------- #
def embed_init(key, cfg):
    """Vocab padded to cfg.vocab_pad_to so the table TP-shards cleanly."""
    e = jax.random.normal(key, (cfg.padded_vocab, cfg.d_model), jnp.float32) * 0.02
    return e.astype(cfg.param_dtype), ("vocab", "embed")


def embed_lookup(emb, tokens, cfg):
    return jnp.take(emb.astype(cfg.compute_dtype), tokens, axis=0)


def unembed(emb, x, cfg):
    """Tied unembedding: (B,S,D) @ (V,D)^T -> (B,S,V_padded); padding ids
    masked to -inf so sampling/loss never select them."""
    logits = jnp.einsum("bsd,vd->bsv", x, emb.astype(cfg.compute_dtype))
    if cfg.padded_vocab != cfg.vocab:
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    return logits
