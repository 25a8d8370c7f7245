"""Prefill + single-token decode with per-family KV/state caches.

Cache layouts (M = max_len, L = n_layers):
  dense/vlm : k,v (L,B,M,KV,dh)
              - kv_heads | TP  -> cache sharded on heads over "model"
              - else           -> flash-decoding: cache sharded on *seq* over
                                  "model", LSE-combined shard_map attention
  moe       : dense cache + separate block0 entries
  mla_moe   : compressed latent cache (B,M,kv_lora[+rope]) — replicated over
              "model" (shared by all heads), sharded over batch
  ssm       : conv (L,B,W-1,C) + state h (L,B,H,P,N), O(1) per token
  hybrid    : 3 global layers with full KV + per-layer SSM states; window
              layers use a ring buffer of size `window` + always-visible meta
              K/V — decode memory is O(window), enabling long_500k.

``pos`` is the number of *text* tokens already consumed; the new token sits
at text index ``pos`` (hybrid adds the n_meta offset internally).

The ssm and hybrid decode steps update their layer-stacked caches where they
lie (``_ssm_layers``), so a donated cache is never copied whole.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers, lm, mla as mla_mod, moe as moe_mod, ssm as ssm_mod


# --------------------------------------------------------------------------- #
# cache specification
# --------------------------------------------------------------------------- #
def _kv_axes(env_flash):
    if env_flash:
        return (None, "batch", "seq_kv", None, None)
    return (None, "batch", None, "kv_heads", None)


def cache_spec(cfg, batch, max_len, env=None):
    """Returns (tree of jax.ShapeDtypeStruct, tree of logical-axes tuples)."""
    fam = cfg.family
    cd = cfg.compute_dtype
    flash = bool(env is not None and env.flash_decode)
    shapes, axes = {}, {}

    def add(name, shape, ax, dtype=cd):
        shapes[name] = jax.ShapeDtypeStruct(shape, dtype)
        axes[name] = ax

    if fam in ("dense", "vlm"):
        kv = (cfg.n_layers, batch, max_len, cfg.n_kv, cfg.head_dim)
        add("k", kv, _kv_axes(flash)); add("v", kv, _kv_axes(flash))
    elif fam == "moe":
        kv = (cfg.n_layers - 1, batch, max_len, cfg.n_kv, cfg.head_dim)
        kv0 = (batch, max_len, cfg.n_kv, cfg.head_dim)
        add("k", kv, _kv_axes(flash)); add("v", kv, _kv_axes(flash))
        add("k0", kv0, _kv_axes(flash)[1:]); add("v0", kv0, _kv_axes(flash)[1:])
    elif fam == "mla_moe":
        a = cfg.mla
        add("c_lat", (cfg.n_layers - 1, batch, max_len, a.kv_lora),
            (None, "batch", None, None))
        add("k_rope", (cfg.n_layers - 1, batch, max_len, a.dh_rope),
            (None, "batch", None, None))
        add("c0", (batch, max_len, a.kv_lora), ("batch", None, None))
        add("r0", (batch, max_len, a.dh_rope), ("batch", None, None))
    elif fam == "ssm":
        st = ssm_mod.ssm_state_shape(cfg, batch)
        for nm, (shp, ax) in st.items():
            add(nm, (cfg.n_layers, *shp), (None, *ax),
                dtype=jnp.float32 if nm == "h" else cd)
    elif fam == "hybrid":
        hy = cfg.hybrid
        st = ssm_mod.ssm_state_shape(cfg, batch)
        kvg = (batch, max_len + hy.n_meta, cfg.n_kv, cfg.head_dim)
        for i in range(3):
            add(f"gk{i}", kvg, ("batch", None, None, None))
            add(f"gv{i}", kvg, ("batch", None, None, None))
            for nm, (shp, ax) in st.items():
                add(f"g{nm}{i}", shp, ax,
                    dtype=jnp.float32 if nm == "h" else cd)
        for seg, n in (("wa", lm._hybrid_seg_sizes(cfg)[0]),
                       ("wb", lm._hybrid_seg_sizes(cfg)[1])):
            ring = (n, batch, hy.window, cfg.n_kv, cfg.head_dim)
            meta = (n, batch, hy.n_meta, cfg.n_kv, cfg.head_dim)
            add(f"{seg}_k", ring, (None, "batch", None, None, None))
            add(f"{seg}_v", ring, (None, "batch", None, None, None))
            add(f"{seg}_mk", meta, (None, "batch", None, None, None))
            add(f"{seg}_mv", meta, (None, "batch", None, None, None))
            for nm, (shp, ax) in st.items():
                add(f"{seg}_{nm}", (n, *shp), (None, *ax),
                    dtype=jnp.float32 if nm == "h" else cd)
    else:
        raise ValueError(fam)
    return shapes, axes


def init_cache(cfg, batch, max_len, env=None):
    shapes, axes = cache_spec(cfg, batch, max_len, env)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes), axes


# --------------------------------------------------------------------------- #
# attention block: prefill (returns padded per-layer KV) + decode
# --------------------------------------------------------------------------- #
def _attn_prefill(p, x, cfg, env, positions, use_moe, max_len):
    h = layers.rms_norm(x, p["ln1"])
    q, k, v = layers.qkv_project(p["attn"], h, cfg, positions, env)
    att = layers.prefill_attention(q, k, v, kv_chunk=cfg.attn_kv_chunk)
    att = layers.attn_output(p["attn"], att, cfg)
    x = x + att
    h2 = layers.rms_norm(x, p["ln2"])
    if use_moe:
        f, _ = moe_mod.moe_apply(p["ffn"], h2, cfg, env)
    else:
        f = layers.mlp_apply(p["ffn"], h2, cfg)
    x = env.constrain(x + f, ("batch", "seq", None))
    pad = max_len - k.shape[1]
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x, (kp, vp)


def _attn_decode(p, x, kc, vc, pos, cfg, env, use_moe):
    b = x.shape[0]
    h = layers.rms_norm(x, p["ln1"])
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = layers.qkv_project(p["attn"], h, cfg, positions, env)
    if env.flash_decode and env.mesh is not None:
        att, kc, vc = layers.flash_decode_shardmap(q, kc, vc, k, v, pos, env)
    else:
        kc = jax.lax.dynamic_update_slice(kc, k, (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, pos, 0, 0))
        att = layers.decode_attention(q, kc, vc, pos + 1)
    att = layers.attn_output(p["attn"], att, cfg)
    x = x + att
    h2 = layers.rms_norm(x, p["ln2"])
    if use_moe:
        f, _ = moe_mod.moe_apply(p["ffn"], h2, cfg, env)
    else:
        f = layers.mlp_apply(p["ffn"], h2, cfg)
    return x + f, kc, vc


def _mla_prefill(p, x, cfg, env, positions, use_moe, max_len):
    h = layers.rms_norm(x, p["ln1"])
    att, (c_lat, k_rope) = mla_mod.mla_forward(p["attn"], h, cfg, env, positions)
    x = x + att
    h2 = layers.rms_norm(x, p["ln2"])
    if use_moe:
        f, _ = moe_mod.moe_apply(p["ffn"], h2, cfg, env)
    else:
        f = layers.mlp_apply(p["ffn"], h2, cfg)
    x = env.constrain(x + f, ("batch", "seq", None))
    pad = max_len - c_lat.shape[1]
    cp = jnp.pad(c_lat, ((0, 0), (0, pad), (0, 0)))
    rp = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    return x, (cp, rp)


def _mla_decode(p, x, c_lat, k_rope, pos, cfg, env, use_moe):
    h = layers.rms_norm(x, p["ln1"])
    att, new = mla_mod.mla_decode(p["attn"], h, {"c_lat": c_lat, "k_rope": k_rope},
                                  pos, cfg, env)
    x = x + att
    h2 = layers.rms_norm(x, p["ln2"])
    if use_moe:
        f, _ = moe_mod.moe_apply(p["ffn"], h2, cfg, env)
    else:
        f = layers.mlp_apply(p["ffn"], h2, cfg)
    return x + f, new["c_lat"], new["k_rope"]


# --------------------------------------------------------------------------- #
# prefill
# --------------------------------------------------------------------------- #
def prefill(params, batch, cfg, env, max_len):
    """Run the full context; returns (last-token logits (B,V), cache)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = layers.embed_lookup(params["embed"], tokens, cfg)
    if cfg.family == "vlm":
        img = batch["img_embeds"].astype(cfg.compute_dtype)
        x = jnp.concatenate([img, x[:, img.shape[1]:]], axis=1)
    x = env.constrain(x, ("batch", "seq", None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    fam = cfg.family
    cache = {}

    if fam in ("dense", "vlm"):
        def body(h, p):
            h, (kp, vp) = _attn_prefill(p, h, cfg, env, positions, False, max_len)
            return h, (kp, vp)
        x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
        cache["k"], cache["v"] = ks, vs
    elif fam == "moe":
        x, (k0, v0) = _attn_prefill(params["block0"], x, cfg, env, positions,
                                    False, max_len)
        cache["k0"], cache["v0"] = k0, v0
        def body(h, p):
            h, kv = _attn_prefill(p, h, cfg, env, positions, True, max_len)
            return h, kv
        x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
        cache["k"], cache["v"] = ks, vs
    elif fam == "mla_moe":
        x, (c0, r0) = _mla_prefill(params["block0"], x, cfg, env, positions,
                                   False, max_len)
        cache["c0"], cache["r0"] = c0, r0
        def body(h, p):
            h, cr = _mla_prefill(p, h, cfg, env, positions, True, max_len)
            return h, cr
        x, (cs, rs) = jax.lax.scan(body, x, params["blocks"])
        cache["c_lat"], cache["k_rope"] = cs, rs
    elif fam == "ssm":
        def body(h, p):
            hh = layers.rms_norm(h, p["ln"])
            y, (conv, hstate) = ssm_mod.ssm_forward(p["mix"], hh, cfg, env)
            return h + y, (conv["x"], conv["B"], conv["C"], hstate)
        x, (cx, cb, cc, hs) = jax.lax.scan(body, x, params["blocks"])
        cache["conv_x"], cache["conv_B"], cache["conv_C"], cache["h"] = \
            cx, cb, cc, hs
    elif fam == "hybrid":
        x, cache = _hybrid_prefill(params, x, cfg, env, s, max_len)
    else:
        raise ValueError(fam)

    x = layers.rms_norm(x[:, -1:], params["ln_f"])
    logits = layers.unembed(params["embed"], x, cfg)[:, 0]
    return logits, cache


def _hybrid_block_prefill(p, x, cfg, env, positions, window):
    """Returns new x plus (k, v, conv, h) for cache assembly."""
    hy = cfg.hybrid
    h = layers.rms_norm(x, p["ln1"])
    q, k, v = layers.qkv_project(p["attn"], h, cfg, positions, env)
    if window is None:
        att = layers.chunked_attention(q, k, v, causal=True,
                                       kv_chunk=cfg.attn_kv_chunk)
    else:
        nm = hy.n_meta
        att_meta = layers.naive_attention(q[:, :nm], k[:, :nm], v[:, :nm],
                                          causal=True)
        att_seq = layers.windowed_attention(
            q[:, nm:], k[:, nm:], v[:, nm:], window=window,
            q_chunk=cfg.attn_q_chunk, q_pos0=nm,
            prefix_kv=(k[:, :nm], v[:, :nm]))
        att = jnp.concatenate([att_meta, att_seq], axis=1)
    att = layers.attn_output(p["attn"], att, cfg)
    sso, (conv, hstate) = ssm_mod.ssm_forward(p["mix"], h, cfg, env)
    bta = p["beta"]
    y = (0.5 * (bta[0] * layers.rms_norm(att, p["na"])
                + bta[1] * layers.rms_norm(sso, p["ns"]))).astype(cfg.compute_dtype)
    x = x + y
    h2 = layers.rms_norm(x, p["ln2"])
    x = env.constrain(x + layers.mlp_apply(p["ffn"], h2, cfg),
                      ("batch", "seq", None))
    return x, k, v, conv, hstate


def _hybrid_prefill(params, x, cfg, env, s, max_len):
    hy = cfg.hybrid
    b = x.shape[0]
    nm = hy.n_meta
    meta = jnp.broadcast_to(params["meta"].astype(cfg.compute_dtype)[None],
                            (b, nm, cfg.d_model))
    x = jnp.concatenate([meta, x], axis=1)
    sm = s + nm
    positions = jnp.broadcast_to(jnp.arange(sm, dtype=jnp.int32)[None], (b, sm))
    cache = {}
    w = hy.window
    assert s % w == 0, "prefill length must be a multiple of the window"

    def ring_of(k):  # last `window` seq tokens; s % w == 0 keeps slots aligned
        return k[:, -w:]

    gi = 0
    def run_global(x):
        nonlocal gi
        p = params[f"global{gi}"]
        x, k, v, conv, hs = _hybrid_block_prefill(p, x, cfg, env, positions, None)
        pad = (max_len + nm) - k.shape[1]
        cache[f"gk{gi}"] = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache[f"gv{gi}"] = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache[f"gconv_x{gi}"], cache[f"gconv_B{gi}"], cache[f"gconv_C{gi}"] = \
            conv["x"], conv["B"], conv["C"]
        cache[f"gh{gi}"] = hs
        gi += 1
        return x

    def run_window_seg(x, seg, pstack):
        def body(h, p):
            h, k, v, conv, hs = _hybrid_block_prefill(p, h, cfg, env, positions,
                                                      w)
            return h, (ring_of(k), ring_of(v), k[:, :nm], v[:, :nm],
                       conv["x"], conv["B"], conv["C"], hs)
        x, (rk, rv, mk, mv, cx, cb, cc, hs) = jax.lax.scan(body, x, pstack)
        cache[f"{seg}_k"], cache[f"{seg}_v"] = rk, rv
        cache[f"{seg}_mk"], cache[f"{seg}_mv"] = mk, mv
        cache[f"{seg}_conv_x"], cache[f"{seg}_conv_B"] = cx, cb
        cache[f"{seg}_conv_C"], cache[f"{seg}_h"] = cc, hs
        return x

    x = run_global(x)
    x = run_window_seg(x, "wa", params["win_a"])
    x = run_global(x)
    x = run_window_seg(x, "wb", params["win_b"])
    x = run_global(x)
    return x, cache


# --------------------------------------------------------------------------- #
# decode
# --------------------------------------------------------------------------- #
def _ssm_layers(body, x, xs, cache, pre=""):
    """Loop a decode step over a stack of layers whose SSM states, the
    caches ``{pre}conv_x``, ``{pre}conv_B``, ``{pre}conv_C`` and ``{pre}h``,
    are updated in place.

    ``body(x, xs_l, state)`` gets a layer's slice of ``xs`` (its weights and
    read-only caches) and its state, and returns (x, new state, out); the
    outs are stacked over the layers.  The state stacks ride in the loop's
    carry and each layer writes back only its own slice.  Collected as scan
    outputs instead, the new states would land in a fresh buffer that XLA
    then copies whole into the donated cache, every token.
    """
    names = [f"{pre}conv_{k}" for k in "xBC"] + [f"{pre}h"]

    def step(carry, xs_l):
        x, stacks, i = carry
        conv = {k: jax.lax.dynamic_index_in_dim(stacks[f"{pre}conv_{k}"], i,
                                                keepdims=False)
                for k in "xBC"}
        # the SSM state keeps its layer axis, (1,B,H,P,N): squeezed, the
        # bitcasts around its update keep XLA:TPU from fusing the update
        # into the in-place write, and it copies the layer's state out first
        hs = jax.lax.dynamic_slice_in_dim(stacks[f"{pre}h"], i, 1)
        x, (conv, hs), out = body(x, xs_l, (conv, hs))
        new = [conv[k] for k in "xBC"] + [hs]
        stacks = {n: jax.lax.dynamic_update_index_in_dim(stacks[n], v, i, 0)
                  for n, v in zip(names, new)}
        return (x, stacks, i + 1), out

    stacks = {n: cache[n] for n in names}
    (x, stacks, _), outs = jax.lax.scan(step, (x, stacks, jnp.int32(0)), xs)
    return x, {**cache, **stacks}, outs


def decode_step(params, cache, token, pos, cfg, env):
    """token: (B,1) int32; pos: () int32.  Returns (logits (B,V), cache)."""
    fam = cfg.family
    x = layers.embed_lookup(params["embed"], token, cfg)
    x = env.constrain(x, ("batch", "seq", None))
    cache = dict(cache)

    if fam in ("dense", "vlm"):
        def body(h, inp):
            p, kc, vc = inp
            h, kc, vc = _attn_decode(p, h, kc, vc, pos, cfg, env, False)
            return h, (kc, vc)
        x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
        cache["k"], cache["v"] = ks, vs
    elif fam == "moe":
        x, cache["k0"], cache["v0"] = _attn_decode(
            params["block0"], x, cache["k0"], cache["v0"], pos, cfg, env, False)
        def body(h, inp):
            p, kc, vc = inp
            h, kc, vc = _attn_decode(p, h, kc, vc, pos, cfg, env, True)
            return h, (kc, vc)
        x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
        cache["k"], cache["v"] = ks, vs
    elif fam == "mla_moe":
        x, cache["c0"], cache["r0"] = _mla_decode(
            params["block0"], x, cache["c0"], cache["r0"], pos, cfg, env, False)
        def body(h, inp):
            p, cc, rr = inp
            h, cc, rr = _mla_decode(p, h, cc, rr, pos, cfg, env, True)
            return h, (cc, rr)
        x, (cs, rs) = jax.lax.scan(
            body, x, (params["blocks"], cache["c_lat"], cache["k_rope"]))
        cache["c_lat"], cache["k_rope"] = cs, rs
    elif fam == "ssm":
        def body(h, p, state):
            hh = layers.rms_norm(h, p["ln"])
            y, state = ssm_mod.ssm_decode(p["mix"], hh, state, cfg, env)
            return h + y, state, None
        x, cache, _ = _ssm_layers(body, x, params["blocks"], cache)
    elif fam == "hybrid":
        x, cache = _hybrid_decode(params, cache, x, pos, cfg, env)
    else:
        raise ValueError(fam)

    x = layers.rms_norm(x, params["ln_f"])
    logits = layers.unembed(params["embed"], x, cfg)[:, 0]
    return logits, cache


def _hybrid_global_decode(p, x, kc, vc, conv, hs, pos, cfg, env):
    hy = cfg.hybrid
    b = x.shape[0]
    h = layers.rms_norm(x, p["ln1"])
    apos = pos + hy.n_meta
    positions = jnp.full((b, 1), apos, jnp.int32)
    q, k, v = layers.qkv_project(p["attn"], h, cfg, positions, env)
    kc = jax.lax.dynamic_update_slice(kc, k, (0, apos, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v, (0, apos, 0, 0))
    att = layers.decode_attention(q, kc, vc, apos + 1)
    att = layers.attn_output(p["attn"], att, cfg)
    sso, (conv, hs) = ssm_mod.ssm_decode(p["mix"], h, (conv, hs), cfg, env)
    bta = p["beta"]
    y = (0.5 * (bta[0] * layers.rms_norm(att, p["na"])
                + bta[1] * layers.rms_norm(sso, p["ns"]))).astype(cfg.compute_dtype)
    x = x + y
    h2 = layers.rms_norm(x, p["ln2"])
    return x + layers.mlp_apply(p["ffn"], h2, cfg), kc, vc, conv, hs


def _hybrid_window_decode(p, x, rk, rv, mk, mv, conv, hs, pos, cfg, env):
    """Returns (x, k, v, conv, hs): the new token's K/V for the ring's slot,
    which the caller writes."""
    hy = cfg.hybrid
    b = x.shape[0]
    w = hy.window
    h = layers.rms_norm(x, p["ln1"])
    apos = pos + hy.n_meta
    positions = jnp.full((b, 1), apos, jnp.int32)
    q, k, v = layers.qkv_project(p["attn"], h, cfg, positions, env)
    slot = jnp.mod(pos, w)
    rk = jax.lax.dynamic_update_slice(rk, k, (0, slot, 0, 0))
    rv = jax.lax.dynamic_update_slice(rv, v, (0, slot, 0, 0))
    # attend [meta | ring]; unfilled ring slots masked via cur_len trick:
    kall = jnp.concatenate([mk, rk], axis=1)
    vall = jnp.concatenate([mv, rv], axis=1)
    nvalid = hy.n_meta + jnp.minimum(pos + 1, w)
    # ring slots are stored unordered in time but all lie within the window,
    # so plain masked softmax over filled slots is exact.
    att = layers.decode_attention(q, kall, vall, nvalid)
    att = layers.attn_output(p["attn"], att, cfg)
    sso, (conv, hs) = ssm_mod.ssm_decode(p["mix"], h, (conv, hs), cfg, env)
    bta = p["beta"]
    y = (0.5 * (bta[0] * layers.rms_norm(att, p["na"])
                + bta[1] * layers.rms_norm(sso, p["ns"]))).astype(cfg.compute_dtype)
    x = x + y
    h2 = layers.rms_norm(x, p["ln2"])
    return x + layers.mlp_apply(p["ffn"], h2, cfg), k, v, conv, hs


def _hybrid_decode(params, cache, x, pos, cfg, env):
    cache = dict(cache)
    gi = 0
    def g(x):
        nonlocal gi
        p = params[f"global{gi}"]
        conv = {"x": cache[f"gconv_x{gi}"], "B": cache[f"gconv_B{gi}"],
                "C": cache[f"gconv_C{gi}"]}
        x, kc, vc, conv, hs = _hybrid_global_decode(
            p, x, cache[f"gk{gi}"], cache[f"gv{gi}"], conv,
            cache[f"gh{gi}"], pos, cfg, env)
        cache[f"gk{gi}"], cache[f"gv{gi}"] = kc, vc
        cache[f"gconv_x{gi}"], cache[f"gconv_B{gi}"], cache[f"gconv_C{gi}"] = \
            conv["x"], conv["B"], conv["C"]
        cache[f"gh{gi}"] = hs
        gi += 1
        return x

    def seg(x, name, pstack):
        def body(h, inp, state):
            p, rk, rv, mk, mv = inp
            h, k, v, conv, hs = _hybrid_window_decode(
                p, h, rk, rv, mk, mv, *state, pos, cfg, env)
            return h, (conv, hs), (k, v)
        x, new, (ks, vs) = _ssm_layers(
            body, x, (pstack, cache[f"{name}_k"], cache[f"{name}_v"],
                      cache[f"{name}_mk"], cache[f"{name}_mv"]),
            cache, f"{name}_")
        # the rings are read-only in the loop: carried, XLA:TPU would lay
        # them out for the attention inside it and copy them in and out.
        # Every layer's new slot is written in place after it.
        slot = jnp.mod(pos, cfg.hybrid.window)
        for nm, kv in (("k", ks), ("v", vs)):
            new[f"{name}_{nm}"] = jax.lax.dynamic_update_slice(
                new[f"{name}_{nm}"], kv, (0, 0, slot, 0, 0))
        cache.update(new)
        return x

    x = g(x)
    x = seg(x, "wa", params["win_a"])
    x = g(x)
    x = seg(x, "wb", params["win_b"])
    x = g(x)
    return x, cache
