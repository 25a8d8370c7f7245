"""Per-arch smoke tests (assignment requirement): reduced config, one
forward/train step on CPU, output shapes + no NaNs; plus decode-vs-forward
consistency for representative families."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.registry import decode_module, model_module
from repro.models import decode, layers, ssm as ssm_mod
from repro.parallel.sharding import make_env
from repro.runtime import serve_loop

ENV = make_env(None, None)


def _batch(cfg, b=2, s=32, seed=0):
    key = jax.random.PRNGKey(seed)
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["img_embeds"] = jax.random.normal(
            key, (b, cfg.vlm.n_patches, cfg.d_model), cfg.compute_dtype)
    if cfg.family == "encdec":
        batch["enc_frames"] = jax.random.normal(
            key, (b, cfg.encdec.n_frames, cfg.d_model), cfg.compute_dtype)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    mod = model_module(cfg)
    params, axes = mod.init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits, aux = mod.forward(params, batch, cfg, ENV)
    assert logits.shape == (2, 32, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    loss, grads = jax.value_and_grad(
        lambda p: mod.loss_fn(p, batch, cfg, ENV))(params)
    assert bool(jnp.isfinite(loss))
    for g in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode(arch):
    cfg = get_config(arch, smoke=True)
    mod, dec = model_module(cfg), decode_module(cfg)
    params, _ = mod.init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits, cache = dec.prefill(params, batch, cfg, ENV, max_len=64)
    assert logits.shape == (2, cfg.padded_vocab)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache = dec.decode_step(params, cache, tok, jnp.int32(32), cfg, ENV)
    assert bool(jnp.isfinite(logits2.astype(jnp.float32)).all())
    assert int(jnp.argmax(logits2[0])) < cfg.vocab     # pad ids masked


@pytest.mark.parametrize("arch", ["llama3-8b", "mamba2-130m",
                                  "deepseek-v2-236b", "whisper-medium"])
def test_decode_matches_forward(arch):
    """Incremental decode must reproduce teacher-forced forward logits."""
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32)
    if cfg.moe is not None:
        # capacity-based token dropping legitimately differs between a
        # 16-token prefill and the 32-token forward (different T -> different
        # capacity); raise cf so no tokens drop and the cache math is tested
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    mod, dec = model_module(cfg), decode_module(cfg)
    params, _ = mod.init(jax.random.PRNGKey(1), cfg)
    b, s, ctx = 2, 32, 16
    batch = _batch(cfg, b, s, seed=1)
    full_logits, _ = mod.forward(params, batch, cfg, ENV)

    prefill_batch = dict(batch, tokens=batch["tokens"][:, :ctx])
    logits, cache = dec.prefill(params, prefill_batch, cfg, ENV, max_len=s)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, ctx - 1]),
                               atol=2e-3, rtol=2e-3)
    for i in range(ctx, s):
        tok = batch["tokens"][:, i: i + 1]
        logits, cache = dec.decode_step(params, cache, tok, jnp.int32(i),
                                        cfg, ENV)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits[:, i]),
                                   atol=2e-3, rtol=2e-3)


def _scan_decode_step(params, cache, token, pos, cfg, env):
    """``decode.decode_step`` for the ssm and hybrid families as it was
    written before the caches were updated in place: each layer-stacked
    cache goes into ``lax.scan`` as an input and comes back as one of its
    outputs, a fresh buffer."""
    x = layers.embed_lookup(params["embed"], token, cfg)
    cache = dict(cache)
    leaves = ("conv_x", "conv_B", "conv_C", "h")

    def ssm_state(cx, cb, cc, hs):
        return {"x": cx, "B": cb, "C": cc}, hs

    if cfg.family == "ssm":
        def body(h, inp):
            p, *state = inp
            y, (conv, hs) = ssm_mod.ssm_decode(
                p["mix"], layers.rms_norm(h, p["ln"]), ssm_state(*state),
                cfg, env)
            return h + y, (conv["x"], conv["B"], conv["C"], hs)
        x, new = jax.lax.scan(body, x, (params["blocks"],
                                        *(cache[n] for n in leaves)))
        cache.update(zip(leaves, new))
    else:
        w = cfg.hybrid.window

        def glob(x, gi):
            x, kc, vc, conv, hs = decode._hybrid_global_decode(
                params[f"global{gi}"], x, cache[f"gk{gi}"], cache[f"gv{gi}"],
                *ssm_state(*(cache[f"g{n}{gi}"] for n in leaves)), pos, cfg,
                env)
            cache[f"gk{gi}"], cache[f"gv{gi}"], cache[f"gh{gi}"] = kc, vc, hs
            for k in "xBC":
                cache[f"gconv_{k}{gi}"] = conv[k]
            return x

        def seg(x, name, pstack):
            names = [f"{name}_{n}" for n in ("k", "v", *leaves)]

            def body(h, inp):
                p, rk, rv, mk, mv, *state = inp
                h, k, v, conv, hs = decode._hybrid_window_decode(
                    p, h, rk, rv, mk, mv, *ssm_state(*state), pos, cfg, env)
                slot = jnp.mod(pos, w)
                rk = jax.lax.dynamic_update_slice(rk, k, (0, slot, 0, 0))
                rv = jax.lax.dynamic_update_slice(rv, v, (0, slot, 0, 0))
                return h, (rk, rv, conv["x"], conv["B"], conv["C"], hs)
            x, new = jax.lax.scan(
                body, x, (pstack, cache[names[0]], cache[names[1]],
                          cache[f"{name}_mk"], cache[f"{name}_mv"],
                          *(cache[n] for n in names[2:])))
            cache.update(zip(names, new))
            return x

        x = glob(x, 0)
        x = seg(x, "wa", params["win_a"])
        x = glob(x, 1)
        x = seg(x, "wb", params["win_b"])
        x = glob(x, 2)
    x = layers.rms_norm(x, params["ln_f"])
    return layers.unembed(params["embed"], x, cfg)[:, 0], cache


def _tree_spec(tree):
    return (jax.tree.structure(tree),
            [(a.shape, a.dtype) for a in jax.tree.leaves(tree)])


@pytest.mark.parametrize("arch", ["mamba2-130m", "hymba-1.5b"])
def test_in_place_decode_matches_scan_decode(arch):
    """The decode step that updates the stacked caches in place gives the
    tokens, logits and caches of the scan-output form, over more steps than
    hymba's ring holds, and hands back a cache of the tree it was given."""
    cfg = get_config(arch, smoke=True)
    mod, dec = model_module(cfg), decode_module(cfg)
    params, _ = mod.init(jax.random.PRNGKey(2), cfg)
    s = 16
    steps = (cfg.hybrid.window if cfg.hybrid else 16) + 8
    logits, cache = dec.prefill(params, _batch(cfg, 2, s, seed=2), cfg, ENV,
                                max_len=s + steps)
    step = jax.jit(dec.decode_step, static_argnums=(4, 5))
    ref_step = jax.jit(_scan_decode_step, static_argnums=(4, 5))
    given = _tree_spec(cache)
    ref_cache = cache
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            logits, cache = step(params, cache, tok, jnp.int32(s + i), cfg, ENV)
            ref_logits, ref_cache = ref_step(params, ref_cache, tok,
                                             jnp.int32(s + i), cfg, ENV)
            assert _tree_spec(cache) == given
            np.testing.assert_allclose(
                np.asarray(logits, np.float32),
                np.asarray(ref_logits, np.float32), atol=1e-4, rtol=0)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            np.testing.assert_array_equal(
                tok, jnp.argmax(ref_logits, -1)[:, None])
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4, rtol=0)


@pytest.mark.parametrize("arch", ["mamba2-130m", "hymba-1.5b"])
def test_serve_decode_step_donates_the_cache(arch):
    cfg = get_config(arch, smoke=True)
    params, _ = model_module(cfg).init(jax.random.PRNGKey(0), cfg)
    s = 16
    logits, cache = serve_loop._prefill(params, _batch(cfg, 2, s), cfg, ENV,
                                        s + 4)
    given = jax.tree.leaves(cache)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    _, new = serve_loop._decode_step(params, cache, tok, jnp.int32(s), cfg,
                                     ENV)
    assert all(a.is_deleted() for a in given)
    assert not any(a.is_deleted() for a in jax.tree.leaves(new))


def test_param_count_matches_actual():
    for arch in ("llama3-8b", "mamba2-130m"):
        cfg = get_config(arch, smoke=True)
        mod = model_module(cfg)
        params, _ = mod.init(jax.random.PRNGKey(0), cfg)
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        # padded vocab + norm scales make actual slightly larger
        assert actual == pytest.approx(cfg.param_count(), rel=0.12)
