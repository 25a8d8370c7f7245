"""Sharded execution against one-device execution, on any mesh.

:func:`check_sharded_equivalence` runs the three sharded paths of the model
zoo at reduced width in fp32 and compares each with the same computation
on one device:

  * the FSDP/TP train step (llama3-8b smoke) == the unsharded step
  * flash-decoding (seq-sharded KV cache, shard_map LSE combine) == plain
    decode
  * shard_map expert-parallel MoE (deepseek-moe-16b smoke) == local
    dispatch

The tests call it on a (2, 4) ("data", "model") mesh of eight CPU virtual
devices and ``chip_smoke.py --chips 4`` on a (2, 2) mesh of four chips.
Matmuls run at full fp32 precision so that the TPU's default one-pass
bf16 matmul does not blur the comparison.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.registry import decode_module, model_module
from repro.launch.specs import make_train_step
from repro.models import moe as moe_mod
from repro.optim import adamw
from repro.parallel.sharding import make_env, param_shardings

TOL = 2e-3                 # fp32 sharded vs unsharded, loss / params / logits
MOE_AGREE = 0.95           # share of tokens whose MoE output agrees within TOL


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _fp32(cfg):
    return dataclasses.replace(cfg, param_dtype=jnp.float32,
                               compute_dtype=jnp.float32)


def _max_abs_diff(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def check_sharded_equivalence(mesh, log=print) -> dict:
    """Run the three equivalences on ``mesh`` (axes ("data", "model")).

    Inputs are placed across the whole mesh with ``NamedSharding``.
    Returns the measured differences; raises ``AssertionError`` on the
    first check that fails."""
    with jax.default_matmul_precision("highest"):
        out = {}
        out.update(_train_step(mesh))
        log(f"train_step sharded==unsharded OK loss {out['loss_1']} "
            f"vs {out['loss_n']}")
        out.update(_flash_decode(mesh))
        log(f"flash_decode == plain decode OK {out['decode_logit_diff']}")
        out.update(_moe(mesh))
        log(f"moe shard_map ~= local OK {out['moe_frac_equal']}")
    return out


def _train_step(mesh) -> dict:
    cfg = _fp32(get_config("llama3-8b", smoke=True))
    mod = model_module(cfg)
    params, axes = mod.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                          cfg.vocab)}
    loss1, p1, _ = jax.jit(make_train_step(cfg, make_env(cfg, None)))(
        params, adamw.init(params), batch)

    env = make_env(cfg, mesh)
    p_sh = param_shardings(env, axes, jax.eval_shape(lambda: params))
    params_s = jax.tree.map(jax.device_put, params, p_sh)
    batch_s = {"tokens": jax.device_put(batch["tokens"],
                                        NamedSharding(mesh, P("data", None)))}
    lossN, pN, _ = jax.jit(make_train_step(cfg, env))(
        params_s, adamw.init(params_s), batch_s)

    loss1, lossN = float(loss1), float(lossN)
    _require(abs(loss1 - lossN) < TOL, f"train loss {loss1} vs {lossN}")
    d = max(_max_abs_diff(a, b)
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pN)))
    _require(d < TOL, f"updated params differ by {d}")
    return {"loss_1": loss1, "loss_n": lossN, "param_diff": d}


def _flash_decode(mesh) -> dict:
    cfg = _fp32(get_config("llama3-8b", smoke=True))
    dec = decode_module(cfg)
    params, _ = model_module(cfg).init(jax.random.PRNGKey(2), cfg)
    b, s, m = 2, 16, 32
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (b, s), 0,
                                          cfg.vocab)}
    env1 = make_env(cfg, None)
    lg1, cache1 = dec.prefill(params, batch, cfg, env1, m)
    tok = jnp.argmax(lg1, -1)[:, None].astype(jnp.int32)
    lg1b, _ = dec.decode_step(params, cache1, tok, jnp.int32(s), cfg, env1)

    # make_env turns flash-decoding on by itself where the kv heads do not
    # divide the model axis; on a model axis they divide, ask for it
    if cfg.n_kv % mesh.shape["model"]:
        _require(make_env(cfg, mesh).flash_decode,
                 f"kv={cfg.n_kv} % tp={mesh.shape['model']} != 0 must "
                 f"enable flash decode")
    env = make_env(cfg, mesh, flash_decode=True)
    replicated = NamedSharding(mesh, P())
    params_s = jax.device_put(params, replicated)
    batch_s = {"tokens": jax.device_put(batch["tokens"],
                                        NamedSharding(mesh, P("data", None)))}
    lgN, cacheN = jax.jit(lambda p, bt: dec.prefill(p, bt, cfg, env, m))(
        params_s, batch_s)
    c_sh = {k: NamedSharding(mesh, env.spec_sized(ax, cacheN[k].shape))
            for k, ax in dec.cache_spec(cfg, b, m, env)[1].items()}
    cacheN = jax.tree.map(jax.device_put, cacheN, c_sh)
    lgNb, _ = jax.jit(
        lambda p, c, t, i: dec.decode_step(p, c, t, i, cfg, env))(
        params_s, cacheN, jax.device_put(tok, replicated), jnp.int32(s))
    dd = _max_abs_diff(lg1b, lgNb)
    _require(dd < TOL, f"flash-decode logits differ by {dd}")
    return {"decode_logit_diff": dd}


def _moe(mesh) -> dict:
    cfg = _fp32(get_config("deepseek-moe-16b", smoke=True))
    p, _ = moe_mod.moe_init(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 16, cfg.d_model))
    out1, aux1 = moe_mod.moe_apply(p, x, cfg, make_env(cfg, None))
    env = make_env(cfg, mesh)
    x_s = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    p_s = jax.device_put(p, NamedSharding(mesh, P()))
    outN, auxN = jax.jit(lambda p, x: moe_mod.moe_apply(p, x, cfg, env))(
        p_s, x_s)
    # EP partitions the capacity per (data-shard, expert): with tokens split
    # across data shards the dropping boundary can differ for a few tokens;
    # compare the overwhelming majority instead of a strict allclose
    diff = np.abs(np.asarray(out1) - np.asarray(outN)).max(axis=-1).ravel()
    frac_equal = float((diff < TOL).mean())
    _require(frac_equal > MOE_AGREE,
             f"only {frac_equal:.3f} of MoE tokens agree")
    aux_diff = abs(float(aux1) - float(auxN))
    _require(aux_diff < 1e-3, f"MoE aux loss differs by {aux_diff}")
    return {"moe_frac_equal": frac_equal, "moe_aux_diff": aux_diff}
