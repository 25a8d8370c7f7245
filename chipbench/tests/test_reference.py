"""The plain references against the program, at the smoke sizes on the CPU
with every product in float32: the seeded weights are the program's own
draws bit for bit, and forward, prefill-then-decode and loss agree to
float32 rounding (1e-4 absolute on logits of magnitude ~1, 1e-5 relative
on the loss)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import spec_of
from repro.configs import get_config
from repro.models import decode, lm
from repro.parallel.sharding import make_env

ARCHS = ["hymba-1.5b", "mamba2-130m"]


def _setup(arch):
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32)
    cmod = harness.load_module(harness.HERE / "configs" / f"{arch}.py")
    return cfg, spec_of(cfg), cmod, make_env(cfg, None)


@pytest.mark.parametrize("arch", ARCHS)
def test_weights_are_the_programs_draws(arch):
    cfg = get_config(arch, smoke=True)
    cmod = harness.load_module(harness.HERE / "configs" / f"{arch}.py")
    key = jax.random.PRNGKey(1234567)
    mine = cmod.init_params(key, spec_of(cfg))
    theirs = lm.init(key, cfg)[0]
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_served_logits_match(arch):
    cfg, spec, cmod, env = _setup(arch)
    params = cmod.init_params(jax.random.PRNGKey(3), spec)
    T = 48
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, T), 0, cfg.vocab)
    ref = cmod.logits(params, toks[0], spec)
    with jax.default_matmul_precision("highest"):
        fwd = lm.forward(params, {"tokens": toks}, cfg, env)[0][0, :, :cfg.vocab]
        P = cfg.hybrid.window if cfg.hybrid else 20
        lg, cache = decode.prefill(params, {"tokens": toks[:, :P]}, cfg, env, T)
        served = [lg]
        for i in range(P, T - 1):
            lg, cache = decode.decode_step(params, cache, toks[:, i:i + 1],
                                           jnp.int32(i), cfg, env)
            served.append(lg)
    served = jnp.stack(served, 1)[0, :, :cfg.vocab]
    np.testing.assert_allclose(fwd, ref, atol=1e-4)
    np.testing.assert_allclose(served, ref[P - 1:T - 1], atol=1e-4)


def test_training_loss_matches():
    cfg, spec, cmod, env = _setup("mamba2-130m")
    params = cmod.init_params(jax.random.PRNGKey(5), spec)
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        want = lm.loss_fn(params, {"tokens": toks}, cfg, env)
    assert float(cmod.loss(params, toks, spec)) == pytest.approx(float(want),
                                                                 rel=1e-5)


def test_program_config_is_checked_against_the_benchmark_sizes():
    spec = harness.read_json(harness.HERE / "configs" / "hymba-1.5b.json")
    assert harness.program_config(spec).d_model == 1600
    with pytest.raises(ValueError, match="d_ff"):
        harness.program_config(dict(spec, d_ff=5000))
