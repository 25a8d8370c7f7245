"""The configurations' operation counts against the program's own jaxpr,
counted by ``repro.costmodel`` (``dot_general`` FLOPs, scans multiplied
through), at the smoke sizes.

Tolerance 2%: the program contracts three-operand einsums of the
state-space update in two products, one of them an outer product the
counts here leave out (under 1% of the total at these sizes)."""
import jax
import jax.numpy as jnp
import pytest

import harness
from conftest import spec_of
from repro import costmodel
from repro.configs import get_config
from repro.models import decode, lm
from repro.parallel.sharding import make_env

TOL = 0.02


def _module(name):
    return harness.load_module(harness.HERE / "configs" / f"{name}.py")


def test_mamba2_forward_flops_match_costmodel():
    cfg = get_config("mamba2-130m", smoke=True)
    env = make_env(cfg, None)
    spec, cmod = spec_of(cfg), _module("mamba2-130m")
    params = jax.eval_shape(lambda k: lm.init(k, cfg)[0], jax.random.PRNGKey(0))
    S = 3 * cfg.ssm.chunk
    toks = jax.ShapeDtypeStruct((1, S), jnp.int32)
    st = costmodel.cost_of(lambda p, t: lm.forward(p, {"tokens": t}, cfg, env)[0],
                           params, toks)
    want = cmod.fwd_flops(spec, S, full_square=True)
    assert st.flops == pytest.approx(want, rel=TOL)


@pytest.mark.parametrize("arch", ["hymba-1.5b", "mamba2-130m"])
def test_decode_flops_match_costmodel(arch):
    cfg = get_config(arch, smoke=True)
    env = make_env(cfg, None)
    spec, cmod = spec_of(cfg), _module(arch)
    params = jax.eval_shape(lambda k: lm.init(k, cfg)[0], jax.random.PRNGKey(0))
    b, max_len = 2, 4 * (cfg.hybrid.window if cfg.hybrid else 16)
    cache = decode.cache_spec(cfg, b, max_len)[0]
    pos = max_len - 1       # every cache slot the step scores is a valid key
    st = costmodel.cost_of(
        lambda p, c, t: decode.decode_step(p, c, t, jnp.int32(pos), cfg, env),
        params, cache, jax.ShapeDtypeStruct((b, 1), jnp.int32))
    assert st.flops == pytest.approx(b * cmod.decode_flops(spec, pos), rel=TOL)


def test_training_counts_three_forwards():
    spec = harness.read_json(harness.HERE / "configs" / "mamba2-130m.json")
    cmod = _module("mamba2-130m")
    assert cmod.train_flops_per_token(spec, 2048) == pytest.approx(
        3 * cmod.fwd_flops(spec, 2048) / 2048)
    # about 6 x the parameters touched per token, plus the chunked scan
    n = cmod.param_bytes(spec) / 2
    assert 6 * n < cmod.train_flops_per_token(spec, 2048) < 6 * n * 1.5
