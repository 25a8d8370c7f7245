"""Gradient compression: error feedback kills quantization bias; training
with compressed grads tracks the uncompressed baseline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests run when installed
from hypothesis import given, settings, strategies as st

from repro.optim import adamw
from repro.optim.compression import compress, init_error


def test_error_feedback_unbiased_accumulation():
    """Constant gradient g: sum of compressed emissions over T steps must
    equal T*g up to one quantum (bias does not accumulate)."""
    g = {"w": jnp.full((64,), 1.0 + 1e-3, jnp.float32)}  # not bf16-exact
    err = init_error(g)
    total = jnp.zeros((64,), jnp.float32)
    T = 200
    for _ in range(T):
        q, err = compress(g, err)
        total = total + q["w"].astype(jnp.float32)
    # residual bias decays as O(quantum / T): one bf16 quantum (~4e-3 at
    # this magnitude) spread over 200 steps leaves ~2e-5 relative error
    np.testing.assert_allclose(np.asarray(total) / T,
                               np.asarray(g["w"]), rtol=1e-4)


def test_compressed_training_tracks_fp32():
    """Least-squares toy problem: Adam with bf16+EF grads converges to the
    same loss neighborhood as fp32 grads."""
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (128, 16))
    w_true = jax.random.normal(jax.random.PRNGKey(1), (16,))
    y = X @ w_true

    def loss_fn(w):
        return jnp.mean((X @ w - y) ** 2)

    cfg = adamw.AdamWConfig(lr=5e-2, weight_decay=0.0)

    def run(compressed):
        w = {"w": jnp.zeros((16,))}
        st_ = adamw.init(w)
        err = init_error(w)
        for _ in range(300):
            g = jax.grad(lambda p: loss_fn(p["w"]))(w)
            if compressed:
                g, err = compress(g, err)
            w, st_, _ = adamw.update(w, g, st_, cfg)
        return float(loss_fn(w["w"]))

    l_fp32 = run(False)
    l_comp = run(True)
    assert l_comp < 1e-2, l_comp
    assert abs(l_comp - l_fp32) < 5e-3


@given(st.integers(0, 1000), st.floats(1e-4, 10.0))
@settings(max_examples=25, deadline=None)
def test_compress_residual_bounded(seed, scale):
    """Property: the error-feedback residual never exceeds one bf16 ULP of
    the corrected gradient (no runaway error state)."""
    g = {"w": scale * jax.random.normal(jax.random.PRNGKey(seed), (32,))}
    err = init_error(g)
    for _ in range(5):
        q, err = compress(g, err)
        corrected = np.abs(np.asarray(g["w"], np.float32)) + 1e-30
        # bf16 has 8 mantissa bits -> relative quantum ~ 2^-8
        assert (np.abs(np.asarray(err["w"])) <=
                corrected * 2.0 ** -7 + 1e-6).all()


def test_train_loop_with_compression():
    """Integration: the grad_compression flag trains and learns."""
    from repro.configs import get_config
    from repro.configs.shapes import ShapeSpec
    from repro.parallel.sharding import make_env
    from repro.runtime.train_loop import TrainConfig, train

    cfg = get_config("llama3-8b", smoke=True)
    shape = ShapeSpec("t", 32, 4, "train")
    m = train(cfg, shape, make_env(cfg, None),
              TrainConfig(steps=20, lr=2e-3, log_every=100,
                          grad_compression=True), verbose=False)
    assert np.mean(m["loss"][-3:]) < np.mean(m["loss"][:3])
