"""Whole runs at the smoke sizes on the CPU, the harness's look for a chip
skipped, with the timed path broken underneath: ``correct`` must come out
false for every fault the cell can have, and true when nothing is broken.

Serving can alter a token where it is produced and can return its state
(the cache) unchanged from a decode step; training can return its state
unchanged from a step and can leave out half of the batch, the mean taken
over the rest.  Every cell runs on one chip, so no exchange between chips
can be left out."""
import jax
import jax.numpy as jnp
import pytest

import drive_serve
import drive_train
import harness
from repro.runtime import serve_loop, train_loop

SEED = 2 ** 31 + 17
SERVE = ["hymba-serve-decode", "mamba2-serve-decode"]


def _run(drv, cell):
    res, checks, _ = drv.run(cell, SEED, 1.0, False, harness.now(), jax.devices())
    return res, checks


@pytest.mark.parametrize("name", SERVE)
def test_sound_serving_is_correct(smoke_cell, name):
    res, checks = _run(drive_serve, smoke_cell(name))
    assert res["correct"], checks


@pytest.mark.parametrize("name", SERVE)
def test_served_token_altered(smoke_cell, monkeypatch, name):
    real = serve_loop._decode_step

    def altered(params, cache, tok, pos, cfg, env):
        logits, cache = real(params, cache, tok, pos, cfg, env)
        lg = logits[:, :cfg.vocab].astype(jnp.float32)
        worst = jnp.argmin(lg, -1)
        lg = lg.at[jnp.arange(lg.shape[0]), worst].set(jnp.max(lg, -1) + 1.0)
        return lg.astype(logits.dtype), cache

    monkeypatch.setattr(serve_loop, "_decode_step", altered)
    res, checks = _run(drive_serve, smoke_cell(name))
    assert not res["correct"], checks


@pytest.mark.parametrize("name", SERVE)
def test_decode_state_unchanged(smoke_cell, monkeypatch, name):
    real = serve_loop._decode_step

    def stale(params, cache, tok, pos, cfg, env):
        kept = jax.tree.map(jnp.copy, cache)
        logits, _ = real(params, cache, tok, pos, cfg, env)
        return logits, kept

    monkeypatch.setattr(serve_loop, "_decode_step", stale)
    res, checks = _run(drive_serve, smoke_cell(name))
    assert not res["correct"], checks


def test_sound_training_is_correct(smoke_cell):
    res, checks = _run(drive_train, smoke_cell("mamba2-train"))
    assert res["correct"], checks


def _broken_step(monkeypatch, wrap):
    real = train_loop.make_train_step
    monkeypatch.setattr(train_loop, "make_train_step",
                        lambda *a, **k: wrap(real(*a, **k)))


def test_training_state_unchanged(smoke_cell, monkeypatch):
    def wrap(step):
        def unchanged(params, opt_state, batch):
            loss, _, _ = step(params, opt_state, batch)
            return loss, params, opt_state
        return unchanged

    _broken_step(monkeypatch, wrap)
    res, checks = _run(drive_train, smoke_cell("mamba2-train"))
    assert not res["correct"], checks
    assert checks["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_training_half_batch(smoke_cell, monkeypatch):
    def wrap(step):
        def half(params, opt_state, batch):
            rows = batch["tokens"].shape[0] // 2
            return step(params, opt_state, {"tokens": batch["tokens"][:rows]})
        return half

    _broken_step(monkeypatch, wrap)
    res, checks = _run(drive_train, smoke_cell("mamba2-train", batch=4))
    assert not res["correct"], checks
