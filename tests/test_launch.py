"""The launchers' smoke paths and the compile-cache rule they start with."""
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch import compile_cache, serve, train

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test that sets it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             cache_config):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_ignored_checkout_dir(monkeypatch,
                                                              cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.use_compile_cache() == path      # no pid, no time
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_compile_cache_sets_nothing_outside_a_checkout(monkeypatch, tmp_path,
                                                       cache_config):
    """An installed package (no pyproject.toml above it) keeps no cache
    rather than one shared by every environment of the prefix."""
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT", tmp_path)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_serve_launcher_smoke_mesh(monkeypatch, tmp_path):
    """--smoke serves on a 1x1 ("data","model") mesh (hymba: prompt 32 is
    two windows of the smoke config)."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    res = serve.main(["--arch", "hymba-1.5b", "--smoke", "--new-tokens", "4"])
    tokens = np.asarray(res["tokens"])
    assert tokens.shape == (4, 4)
    assert ((tokens >= 0) & (tokens < 256)).all()
    assert np.isfinite(np.asarray(res["logits"], np.float32)).all()


def test_train_launcher_smoke_mesh_with_governor(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    m = train.main(["--arch", "mamba2-130m", "--smoke", "--steps", "2",
                    "--seq", "32", "--batch", "4", "--governor", "a100"])
    assert len(m["loss"]) == 2
    assert all(math.isfinite(loss) for loss in m["loss"])
    assert m["governor"].time_s > 0
