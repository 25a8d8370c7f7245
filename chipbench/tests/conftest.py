"""The benchmark's own tests run on the CPU at small sizes:

    PYTHONPATH=src python -m pytest -q chipbench/tests
"""
import dataclasses
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402


def spec_of(cfg) -> dict:
    """A benchmark configuration dict holding a program config's sizes."""
    from repro.models import lm

    d = dict(arch=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
             vocab=cfg.vocab, vocab_pad_to=cfg.vocab_pad_to,
             param_dtype=jnp.dtype(cfg.param_dtype).name,
             compute_dtype=jnp.dtype(cfg.compute_dtype).name,
             ssm=dataclasses.asdict(cfg.ssm))
    if cfg.family == "hybrid":
        d.update(n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
                 d_ff=cfg.d_ff, rope_theta=cfg.rope_theta,
                 hybrid=dataclasses.asdict(cfg.hybrid),
                 global_layers=list(lm._global_layer_ids(cfg)))
    return d


SMOKE_TRAFFIC = {
    "hymba-1.5b": dict(batch=2, prompt_lens=[16, 32], weights=[1, 1],
                       new_tokens=6, sample_requests=3),
    "mamba2-130m": dict(batch=2, prompt_lens=[20, 24], weights=[1, 1],
                        new_tokens=6, sample_requests=3),
}


def cut_to_cpu(arch):
    """The published configuration, hymba's window and meta tokens cut to
    64 and 16 so that short prompts fit a CPU test."""
    from repro.configs import get_config
    from repro.configs.base import HybridConfig

    cfg = get_config(arch)
    if cfg.hybrid is not None:
        cfg = dataclasses.replace(cfg, hybrid=HybridConfig(window=64, n_meta=16))
    return cfg


@pytest.fixture
def cell_at(monkeypatch):
    """A cell of BENCHMARK.json run at the program configuration ``cfg``,
    with the harness pointed at it and its traffic updated."""
    import harness

    def make(name, cfg, **traffic):
        cell = harness.cell(name)
        cell.spec = spec_of(cfg)
        cell.traffic.update(traffic)
        monkeypatch.setattr(harness, "program_config", lambda spec: cfg)
        return cell

    return make


@pytest.fixture
def smoke_cell(cell_at):
    """A cell cut to the program's smoke configuration."""
    from repro.configs import get_config

    def make(name, **traffic):
        import harness

        arch = harness.cell(name).spec["arch"]
        cfg = get_config(arch, smoke=True)
        kind = harness.cell(name).traffic["kind"]
        base = SMOKE_TRAFFIC[arch] if kind == "serve" else dict(batch=2, seq=64)
        return cell_at(name, cfg, **{**base, **traffic})

    return make
