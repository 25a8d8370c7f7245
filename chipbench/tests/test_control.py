"""The control of ``correct``: the plain reference computed in float8 (e4m3,
one scale per tensor), the precision below the configurations' bfloat16,
put in the program's place.  It has to come out not correct against the
cell's own limits while the sound program, in the same run, is correct.

On the CPU at the published widths with short traffic.  The rounding error
that separates the two grows with depth, so depth is cut only where the
model is large: hymba-1.5b runs 6 of its 32 layers (three global, three
window; window and meta tokens cut to 64 and 16 for short prompts),
mamba2-130m's serving all 24 layers and its training 12.  The chip
readings at the cells' own sizes are in PERF.md (``calibrate.py``)."""
import dataclasses

import jax

import drive_serve
import drive_train
import generator
import harness
from conftest import cut_to_cpu

SEED = 2 ** 31 + 29
SHORT = dict(batch=2, prompt_lens=[64], weights=[1], new_tokens=16,
             sample_requests=2)


def _serving(cell_at, name, arch, n_layers):
    cfg = dataclasses.replace(cut_to_cpu(arch), n_layers=n_layers)
    cell = cell_at(name, cfg, **SHORT)
    res, checks, extra = drive_serve.run(cell, SEED, 0.0, False, harness.now(),
                                         jax.devices())
    assert res["correct"], checks
    params = extra["session"].init(extra["session"].key)
    gap = drive_serve.widest_gap(cell.cmod, cell.spec, params, extra["sample"],
                                 q="fp8")
    assert gap > cell.limits["max_logit_gap"], gap


def test_hymba_serving_control_fails(cell_at):
    _serving(cell_at, "hymba-serve-decode", "hymba-1.5b", 6)


def test_mamba2_serving_control_fails(cell_at):
    _serving(cell_at, "mamba2-serve-decode", "mamba2-130m", 24)


def test_training_control_fails(cell_at):
    cfg = dataclasses.replace(cut_to_cpu("mamba2-130m"), n_layers=12)
    cell = cell_at("mamba2-train", cfg, batch=2, seq=256)
    mix = cell.traffic
    traffic = generator.make(mix, cell.spec["vocab"], SEED)
    key = jax.random.PRNGKey(harness.init_seed(SEED))
    run = lambda **kw: drive_train.reference_steps(
        cell.cmod, cell.spec, mix["optimizer"], key, traffic,
        mix["checked_steps"], **kw)
    gaps = drive_train.compare(run(q="fp8"), run())
    assert any(gaps[k] > lim for k, lim in cell.limits.items()), gaps
