"""Training driver: jit'd step + checkpointing + fault-tolerance hooks +
the energy-aware DVFS governor (the paper's runtime integrated first-class).

Per step the governor is consulted at each region boundary (regions from
the dry-run roofline cell when available, else measured step fractions);
its decisions are logged into the metrics stream.  Because the container
has no DVFS control surface, "applying" a frequency is a simulator call —
on real hardware the same hook issues the platform command (DESIGN.md #2).

With a :mod:`repro.obs` recorder installed, ``train()`` records
``train.init`` (weights, optimizer state, the jitted step, restore) and,
per step, ``train.iter`` holding ``train.batch`` (``make_batch``),
``train.step`` (``retry_step``: the dispatch), ``train.sync`` (the wait
for the loss), ``train.plan`` (the governor calls) and ``train.ckpt``
(``save_async``, when a checkpoint is taken).
"""
from __future__ import annotations

import dataclasses
import time

import jax

from repro import obs
from repro.checkpoint import Checkpointer
from repro.configs.registry import model_module
from repro.data.synthetic import make_batch
from repro.launch.specs import abstract_init, make_train_step
from repro.optim import adamw
from repro.parallel.sharding import param_shardings
from repro.runtime.fault_tolerance import retry_step


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 50
    checkpoint_dir: str | None = None
    seed: int = 0
    lr: float = 3e-4
    microbatches: int = 1
    grad_compression: bool = False   # bf16 grads + error feedback
    resume: bool = True


def train(cfg, shape, env, tc: TrainConfig | None = None, *,
          governor=None, device=None, regions=None, verbose=True) -> dict:
    """Returns metrics dict (losses, step times, governor stats)."""
    if tc is None:
        tc = TrainConfig()
    with obs.span("train.init", "train"):
        mod = model_module(cfg)
        key = jax.random.PRNGKey(tc.seed)
        params, axes = mod.init(key, cfg)
        opt_state = adamw.init(params)
        if env.mesh is not None:
            p_sds, _ = abstract_init(cfg)
            p_sh = param_shardings(env, axes, p_sds)
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, s) if s is not None else x,
                params, p_sh)

        if tc.grad_compression:
            from repro.optim import compression
            opt_state["err"] = compression.init_error(params)
        opt_cfg = adamw.AdamWConfig(lr=tc.lr)
        step_fn = jax.jit(make_train_step(cfg, env, opt_cfg,
                                          microbatches=tc.microbatches,
                                          grad_compression=tc.grad_compression),
                          donate_argnums=(0, 1))

        ckpt = Checkpointer(tc.checkpoint_dir) if tc.checkpoint_dir else None
        start = 0
        if ckpt and tc.resume:
            latest = ckpt.latest_step()
            if latest is not None:
                state = ckpt.restore(latest, {"params": params, "opt": opt_state})
                params, opt_state = state["params"], state["opt"]
                start = latest + 1
                if verbose:
                    print(f"[train] resumed from step {latest}")

    metrics = {"loss": [], "step_time": [], "governor": None,
               "resumed_at": start}

    for step in range(start, tc.steps):
        with obs.span("train.iter", "train"):
            with obs.span("train.batch", "train"):
                batch = make_batch(cfg, shape, step=step, seed=tc.seed)
            t0 = time.perf_counter()
            with obs.span("train.step", "train"):
                loss, params, opt_state = retry_step(step_fn, params,
                                                     opt_state, batch)
            with obs.span("train.sync", "train"):
                loss = float(loss)
            dt = time.perf_counter() - t0
            metrics["loss"].append(loss)
            metrics["step_time"].append(dt)

            if governor is not None and regions is not None:
                # region-boundary frequency planning for the *next* step
                with obs.span("train.plan", "train"):
                    for r in regions:
                        governor.plan(r, device)

            if ckpt and tc.checkpoint_every and (step + 1) % tc.checkpoint_every == 0:
                with obs.span("train.ckpt", "train"):
                    ckpt.save_async(step, {"params": params, "opt": opt_state})
            if verbose and (step % tc.log_every == 0 or step == tc.steps - 1):
                print(f"[train] step {step:5d} loss {loss:.4f} ({dt*1e3:.0f} ms)")

    if ckpt:
        ckpt.wait()
        ckpt.save(tc.steps - 1, {"params": params, "opt": opt_state})
    if governor is not None and regions is not None:
        metrics["governor"] = governor.simulate(regions * tc.steps)
    metrics["params"] = params
    metrics["opt_state"] = opt_state
    return metrics
