"""Smoke run of the system's main paths on a TPU.

    python chip_smoke.py             # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4   # four chips: the sharded equivalences only

One process holds the chip for every phase:

  (a) the methodology's workload: the Pallas ``microbench`` FMA chain,
      compiled by Mosaic (16 cores, 64 iterations of a 32-step chain),
      checked against ``microbench_ref`` on the same chip and timed over
      20 launches;
  (b) governed serving: hymba-1.5b at its published widths through
      ``repro.runtime.serve_loop.serve`` (batch 4, prompt 1024, 32 new
      tokens), with a governor measured on the ``vmapped-sim`` backend;
      the tokens must be in-vocab ids and the last logits finite;
  (c) governed training: mamba2-130m at its published widths through the
      ``repro.launch.train`` entry point (batch 8, sequence 2048,
      ``--governor a100``); every loss must be finite and the parameters
      must change.

With ``--chips 4`` it runs only
:func:`repro.parallel.equivalence.check_sharded_equivalence` on a (2, 2)
("data", "model") mesh of the four chips.  Weights are random, from fixed
seeds.  The last line of standard output is one JSON object naming the
device; it reads ``"ok": true`` only when every phase passed.  Without a
TPU, or outside a checkout of this repository, the script exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

MICROBENCH = dict(cores=16, n_iters=64, unroll=32, launches=20)
SERVE = dict(arch="hymba-1.5b", batch=4, prompt=1024, new_tokens=32,
             requests=2)
TRAIN = dict(arch="mamba2-130m",
             argv=["--batch", "8", "--seq", "2048", "--steps", "4",
                   "--governor", "a100"])


def phase_microbench(cores, n_iters, unroll, launches) -> dict:
    """(a) Compiled microbench vs its jnp reference on the same device."""
    import jax
    import numpy as np
    from repro.kernels.microbench import microbench, microbench_ref
    from repro.kernels.microbench.ops import make_input

    x = make_input(cores, seed=0)
    hlo = microbench.lower(x, n_iters=n_iters, unroll=unroll).compile().as_text()
    compiled = "tpu_custom_call" in hlo
    out = np.asarray(microbench(x, n_iters=n_iters, unroll=unroll))
    ref = np.asarray(jax.jit(microbench_ref, static_argnames=(
        "n_iters", "unroll"))(x, n_iters=n_iters, unroll=unroll))
    # each chain step rounds twice (mul, add) at <= 2^-24 relative; the
    # kernel and the reference may round differently (fused or not), so
    # they may drift apart by at most 2 * 2 roundings per step
    rtol = 4 * n_iters * unroll * 2.0 ** -24
    rel = float(np.max(np.abs(out - ref) / np.abs(ref)))
    times = []
    for _ in range(launches):
        t0 = time.perf_counter()
        jax.block_until_ready(microbench(x, n_iters=n_iters, unroll=unroll))
        times.append(time.perf_counter() - t0)
    print(f"(a) microbench cores={cores} n_iters={n_iters} unroll={unroll}: "
          f"compiled={compiled} max_rel_diff={rel!r} (tol {rtol!r}); "
          f"per-launch median {statistics.median(times) * 1e6!r} us, "
          f"min {min(times) * 1e6!r} us, max {max(times) * 1e6!r} us, "
          f"stdev {statistics.stdev(times) * 1e6!r} us over {launches}")
    if not compiled:
        raise RuntimeError("microbench did not compile to a Mosaic kernel")
    if not (np.all(np.isfinite(out)) and rel <= rtol):
        raise RuntimeError(f"microbench differs from microbench_ref: "
                           f"{rel} > {rtol}")
    return {"max_rel_diff": rel, "median_launch_s": statistics.median(times)}


def phase_serve(cfg, batch, prompt, new_tokens, requests) -> dict:
    """(b) Governed serving of ``cfg`` through serve_loop.serve."""
    import jax
    import numpy as np
    from repro.backends import create_backend
    from repro.configs.registry import model_module
    from repro.configs.shapes import ShapeSpec
    from repro.core.evaluation import MeasureConfig
    from repro.core.session import (LatestConfig, MeasurementSession,
                                    SessionConfig)
    from repro.data.synthetic import make_batch
    from repro.dvfs.governor import Governor
    from repro.parallel.sharding import make_env
    from repro.runtime.serve_loop import ServeConfig, serve

    device = create_backend("vmapped-sim", kind="a100", seed=0, n_cores=8)
    fs = device.frequencies
    freqs = [float(fs[i]) for i in (0, len(fs) // 2, -1)]
    session = MeasurementSession(
        device, freqs,
        SessionConfig(latest=LatestConfig(measure=MeasureConfig(
            min_measurements=6, max_measurements=10, rse_check_every=6))),
        device_name="a100")
    governor = Governor.from_session(session)

    # initialised op by op, as repro.launch.serve does: one jitted init
    # unrolls every layer's random draws and compiles for minutes
    params, _ = model_module(cfg).init(jax.random.PRNGKey(0), cfg)
    env = make_env(cfg, None)
    shape = ShapeSpec("chip_smoke", prompt, batch, "prefill")
    res = None
    for r in range(requests):
        n_cmds = len(device.history)
        res = serve(cfg, env, params, make_batch(cfg, shape, step=r),
                    ServeConfig(max_new_tokens=new_tokens),
                    governor=governor, device=device)
        clock = " -> ".join(f"{h['to']:g}" for h in device.history[n_cmds:])
        print(f"(b) {cfg.name} batch {r} ({batch} requests, prompt {prompt}, "
              f"{new_tokens} new tokens){' [compile included]' if r == 0 else ''}: "
              f"prefill {res['prefill_s'] * 1e3!r} ms, decode "
              f"{res['tokens_per_s']!r} tok/s; governor on a simulated a100 "
              f"clock (TPUs expose no DVFS API): "
              f"{clock + ' MHz' if clock else 'no change'}")
        tokens = np.asarray(res["tokens"])
        if tokens.shape != (batch, new_tokens):
            raise RuntimeError(f"tokens shape {tokens.shape}")
        if not ((tokens >= 0) & (tokens < cfg.vocab)).all():
            raise RuntimeError("token ids outside the vocabulary")
        if not np.isfinite(np.asarray(res["logits"], np.float32)).all():
            raise RuntimeError("non-finite logits")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"(b) first row {tokens[0, :8].tolist()}; "
          f"peak_bytes_in_use {peak!r}")
    return {"prefill_s": res["prefill_s"], "tokens_per_s": res["tokens_per_s"],
            "peak_bytes_in_use": peak}


def phase_train(arch, argv) -> dict:
    """(c) Governed training through the repro.launch.train entry point."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.registry import model_module
    from repro.launch import train as train_launcher

    m = train_launcher.main(["--arch", arch, *argv])
    cfg = get_config(arch)
    # train() initialises from PRNGKey(seed=0) and donates the initial
    # parameters; rebuild them to compare
    init, _ = model_module(cfg).init(jax.random.PRNGKey(0), cfg)
    moved = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b, np.float32))))
                for a, b in zip(jax.tree.leaves(init),
                                jax.tree.leaves(m["params"])))
    print(f"(c) {cfg.name}: losses {m['loss']!r}; step times "
          f"{m['step_time']!r} s (first includes compile); max |param "
          f"change| {moved!r}")
    if not all(math.isfinite(loss) for loss in m["loss"]):
        raise RuntimeError(f"non-finite loss {m['loss']}")
    if not moved > 0.0:
        raise RuntimeError("training did not change the parameters")
    return {"loss": m["loss"], "max_param_change": moved}


def phase_sharded(n_chips) -> dict:
    """--chips 4: the sharded paths against one device, on all chips."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.parallel.equivalence import check_sharded_equivalence

    if len(jax.devices()) != n_chips:
        raise RuntimeError(f"want {n_chips} chips, JAX sees "
                           f"{len(jax.devices())}")
    mesh = make_mesh((2, n_chips // 2), ("data", "model"),
                     devices=jax.devices())
    return check_sharded_equivalence(
        mesh, log=lambda s: print(f"(sharded {mesh.shape}) {s}"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"no repro package under {SRC}: run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's backend is {dev.platform!r}", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache}")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_microbench(**MICROBENCH)
        from repro.configs import get_config
        phase_serve(get_config(SERVE["arch"]), SERVE["batch"],
                    SERVE["prompt"], SERVE["new_tokens"], SERVE["requests"])
        phase_train(**TRAIN)
    print(f"all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
