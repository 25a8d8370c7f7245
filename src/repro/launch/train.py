"""Training launcher:  PYTHONPATH=src python -m repro.launch.train \
    --arch llama3-8b --smoke --steps 50 [--governor a100]"""
from __future__ import annotations

import argparse

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import ShapeSpec
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.parallel.sharding import make_env
from repro.runtime.train_loop import TrainConfig, train


def main(argv=None) -> dict:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--governor", choices=("a100", "gh200", "rtx6000"),
                    default=None)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    mesh = make_smoke_mesh() if args.smoke else None
    env = make_env(cfg, mesh)

    governor = device = regions = None
    if args.governor:
        from repro.core.latest import run_latest, LatestConfig
        from repro.core.evaluation import MeasureConfig
        from repro.dvfs import make_device, PowerModel
        from repro.dvfs.governor import Governor
        from repro.dvfs.planner import Region
        device = make_device(args.governor, seed=0, n_cores=8)
        freqs = list(device.cfg.frequencies[:: max(1, len(device.cfg.frequencies) // 4)])[:4]
        table = run_latest(device, freqs, LatestConfig(
            measure=MeasureConfig(min_measurements=5, max_measurements=5)))
        governor = Governor(table, PowerModel(f_max_mhz=max(freqs)), freqs)
        regions = [Region("compute", 0.5), Region("collective", 0.2),
                   Region("host", 0.05)]

    tc = TrainConfig(steps=args.steps, lr=args.lr,
                     microbatches=args.microbatches,
                     checkpoint_dir=args.ckpt_dir)
    m = train(cfg, shape, env, tc, governor=governor, device=device,
              regions=regions)
    print(f"final loss: {m['loss'][-1]:.4f}  "
          f"mean step: {sum(m['step_time'])/len(m['step_time'])*1e3:.0f} ms")
    if m["governor"]:
        print("governor:", m["governor"])
    return m


if __name__ == "__main__":
    main()
