"""End-to-end driver: train a ~small LM for a few hundred steps with the
energy-aware DVFS governor planning frequencies from a MEASURED latency
table (the paper's §VIII runtime, integrated with the training loop).

  PYTHONPATH=src python examples/train_energy_aware.py [--steps 200]
"""
import argparse

from repro.backends import create_backend
from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.core.evaluation import MeasureConfig
from repro.core.paths import results_dir
from repro.core.session import (LatestConfig, MeasurementSession,
                                SessionConfig)
from repro.dvfs.governor import Governor, oblivious_governor_sim, static_sim
from repro.dvfs.planner import Region
from repro.parallel.sharding import make_env
from repro.runtime.train_loop import TrainConfig, train

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--device", choices=("a100", "gh200", "rtx6000"),
                default="a100")
args = ap.parse_args()

# 1) measure the accelerator's switching latency (paper pipeline) through
#    the session API, then 2) derive the governor straight from the session
print(f"== measuring switching latency ({args.device}-like simulator) ==")
device = create_backend("vmapped-sim", kind=args.device, seed=0, n_cores=8)
fs = device.frequencies
freqs = [float(fs[i]) for i in (0, len(fs) // 2, -1)]
session = MeasurementSession(
    device, freqs,
    SessionConfig(latest=LatestConfig(
        measure=MeasureConfig(min_measurements=6, max_measurements=10,
                              rse_check_every=6))),
    device_name=args.device)
governor = Governor.from_session(session, verbose=True)
table = governor.table
power = governor.power
regions = [Region("compute", 0.25), Region("memory", 0.05),
           Region("collective", 0.08), Region("host", 0.01)]

# 3) train a ~100M-scale (smoke-config) llama with governor hooks
print(f"\n== training with energy-aware governor ({args.steps} steps) ==")
cfg = get_config("llama3-8b", smoke=True)
shape = ShapeSpec("train", 64, 8, "train")
env = make_env(cfg, None)
metrics = train(cfg, shape, env,
                TrainConfig(steps=args.steps, lr=1e-3,
                            log_every=25,
                            checkpoint_dir=results_dir("ckpt_energy_aware"),
                            checkpoint_every=100),
                governor=governor, device=device, regions=regions)

print(f"\nfinal loss {metrics['loss'][-1]:.4f} "
      f"(start {metrics['loss'][0]:.4f})")

# 4) energy accounting: aware vs oblivious vs static
stream = regions * args.steps
aware = metrics["governor"]
obliv = oblivious_governor_sim(table, power, freqs, stream)
stat = static_sim(power, freqs, stream)
print("\n== energy accounting over the training run ==")
print(f"  static f_max : {stat.energy_j/1e3:8.2f} kJ  {stat.time_s:7.1f} s")
print(f"  oblivious    : {obliv.energy_j/1e3:8.2f} kJ  {obliv.time_s:7.1f} s"
      f"  (switch overhead {obliv.switch_overhead_s:.1f} s)")
print(f"  latency-aware: {aware.energy_j/1e3:8.2f} kJ  {aware.time_s:7.1f} s"
      f"  (switch overhead {aware.switch_overhead_s:.1f} s, "
      f"{aware.suppressed_short} switches suppressed)")
print(f"  energy saved vs static: {1-aware.energy_j/stat.energy_j:.1%} at "
      f"{aware.time_s/stat.time_s-1:+.1%} runtime")
