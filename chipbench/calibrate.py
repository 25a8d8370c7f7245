"""Readings that the limits of ``correct`` are set from, on the chip, at a
cell's own size; not part of the benchmark's runs.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds S] [--out FILE]

For each seed, in one process: the program's reading (a short window at the
cell's own load, then the same comparison a run makes).  For each control
seed also the control's reading, the plain reference in float8 (e4m3, one
scale per tensor) put in the program's place, and the planted faults':

- serving: the widest gap of the tokens the float8 reference puts first,
  and of the served tokens with one token per request altered;
- training: the float8 reference's three steps, and the reference's steps
  with half of each batch left out (the mean taken over the rest), each
  compared with the float32 reference as the program is.

Prints one JSON object of readings as its last line.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def serve_readings(drv, cell, seed, seconds, devices, control):
    import numpy as np

    result, checks, extra = drv.run(cell, seed, seconds, False, time.perf_counter(),
                                    devices)
    out = {"program": checks["max_logit_gap"]["value"],
           "attempted": result["attempted"]}
    if control:
        reqs = extra["sample"]
        params = extra["session"].init(extra["session"].key)
        out["control_fp8"] = drv.widest_gap(cell.cmod, cell.spec, params, reqs,
                                            q="fp8")
        rng = np.random.default_rng([seed, 9])
        altered = []
        for prompt, served in reqs:
            served = served.copy()
            i = rng.integers(len(served))
            served[i] = (served[i] + 1 + rng.integers(cell.spec["vocab"] - 1)) \
                % cell.spec["vocab"]
            altered.append((prompt, served))
        out["fault_token_altered"] = drv.widest_gap(cell.cmod, cell.spec, params,
                                                    altered)
        del params
    return out


def train_readings(drv, cell, seed, devices, control):
    import jax

    import generator
    import harness

    result, checks, extra = drv.run(cell, seed, 0.0, False, time.perf_counter(),
                                    devices)
    out = {"program": {k: v["value"] for k, v in checks.items()}}
    if control:
        mix = cell.traffic
        traffic = generator.make(mix, cell.spec["vocab"], seed)
        key = jax.random.PRNGKey(harness.init_seed(seed))
        ref = extra["reference"]
        n = mix["checked_steps"]
        fp8 = drv.reference_steps(cell.cmod, cell.spec, mix["optimizer"], key,
                                  traffic, n, q="fp8")
        out["control_fp8"] = drv.compare(fp8, ref)
        half = drv.reference_steps(cell.cmod, cell.spec, mix["optimizer"], key,
                                   traffic, n, rows=mix["batch"] // 2)
        out["fault_half_batch"] = drv.compare(half, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import harness

    cell = harness.cell(args.workload)
    harness.use_compile_cache()
    devices = harness.require_devices(cell.chips)
    drv = harness.load_module(HERE / f"drive_{cell.traffic['kind']}.py")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    readings = {}
    for seed in seeds + sorted(controls - set(seeds)):
        t = time.perf_counter()
        if cell.traffic["kind"] == "serve":
            r = serve_readings(drv, cell, seed, args.seconds, devices,
                               seed in controls)
        else:
            r = train_readings(drv, cell, seed, devices, seed in controls)
        r["seconds"] = time.perf_counter() - t
        readings[str(seed)] = r
        print(f"seed {seed}: {json.dumps(r)}", file=sys.stderr, flush=True)
    line = json.dumps({"workload": args.workload, "readings": readings})
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
