"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout: the system under test is the ``repro``
package under ``src/``.  The cell, its configuration, traffic, limits and
per-layer metrics are found by name from ``BENCHMARK.json`` (see
``harness.py``); the traffic's ``kind`` names the module that drives it,
``drive_<kind>.py``.  Set-up (weights from the seed, the governor, every
shape the traffic uses, compiled or loaded from ``.jax_cache/``) comes
first and is reported as ``setup_s``; then the window runs for
``--seconds``; then the plain reference decides ``correct``.  With
``--trace 1`` the window is traced and the per-layer metrics are printed in
place of the end-to-end ones.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no repro package under {ROOT / 'src'}: run from a checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import harness

    cell = harness.cell(args.workload)
    harness.use_compile_cache()
    marks = {"jax_s": harness.now() - T0}      # JAX imported
    try:
        devices = harness.require_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    marks["devices_s"] = harness.now() - T0    # the chip found
    harness.peaks(devices[0].device_kind)
    drive = harness.load_module(HERE / f"drive_{cell.traffic['kind']}.py")
    marks["imports_s"] = harness.now() - T0    # the program imported
    result, checks, extra = drive.run(cell, args.seed, args.seconds,
                                      bool(args.trace), T0, devices)
    harness.emit(result, checks, {"marks": marks, **extra["timing"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
