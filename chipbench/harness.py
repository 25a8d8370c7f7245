"""What every cell shares: finding a cell's files by name, the device
check, the peaks table, host spans, the governor proxy and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its configuration
is ``configs/<config>.json`` (sizes) beside ``configs/<config>.py`` (seeded
weights, plain reference, operation counts); its traffic is
``traffic/<traffic>.json``; its correctness limits are ``limits/<cell>.json``;
each per-layer metric is read by ``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib.util
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def now() -> float:
    return time.perf_counter()


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    name = "chipbench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(HERE)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict             # the configuration's sizes
    traffic: dict          # the mix's parameters
    limits: dict           # {number compared: limit}
    end_to_end: list       # metric entries this cell reports
    per_layer: list
    config: str

    @property
    def cmod(self):
        return load_module(HERE / "configs" / f"{self.config}.py")


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or read_json(ROOT / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=w["chips"],
                spec=read_json(HERE / "configs" / f"{w['config']}.json"),
                traffic=read_json(HERE / "traffic" / f"{w['traffic']}.json"),
                limits=read_json(HERE / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, config=w["config"])


def peaks(device_kind: str) -> dict:
    table = read_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def require_devices(chips: int):
    """The accelerator devices to run on; raises :class:`NoDevice` when JAX
    finds no TPU or fewer than ``chips``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX's backend is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoDevice(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached however quick its compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def init_seed(seed: int) -> int:
    """31-bit seed for the program's own PRNG keys, derived from ``--seed``."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") >> 1


def program_config(spec: dict):
    """The program's configuration named by ``spec['arch']``; refuses one
    whose sizes differ from ``spec``."""
    import jax.numpy as jnp
    from repro.configs import get_config

    cfg = get_config(spec["arch"])
    diffs = []
    for key, want in spec.items():
        if key in ("name", "source") or not hasattr(cfg, key):
            continue
        have = getattr(cfg, key)
        if dataclasses.is_dataclass(have):
            have = dataclasses.asdict(have)
        elif key.endswith("dtype"):
            have = jnp.dtype(have).name
        if have != want:
            diffs.append(f"{key}: program {have!r}, benchmark {want!r}")
    if diffs:
        raise ValueError(f"{spec['arch']} differs from its benchmark "
                         f"configuration: {'; '.join(diffs)}")
    return cfg


# --------------------------------------------------------------------------- #
# host spans
# --------------------------------------------------------------------------- #
class Spans:
    """Host spans around the calls into each layer: a profiler annotation
    (so a trace can name idle gaps) and a host-clock record."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    def __call__(self, name: str):
        return _Span(self, name)

    def durations(self, name: str, lo: float = float("-inf"),
                  hi: float = float("inf")) -> list[float]:
        return [b - a for n, a, b in self.records if n == name and lo <= a < hi]


class _Span:
    def __init__(self, owner: Spans, name: str):
        import jax

        self.owner, self.name = owner, name
        self.ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = now()
        return self

    def __exit__(self, *exc):
        self.t1 = now()
        self.ann.__exit__(*exc)
        self.owner.records.append((self.name, self.t0, self.t1))
        return False


class GcClock:
    """Host seconds and collections of Python's garbage collector while
    entered: a diagnostic of stalls in the window, not a metric."""

    def __init__(self):
        self.seconds, self.collections, self._t = 0.0, [0, 0, 0], None

    def _callback(self, phase, info):
        if phase == "start":
            self._t = now()
        elif self._t is not None:
            self.seconds += now() - self._t
            self.collections[info["generation"]] += 1
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)
        return False


class PlanTimer:
    """Stands in for the governor: times and annotates each ``plan`` call,
    then delegates.  Everything else passes through."""

    def __init__(self, governor, spans: Spans):
        self._gov, self._spans = governor, spans

    def plan(self, region, device=None):
        with self._spans("cb.plan"):
            return self._gov.plan(region, device)

    def __getattr__(self, name):
        return getattr(self._gov, name)


def build_serve_governor(kind: str):
    """The governor the serving smoke run builds: measured on the
    ``vmapped-sim`` backend at the lowest, middle and highest clock."""
    from repro.backends import create_backend
    from repro.core.evaluation import MeasureConfig
    from repro.core.session import (LatestConfig, MeasurementSession,
                                    SessionConfig)
    from repro.dvfs.governor import Governor

    device = create_backend("vmapped-sim", kind=kind, seed=0, n_cores=8)
    fs = device.frequencies
    freqs = [float(fs[i]) for i in (0, len(fs) // 2, -1)]
    session = MeasurementSession(
        device, freqs,
        SessionConfig(latest=LatestConfig(measure=MeasureConfig(
            min_measurements=6, max_measurements=10, rse_check_every=6))),
        device_name=kind)
    return Governor.from_session(session), device


def build_train_governor(kind: str):
    """Governor, device and regions as ``repro.launch.train --governor``
    builds them."""
    from repro.core.evaluation import MeasureConfig
    from repro.core.latest import LatestConfig, run_latest
    from repro.dvfs import PowerModel, make_device
    from repro.dvfs.governor import Governor
    from repro.dvfs.planner import Region

    device = make_device(kind, seed=0, n_cores=8)
    fr = device.cfg.frequencies
    freqs = list(fr[:: max(1, len(fr) // 4)])[:4]
    table = run_latest(device, freqs, LatestConfig(
        measure=MeasureConfig(min_measurements=5, max_measurements=5)))
    governor = Governor(table, PowerModel(f_max_mhz=max(freqs)), freqs)
    regions = [Region("compute", 0.5), Region("collective", 0.2),
               Region("host", 0.05)]
    return governor, device, regions


# --------------------------------------------------------------------------- #
# tracing and the result line
# --------------------------------------------------------------------------- #
class Profiler:
    """``jax.profiler`` over the window, written under TMPDIR and reduced
    by ``trace_reduce`` once stopped."""

    def __init__(self, on: bool):
        self.on, self.dir, self.reduced = on, None, None

    def start(self):
        if self.on:
            import tempfile

            import jax
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.dir)

    def stop(self):
        if self.on and self.dir is not None:
            import shutil

            import jax
            import trace_reduce
            jax.profiler.stop_trace()
            try:
                self.reduced = trace_reduce.reduce(trace_reduce.load(self.dir))
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)
                self.dir = None


def read_per_layer(cell: Cell, run) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader that
    finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device",
               "breakdown")


def result_line(result: dict, checks: dict) -> dict:
    """The object of the last line: the result's keys that the line carries
    (``breakdown`` only in a traced run), then the numbers compared, each
    with its limit, under ``checks``, last."""
    line = {k: result[k] for k in RESULT_KEYS if k in result}
    line["checks"] = {name: {"value": c["value"], "limit": c["limit"]}
                      for name, c in checks.items()}
    return line


def emit(result: dict, checks: dict, timing: dict) -> None:
    """Print the run's timing diagnostics as an earlier line of stdout, the
    compared numbers beside their limits as the last lines of stderr, and
    the result line as the last line of stdout."""
    print(json.dumps({"timing": timing}), flush=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result_line(result, checks)), flush=True)
