"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is first brought to one plain form, :func:`load`'s dict::

    {"device": {"<plane>": {"ops": [[name, start_ns, dur_ns], ...],
                            "modules": [[name, start_ns, dur_ns], ...]}},
     "spans": [[name, start_ns, dur_ns], ...]}

``device`` holds each accelerator's XLA operations and XLA programs
("modules"); ``spans`` the benchmark's own host annotations (names
starting ``cb.``).  All times share the profiler's clock.  The functions
below work on that form only, so a small recorded trace checks them.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

SPAN_PREFIX = "cb."
WINDOW_SPAN = "cb.window"


# --------------------------------------------------------------------------- #
# loading
# --------------------------------------------------------------------------- #
def _events(line):
    return [[e.name, float(e.start_ns), float(e.duration_ns)]
            for e in line.events]


def from_xplane(path: str) -> dict:
    """Read a ``.xplane.pb`` written by ``jax.profiler`` into the plain form."""
    from jax.profiler import ProfileData

    out = {"device": {}, "spans": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops")
            mods = lines.get("XLA Modules")
            if ops is None and mods is None:
                continue
            out["device"][plane.name] = {
                "ops": _events(ops) if ops is not None else _events(mods),
                "modules": _events(mods) if mods is not None else []}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                out["spans"] += [e for e in _events(ln)
                                 if e[0].startswith(SPAN_PREFIX)]
    return out


def load(path: str) -> dict:
    """A trace directory (newest ``.xplane.pb`` under it) or the plain form
    as ``.json`` / ``.json.gz``."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        return from_xplane(found[-1])
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


# --------------------------------------------------------------------------- #
# reduction
# --------------------------------------------------------------------------- #
def window(trace: dict) -> tuple[float, float]:
    """[start, end] ns of the measured window: the ``cb.window`` span."""
    spans = [s for s in trace["spans"] if s[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace holds no cb.window span")
    s = max(spans, key=lambda e: e[2])
    return s[1], s[1] + s[2]


def merge(intervals):
    """Union of [start, end] intervals, sorted and disjoint."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy(events, lo, hi):
    """Merged intervals in which some event of ``events`` ran, clipped to
    [lo, hi]."""
    clipped = [[max(s, lo), min(s + d, hi)] for _, s, d in events
               if s < hi and s + d > lo]
    return merge(clipped)


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def stable_name(name: str) -> str:
    """Program name without the id XLA appends: ``jit__prefill(12)`` ->
    ``jit__prefill``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_name(name: str) -> str:
    """Operation name without the HLO text a TPU trace appends:
    ``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return re.match(r"%?([^\s=(]*)", name).group(1) or name


def time_by_name(events, lo, hi, key=lambda n: n) -> dict[str, list[float]]:
    """{name: [seconds, count]} of events that start inside [lo, hi]."""
    out: dict[str, list[float]] = {}
    for name, s, d in events:
        if lo <= s < hi:
            acc = out.setdefault(key(name), [0.0, 0])
            acc[0] += d * 1e-9
            acc[1] += 1
    return out


def idle_gaps(busy_iv, spans, lo, hi) -> list[tuple[str, float, float]]:
    """Gaps of the window with no device work, each named by the innermost
    ``cb.`` host span covering its midpoint (``cb.window`` when no other
    does): [(span name, start ns, seconds)]."""
    gaps, t = [], lo
    for a, b in busy_iv:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [s for s in inner if s[1] <= mid <= s[1] + s[2]]
        name = max(cover, key=lambda s: s[1])[0] if cover else WINDOW_SPAN
        out.append((name, a, (b - a) * 1e-9))
    return out


def reduce(trace: dict) -> dict:
    """Busy and window seconds averaged over the devices, program and
    operation times, and the breakdown lists of the result line."""
    lo, hi = window(trace)
    devices = trace["device"]
    if not devices:
        raise ValueError("trace holds no device plane")
    busy_s, ops, modules, gaps = 0.0, {}, {}, {}
    for dev in devices.values():
        iv = busy(dev["ops"], lo, hi)
        busy_s += total(iv) * 1e-9
        for name, (sec, n) in time_by_name(dev["ops"], lo, hi, op_name).items():
            acc = ops.setdefault(name, [0.0, 0])
            acc[0] += sec
            acc[1] += n
        mods = dev["modules"] or dev["ops"]
        for name, (sec, n) in time_by_name(mods, lo, hi, stable_name).items():
            acc = modules.setdefault(name, [0.0, 0])
            acc[0] += sec
            acc[1] += n
        for name, _, sec in idle_gaps(iv, trace["spans"], lo, hi):
            gaps[name] = gaps.get(name, 0.0) + sec
    n = len(devices)
    top = lambda d, k: sorted(([nm, v[0] if isinstance(v, list) else v]
                               for nm, v in d.items()),
                              key=lambda e: -e[1])[:k]
    return {
        "busy_s": busy_s / n,
        "window_s": (hi - lo) * 1e-9,
        "modules": {k: [v[0] / n, v[1] / n] for k, v in modules.items()},
        "breakdown": {"device_ops": top({k: v[0] / n for k, v in ops.items()}, 10),
                      "idle_gaps": top({k: v / n for k, v in gaps.items()}, 10)},
    }


def module_seconds(reduced: dict, prefix: str) -> tuple[float, float]:
    """(seconds, calls) of the programs whose stable name starts with
    ``prefix``."""
    sec = calls = 0.0
    for name, (s, n) in reduced["modules"].items():
        if name.startswith(prefix):
            sec += s
            calls += n
    return sec, calls
