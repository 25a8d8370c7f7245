"""Fault tolerance: heartbeats, straggler detection/mitigation, elastic
re-mesh, and a retrying step executor.

On a real multi-pod job these hooks bind to the cluster control plane; here
they are exercised against simulated failure injectors (tests) and drive
the campaign work-queue scheduler (:mod:`repro.campaign.workqueue`) with
the same interfaces:

  HeartbeatMonitor   per-worker liveness from step-completion stamps;
                     a worker silent for > timeout is declared dead ->
                     the driver requeues its in-flight work (campaign
                     scheduler) or triggers elastic_remesh + checkpoint
                     restore (training loops)
  StragglerPolicy    EWMA of per-step durations; a step slower than
                     ratio x EWMA marks the step degraded; after `budget`
                     consecutive degraded steps the driver requests the
                     slow worker's eviction.  Also tracks *in-flight* task
                     elapsed time so schedulers can speculatively
                     re-dispatch a straggling task before it finishes
  retry_step         transient-failure wrapper (preemption, ICI hiccup):
                     re-executes a pure step function; correctness is free
                     because steps are pure (params, opt, batch) -> ...
  elastic_remesh     rebuild the mesh from the surviving device list and
                     recompute shardings (restore re-shards the state)

All timeout logic runs on an injected clock, ``time.monotonic`` by
default — never wall-clock time, which steps under NTP adjustments and
would spuriously kill (or revive) workers.  Tests inject a fake clock.
"""
from __future__ import annotations

import dataclasses
import time


class HeartbeatMonitor:
    """Liveness from step-completion stamps on an injected monotonic clock.

    Workers are registered up front (``workers`` may be a count or an
    iterable of ids) or dynamically via :meth:`register` — the campaign
    scheduler registers replacements as it respawns crashed processes.
    A worker reaped with :meth:`remove` stays gone: a late ``beat`` from a
    process that was already declared dead is dropped, not resurrected
    (the driver already requeued its work; letting the zombie re-register
    would double-account it).
    """

    def __init__(self, workers=0, timeout_s: float = 60.0,
                 clock=time.monotonic):
        if timeout_s <= 0:
            raise ValueError(f"timeout_s must be positive, got {timeout_s}")
        self.timeout = timeout_s
        self.clock = clock
        ids = range(workers) if isinstance(workers, int) else workers
        now = clock()
        self.last = {w: now for w in ids}

    def register(self, worker) -> None:
        """Start (or restart) tracking ``worker`` from now."""
        self.last[worker] = self.clock()

    def remove(self, worker) -> None:
        """Stop tracking ``worker`` (reaped or evicted); idempotent."""
        self.last.pop(worker, None)

    def beat(self, worker, t: float | None = None) -> None:
        """Record a liveness stamp.  Beats from unknown (never-registered
        or already-removed) workers are ignored — see class docstring."""
        if worker not in self.last:
            return
        self.last[worker] = self.clock() if t is None else t

    def dead(self, now: float | None = None) -> list:
        """Workers silent for longer than the timeout ([] when none are
        tracked)."""
        now = self.clock() if now is None else now
        return [w for w, t in self.last.items() if now - t > self.timeout]


@dataclasses.dataclass
class StragglerPolicy:
    """EWMA straggler detection over an injected monotonic clock.

    Two usage shapes, sharing one EWMA:

    * post-hoc: :meth:`observe` a completed step duration -> ok | degraded
      | evict (consecutive-degraded budget);
    * in-flight: :meth:`start`/:meth:`finish` bracket a task; while it
      runs, :meth:`straggling` compares its elapsed time against
      ratio x EWMA so a scheduler can speculatively re-dispatch it.
    """

    ratio: float = 1.8          # step slower than ratio x EWMA = degraded
    alpha: float = 0.2
    budget: int = 5             # consecutive degraded steps before eviction
    clock: object = time.monotonic
    _ewma: float = 0.0
    _degraded: int = 0
    _started: dict = dataclasses.field(default_factory=dict)

    @property
    def ewma(self) -> float:
        """Current healthy-step EWMA (0 until the first observation)."""
        return self._ewma

    def observe(self, step_time_s: float) -> str:
        """Returns ok | degraded | evict."""
        if self._ewma == 0.0:
            self._ewma = step_time_s
            return "ok"
        verdict = "ok"
        if step_time_s > self.ratio * self._ewma:
            self._degraded += 1
            verdict = "evict" if self._degraded >= self.budget else "degraded"
        else:
            self._degraded = 0
            # only fold healthy steps into the EWMA (stragglers would poison it)
            self._ewma = (1 - self.alpha) * self._ewma + self.alpha * step_time_s
        return verdict

    # ---------------- in-flight tracking ---------------- #
    def start(self, task) -> None:
        """Stamp ``task`` as started now (idempotent per task: a
        speculative duplicate does not reset the original's clock)."""
        self._started.setdefault(task, self.clock())

    def elapsed(self, task) -> float:
        """Seconds since :meth:`start` (0.0 for unknown tasks)."""
        t0 = self._started.get(task)
        return 0.0 if t0 is None else self.clock() - t0

    def straggling(self, task) -> bool:
        """True when ``task`` has been in flight longer than
        ratio x EWMA (never before the first completed observation —
        with no baseline there is nothing to call slow)."""
        return self._ewma > 0.0 and self.elapsed(task) > self.ratio * self._ewma

    def finish(self, task) -> str:
        """Complete ``task``: fold its duration into :meth:`observe` and
        stop tracking it.  Unknown tasks return "ok" untracked."""
        t0 = self._started.pop(task, None)
        if t0 is None:
            return "ok"
        return self.observe(self.clock() - t0)

    def abandon(self, task) -> None:
        """Drop an in-flight task without observing it (its host died —
        the wall time says nothing about step cost); idempotent."""
        self._started.pop(task, None)


def retry_step(fn, *args, retries: int = 3, on_error=None):
    """Call ``fn(*args)`` up to ``retries`` times; raise the FIRST error
    when every attempt fails.  A step jitted with donated inputs (the
    training step) loses them on its first failed attempt, so the later
    attempts fail only on deleted buffers, and their errors would hide the
    cause."""
    first = None
    for i in range(retries):
        try:
            return fn(*args)
        except Exception as e:      # noqa: BLE001 — deliberate catch-all boundary
            if first is None:
                first = e
            if on_error is not None:
                on_error(i, e)
    raise first


def elastic_remesh(devices=None, *, axis_names=("data", "model")):
    """Rebuild the largest usable mesh from the surviving devices.

    Keeps the model axis as large as possible (TP degree preserved) and
    shrinks the data axis; returns (mesh, dropped_devices).

    JAX is imported lazily: everything else in this module is pure-Python
    bookkeeping that campaign worker processes import on spawn, and they
    must not pay (or depend on) the JAX runtime.
    """
    import jax
    import numpy as np
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    tp = 1
    # largest power-of-two TP that divides the survivor count
    for cand in (16, 8, 4, 2, 1):
        if n % cand == 0:
            tp = cand
            break
    dp = n // tp
    used = devices[: dp * tp]
    mesh = jax.sharding.Mesh(
        np.array(used).reshape(dp, tp), axis_names)
    return mesh, devices[dp * tp:]
