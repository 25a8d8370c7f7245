"""``repro.obs`` — low-overhead structured span profiling for campaigns.

The package has two faces:

* an **ambient recording API** (this module): instrumentation sites call
  ``obs.span(...)`` / ``obs.event(...)`` / ``obs.ctx()`` unconditionally;
  when no recorder is installed these are near-free no-ops (one
  thread-local read), so profiling is off by default and the measurement
  hot paths are not perturbed.  ``install()`` activates a
  :class:`~repro.obs.spans.SpanRecorder` process-wide or — for the
  simulated cluster, whose "nodes" are threads of the driver process —
  thread-locally, where the thread-local recorder shadows the process
  default.
* an **analysis toolchain** (``tree``/``export``/``bridge``/``profile``):
  merge per-actor JSONL span files into one tree, walk the critical path,
  export Chrome ``trace_event`` JSON for Perfetto, and feed span-derived
  counters into the monitor's ``MetricsRegistry``.

``suppressed()`` masks recording on the current thread; the cluster node
uses it while uploading its own span file through the (instrumented)
store client, which would otherwise trace its own flushes forever.

Installing a recorder made with ``annotate=True`` also starts, once per
process, a listener on JAX's compile timings: each trace of a jitted
function to a jaxpr becomes an instant event ``jit.trace`` and each XLA
compilation (or load from the persistent cache) ``jit.compile``, with the
``seconds`` it took and the function's name ``fun``, on whatever recorder
is current when it happens.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

from repro.obs.spans import _AMBIENT, SpanRecorder, load_span_rows

#: public alias for the "inherit the ambient parent" sentinel — pass as
#: ``parent`` when a propagated context may be absent:
#: ``obs.span(..., parent=ctx or obs.AMBIENT)``
AMBIENT = _AMBIENT
from repro.obs.tree import (SpanNode, analyze, build_forest, critical_path,
                            self_time, walk)
from repro.obs.export import (to_trace_events, validate_trace_events,
                              write_trace_events)
from repro.obs.bridge import export_to_registry

__all__ = [
    "AMBIENT", "SpanRecorder", "SpanNode", "install", "uninstall", "current",
    "enabled", "span", "event", "ctx", "suppressed", "load_span_rows",
    "build_forest", "critical_path", "self_time", "walk", "analyze",
    "to_trace_events", "validate_trace_events", "write_trace_events",
    "export_to_registry",
]

_default: SpanRecorder | None = None
_tls = threading.local()
_compile_lock = threading.Lock()
_hearing_compiles = False

# JAX's compile timings (``jax.monitoring`` duration events) -> event name
_COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jit.trace",
                  "/jax/core/compile/backend_compile_duration": "jit.compile"}


class _Noop:
    """Reusable no-op context manager: ``with obs.span(...)`` when
    profiling is off costs two attribute lookups and no allocation."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP = _Noop()


def _on_compile(event: str, seconds: float, **kw) -> None:
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        return
    rec = current()
    if rec is not None:
        rec.event(name, "jit", seconds=seconds, fun=kw.get("fun_name"))


def _hear_compiles() -> None:
    global _hearing_compiles
    with _compile_lock:
        if not _hearing_compiles:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
            _hearing_compiles = True


def install(rec: SpanRecorder, *, thread_only: bool = False) -> SpanRecorder:
    """Make ``rec`` the ambient recorder — process-wide, or for this
    thread only (shadowing the process default)."""
    global _default
    if rec.annotate:
        _hear_compiles()
    if thread_only:
        _tls.rec = rec
    else:
        _default = rec
    return rec


def uninstall(*, thread_only: bool = False) -> None:
    global _default
    if thread_only:
        _tls.rec = None
    else:
        _default = None


def current() -> SpanRecorder | None:
    """The ambient recorder, or ``None`` when profiling is off or
    suppressed on this thread."""
    if getattr(_tls, "suppress", 0):
        return None
    rec = getattr(_tls, "rec", None)
    return rec if rec is not None else _default


def enabled() -> bool:
    return current() is not None


def span(name: str, cat: str, parent=_AMBIENT, **attrs):
    """Ambient lexical span; a shared no-op context manager when off."""
    rec = current()
    if rec is None:
        return _NOOP
    return rec.span(name, cat, parent, **attrs)


def event(name: str, cat: str, parent=_AMBIENT, **attrs) -> str | None:
    rec = current()
    if rec is None:
        return None
    return rec.event(name, cat, parent, **attrs)


def ctx() -> str | None:
    """Trace context (current span id) to propagate across task messages
    and node envelopes; ``None`` when profiling is off."""
    rec = current()
    return rec.ctx() if rec is not None else None


@contextmanager
def suppressed():
    """Mask recording on this thread (anti-self-tracing guard)."""
    _tls.suppress = getattr(_tls, "suppress", 0) + 1
    try:
        yield
    finally:
        _tls.suppress -= 1
