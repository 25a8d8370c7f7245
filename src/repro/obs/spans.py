"""Structured span recorder for orchestration profiling.

A :class:`SpanRecorder` captures *spans* (named wall-clock intervals with a
parent link and a small attribute dict) and *instant events* into a chunked
append-only arena — the same growth discipline as ``trace.recorder``'s
columnar ``_Arena``, scaled down to orchestration rates (hundreds of spans
per campaign, not millions of samples).  Rows drain to an append-only JSONL
file per process/actor so a crash loses at most one unflushed chunk and
files from different actors merge by concatenation.

Span ids are ``"<actor>:<seq>"`` and are globally unique as long as actor
names are (the campaign layer names actors ``driver``, ``worker<N>``,
``node-<id>``).  Parent links may cross actors: the driver propagates its
active span id ("trace context") inside task messages and node envelopes,
and the receiving side opens its spans with ``parent=ctx`` so the merged
rows stitch into one tree.

Clocks: all timestamps are absolute wall seconds from a shared epoch
(``time.time() - time.perf_counter()`` captured once per recorder), so rows
recorded by different processes on one host line up to clock-sync error.
Tests inject a deterministic ``clock`` callable instead.

Recording is allocation-light but not free; the ambient helpers in
``repro.obs`` are the zero-cost path when profiling is off.

``annotate=True`` also writes every span and event into ``jax.profiler``'s
host trace, as a ``TraceAnnotation`` of the same name, so a profile of the
device names what the host was doing on the device's own clock.  Only that
path imports JAX.
"""
from __future__ import annotations

import json
import os
import threading
import time

_CHUNK = 512

_AMBIENT = object()  # sentinel: "parent = whatever span is on this thread"


class _Arena:
    """Fixed-size-chunk append arena.  Rows land in a preallocated chunk;
    full chunks are sealed and new ones opened, so steady-state appends
    never resize a list the interpreter has to copy."""

    __slots__ = ("_sealed", "_chunk", "_fill")

    def __init__(self):
        self._sealed: list[list] = []
        self._chunk: list = [None] * _CHUNK
        self._fill = 0

    def append(self, row) -> None:
        self._chunk[self._fill] = row
        self._fill += 1
        if self._fill == _CHUNK:
            self._sealed.append(self._chunk)
            self._chunk = [None] * _CHUNK
            self._fill = 0

    def __len__(self) -> int:
        return len(self._sealed) * _CHUNK + self._fill

    def drain(self) -> list:
        out: list = []
        for chunk in self._sealed:
            out.extend(chunk)
        out.extend(self._chunk[: self._fill])
        self._sealed = []
        self._fill = 0
        return out


class _LiveSpan:
    """Handle for an open span.  ``attrs`` may be mutated while the span is
    open (e.g. a store op sets its final ``attempts`` count just before the
    span closes); the dict is serialized at ``end`` time."""

    __slots__ = ("sid", "parent", "name", "cat", "t0", "tid", "attrs")

    def __init__(self, sid, parent, name, cat, t0, tid, attrs):
        self.sid = sid
        self.parent = parent
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.tid = tid
        self.attrs = attrs


class _SpanCtx:
    """Lexical ``with`` wrapper around begin/end that maintains the
    per-thread ambient parent stack (and, on an annotating recorder, holds
    the span's profiler annotation open)."""

    __slots__ = ("_rec", "_live", "_ann")

    def __init__(self, rec, live):
        self._rec = rec
        self._live = live
        self._ann = None if rec._annotation is None else rec._annotation(live.name)

    def __enter__(self) -> _LiveSpan:
        self._rec._stack().append(self._live.sid)
        if self._ann is not None:
            self._ann.__enter__()
        return self._live

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._rec._stack()
        if stack and stack[-1] == self._live.sid:
            stack.pop()
        if exc_type is not None:
            self._live.attrs["error"] = exc_type.__name__
        self._rec.end(self._live)
        return False


class SpanRecorder:
    """Append-only span/event recorder for one actor (process or thread).

    Thread-safe: node threads in the simulated cluster share the driver
    process, so each installs its own recorder thread-locally, but a single
    recorder also tolerates concurrent use (the arena and seq counter are
    lock-protected; parent stacks are per-thread).
    """

    def __init__(self, actor: str, path: str | None = None, *,
                 clock=None, flush_every: int = _CHUNK, annotate: bool = False):
        self.actor = str(actor)
        self.path = path
        self.annotate = bool(annotate)
        self._annotation = None
        if self.annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        if clock is None:
            epoch = time.time() - time.perf_counter()
            clock = lambda: epoch + time.perf_counter()  # noqa: E731
        self._clock = clock
        self._arena = _Arena()
        self._flushed: list[dict] = []
        self._lock = threading.Lock()
        self._seq = 0
        self._flush_every = int(flush_every)
        self._tids: dict[int, int] = {}
        self._local = threading.local()
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

    # -- internals ---------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _next_sid(self) -> str:
        with self._lock:
            self._seq += 1
            return f"{self.actor}:{self._seq}"

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids) + 1)
        return tid

    def _resolve_parent(self, parent):
        if parent is _AMBIENT:
            stack = self._stack()
            return stack[-1] if stack else None
        return parent

    def _append(self, row: dict) -> None:
        with self._lock:
            self._arena.append(row)
            full = len(self._arena) >= self._flush_every
        if full:
            self.flush()

    # -- recording API -----------------------------------------------------

    def now(self) -> float:
        return float(self._clock())

    def span(self, name: str, cat: str, parent=_AMBIENT, **attrs) -> _SpanCtx:
        """Lexical span: ``with rec.span("unit.exec", "exec", unit=key):``."""
        return _SpanCtx(self, self.begin(name, cat, parent, **attrs))

    def begin(self, name: str, cat: str, parent=_AMBIENT, **attrs) -> _LiveSpan:
        """Open a non-lexical span (e.g. a unit attempt that outlives the
        scheduler loop iteration that dispatched it).  Does NOT touch the
        ambient parent stack; pair with :meth:`end`."""
        return _LiveSpan(self._next_sid(), self._resolve_parent(parent),
                         name, cat, self.now(), self._tid(), dict(attrs))

    def end(self, live: _LiveSpan, **attrs) -> str:
        if attrs:
            live.attrs.update(attrs)
        row = {"sid": live.sid, "parent": live.parent, "actor": self.actor,
               "name": live.name, "cat": live.cat, "ph": "X",
               "tid": live.tid, "t0": live.t0, "t1": self.now()}
        if live.attrs:
            row["attrs"] = live.attrs
        self._append(row)
        return live.sid

    def event(self, name: str, cat: str, parent=_AMBIENT, **attrs) -> str:
        """Instant event (zero-duration point on the timeline)."""
        if self._annotation is not None:
            with self._annotation(name):
                pass
        sid = self._next_sid()
        t = self.now()
        row = {"sid": sid, "parent": self._resolve_parent(parent),
               "actor": self.actor, "name": name, "cat": cat, "ph": "i",
               "tid": self._tid(), "t0": t, "t1": t}
        if attrs:
            row["attrs"] = attrs
        self._append(row)
        return sid

    def ctx(self) -> str | None:
        """Current span id on this thread — the trace context to propagate
        into task messages / node envelopes."""
        stack = self._stack()
        return stack[-1] if stack else None

    # -- draining ----------------------------------------------------------

    def flush(self) -> None:
        """Drain the arena: append to the JSONL file (if any) and keep an
        in-memory copy for same-process analysis."""
        with self._lock:
            rows = self._arena.drain()
            if not rows:
                return
            self._flushed.extend(rows)
            if self.path:
                with open(self.path, "a") as f:
                    for row in rows:
                        f.write(json.dumps(row, separators=(",", ":")))
                        f.write("\n")

    def rows(self) -> list[dict]:
        """All recorded rows (flushes first)."""
        self.flush()
        with self._lock:
            return list(self._flushed)

    def close(self) -> None:
        self.flush()


def load_span_rows(path: str) -> list[dict]:
    """Read one actor's JSONL span file; tolerates a torn final line (the
    actor may have crashed mid-append — that is exactly when profiles are
    most interesting)."""
    rows: list[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return rows
