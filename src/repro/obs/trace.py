"""Structured tracing: nested spans over a ring buffer, JSONL + Chrome export,
and the same spans on the JAX profiler's timeline.

A *span* is one named, timed region with attributes -- ``batcher.dispatch``,
``search.chunk``, ``xla.dispatch`` -- whose duration comes from the
monotonic ``time.perf_counter_ns`` (immune to wall-clock jumps).  Spans nest
per thread (a thread-local stack tracks depth and parent) and land in:

  * an in-memory ring buffer (``collections.deque(maxlen=...)`` -- bounded,
    allocation-cheap, safe to leave on for long service runs);
  * optionally a JSONL trace file, one JSON object per finished span,
    appended under a lock (multi-thread safe);
  * on demand, a Chrome-trace JSON export loadable in ``chrome://tracing``
    or https://ui.perfetto.dev (``ph: "X"`` complete events);
  * the JAX profiler: while telemetry is on, every span also opens a
    ``jax.profiler.TraceAnnotation`` of the same name, with or without a
    ring tracer installed, so a profiler session (``jax.profiler.trace``)
    records it in the ``.xplane.pb`` host plane beside the device ops it
    launched.

Clock: a ring record's ``ts_us`` is ``time.time_ns()`` at the span's start,
the clock the profiler stamps its events with.  An ``.xplane.pb`` stores its
event times as offsets from the session's start (the ``profile_start_time``
stat of its ``Task Environment`` plane, :func:`profile_start_ns`), so
``ts_us * 1e3 - profile_start_ns(...)`` is the span's start on the
profile's timeline: one constant offset per profiler session.

Recording is observational only: spans never touch RNG state, search state
or any value the engines compute.  When telemetry is disabled, ``span()``
returns one shared null context manager -- no allocation, no clock read.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from repro.obs import state as _state

_annotation_cls = None


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` (JAX imported on first use)."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    return _annotation_cls(name)


def profile_start_ns(profile) -> Optional[int]:
    """The ``time.time_ns()`` at which the session of a
    ``jax.profiler.ProfileData`` started: add it to an event's ``start_ns``
    to put the event on the ring's clock.  None when the profile lacks it."""
    for plane in profile.planes:
        if plane.name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    return int(value)
    return None


class _NullSpan:
    """Shared do-nothing span: the disabled path and attr sink."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NULL_SPAN = _NullSpan()


class _ProfilerSpan:
    """Telemetry on without a ring tracer: the profiler annotation alone."""

    __slots__ = ("_ann",)

    def __init__(self, name: str):
        self._ann = _annotation(name)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def set(self, **attrs):
        return self


class _Span:
    """One live span; finished records are plain dicts in the ring.  Its
    profiler annotation encloses the ring's interval."""

    __slots__ = ("tracer", "name", "attrs", "ts", "t0", "parent", "depth",
                 "tid", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> "_Span":
        """Attach attributes discovered mid-span (e.g. fuse width)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        tls = self.tracer._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        self.parent = stack[-1].name if stack else None
        self.depth = len(stack)
        self.tid = threading.get_ident()
        stack.append(self)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.ts = time.time_ns()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.t0
        self._ann.__exit__(*exc)
        stack = self.tracer._tls.stack
        if stack and stack[-1] is self:
            stack.pop()
        self.tracer._record(self.name, self.ts, dur, self.tid, self.depth,
                            self.parent, self.attrs)
        return False


class Tracer:
    """Span collector: ring buffer + optional JSONL sink + exporters."""

    def __init__(self, ring: int = 16384,
                 jsonl_path: Optional[str] = None):
        self._ring: "deque[dict]" = deque(maxlen=max(int(ring), 1))
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._jsonl_path = jsonl_path
        self._jsonl_file = None
        self.dropped = 0
        if jsonl_path:
            os.makedirs(os.path.dirname(os.path.abspath(jsonl_path)),
                        exist_ok=True)
            self._jsonl_file = open(jsonl_path, "w")

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def add(self, name: str, ts_ns: int, dur_ns: int, **attrs) -> None:
        """Record a span timed elsewhere (``ts_ns`` on ``time.time_ns``'s
        clock), nested under this thread's open span -- JAX's own compile
        events arrive this way."""
        stack = getattr(self._tls, "stack", None)
        self._record(name, ts_ns, dur_ns, threading.get_ident(),
                     len(stack) if stack else 0,
                     stack[-1].name if stack else None, attrs)

    def _record(self, name: str, ts_ns: int, dur_ns: int, tid: int,
                depth: int, parent: Optional[str], attrs: Dict) -> None:
        rec = {
            "name": name,
            "ts_us": round(ts_ns / 1e3, 3),
            "dur_us": round(dur_ns / 1e3, 3),
            "tid": tid,
            "depth": depth,
        }
        if parent is not None:
            rec["parent"] = parent
        if attrs:
            rec["attrs"] = {k: _jsonable(v) for k, v in attrs.items()}
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(rec)
            if self._jsonl_file is not None:
                self._jsonl_file.write(json.dumps(rec) + "\n")
                self._jsonl_file.flush()

    def spans(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0

    # -- exporters ----------------------------------------------------------
    def chrome_trace(self) -> Dict:
        """Chrome trace-event JSON (complete events, microsecond units)."""
        pid = os.getpid()
        events = [{
            "name": rec["name"],
            "ph": "X",
            "ts": rec["ts_us"],
            "dur": rec["dur_us"],
            "pid": pid,
            "tid": rec["tid"],
            "args": rec.get("attrs", {}),
        } for rec in self.spans()]
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        """Write the ring buffer: ``.jsonl`` -> one span per line; anything
        else -> Chrome trace JSON (open in chrome://tracing or Perfetto)."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            if path.endswith(".jsonl"):
                for rec in self.spans():
                    f.write(json.dumps(rec) + "\n")
            else:
                json.dump(self.chrome_trace(), f)

    def close(self) -> None:
        with self._lock:
            if self._jsonl_file is not None:
                self._jsonl_file.close()
                self._jsonl_file = None


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        return float(v)
    except (TypeError, ValueError):
        return str(v)


def span(name: str, **attrs):
    """The module-level span entry point every call site uses.

    Telemetry off -> the shared :data:`NULL_SPAN`; on -> a span on the
    profiler's timeline, recorded in the installed tracer's ring too when
    there is one.  Always usable as
    ``with obs.span("x", k=v) as sp: sp.set(more=...)``.
    """
    if not _state.enabled:
        return NULL_SPAN
    tracer = _state.tracer
    if tracer is None:
        return _ProfilerSpan(name)
    return tracer.span(name, **attrs)
