"""``repro.obs``: zero-dependency observability for the serving + engine stack.

Three pieces, one switch:

  * **metrics** -- a process-wide registry of counters / gauges / fixed-
    bucket histograms with Prometheus text exposition and a JSON snapshot
    (:mod:`repro.obs.metrics`);
  * **tracing** -- nested spans over a ring buffer, an optional JSONL
    sink, and a Chrome-trace/Perfetto export; while telemetry is on, each
    span is also a ``jax.profiler.TraceAnnotation``, so a profiler trace
    shows it beside the device ops it launched (:mod:`repro.obs.trace`);
  * **flight recorder** -- a per-search accumulator whose summary lands in
    ``SearchOutcome.telemetry`` (:mod:`repro.obs.recorder`).

Everything is off by default and observational by contract: enabling
telemetry never changes a search result (byte-identity is asserted across
the whole optimizer registry in tests/test_optimizer_conformance.py), and
the disabled path costs one bool check per call site
(benchmarks/bench_obs_overhead.py keeps it under 2% on the 8-way service
mix).

Typical use::

    from repro import api, obs

    obs.enable(trace=True)
    out = api.run_search(api.SearchRequest(workload="ncf", method="ga"))
    print(out.telemetry["hard_evals"], out.telemetry["cache_hit_rate"])
    obs.save_trace("trace.jsonl")          # or .json -> Chrome/Perfetto
    print(obs.REGISTRY.prometheus_text())
    obs.disable()
"""
from __future__ import annotations

from typing import Optional

from repro.obs import state as _state
from repro.obs.metrics import (REGISTRY, Counter, Gauge, Histogram,
                               MetricsRegistry, counter, gauge, histogram,
                               write_prometheus)
from repro.obs.recorder import (FlightRecorder, current_recorder, record,
                                observe, recording)
from repro.obs.trace import NULL_SPAN, Tracer, span
from repro.obs import instrument

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "counter", "gauge", "histogram", "write_prometheus",
    "FlightRecorder", "current_recorder", "record", "observe", "recording",
    "NULL_SPAN", "Tracer", "span", "instrument",
    "enable", "disable", "enabled", "tracer", "save_trace", "reset",
]


def enable(trace: bool = True, ring: int = 16384,
           jsonl_path: Optional[str] = None) -> None:
    """Turn telemetry on process-wide.

    ``trace=True`` installs a :class:`Tracer` (``ring`` spans of in-memory
    history; ``jsonl_path`` additionally streams every finished span to a
    JSONL file).  Metrics, flight recorders, profiler annotations and the
    JIT watcher (:func:`repro.obs.instrument.watch_jit`) activate either
    way.  Idempotent: re-enabling with ``trace=True`` keeps an
    already-installed tracer unless a new ``jsonl_path`` is requested.
    """
    if trace:
        t = _state.tracer
        if t is None or jsonl_path is not None:
            if t is not None:
                t.close()
            _state.tracer = Tracer(ring=ring, jsonl_path=jsonl_path)
    instrument.watch_jit()
    _state.enabled = True


def disable() -> None:
    """Turn telemetry off (the default state); the tracer's buffered spans
    stay readable until :func:`enable` installs a fresh one."""
    _state.enabled = False


def enabled() -> bool:
    return _state.enabled


def tracer() -> Optional[Tracer]:
    return _state.tracer


def save_trace(path: str) -> None:
    """Write the installed tracer's ring buffer: ``.jsonl`` for one span per
    line, any other extension for Chrome-trace JSON (chrome://tracing or
    https://ui.perfetto.dev)."""
    t = _state.tracer
    if t is None:
        raise RuntimeError("no tracer installed; call obs.enable() first")
    t.save(path)


def reset() -> None:
    """Test/bench helper: zero metrics and clear spans."""
    REGISTRY.reset()
    if _state.tracer is not None:
        _state.tracer.clear()
