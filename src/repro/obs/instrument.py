"""Cross-cutting instrumentation helpers + the repo's metric catalog.

Every instrumented module pulls its metric handles from here so the full
catalog lives in one place (mirrored in docs/observability.md).  All
handles are created lazily at import of this module -- creation is cheap
and updates are no-ops while telemetry is disabled.

Also home of the JIT watcher: JAX reports every trace, lowering and backend
compile (and every persistent-cache hit) through ``jax.monitoring``;
:func:`watch_jit` listens once per process and, while telemetry is on,
turns those events into ``repro_jit_seconds{phase}``,
``repro_jit_compiles{result}``, ``jit.<phase>`` ring spans and the current
search's ``jit_s`` / ``jit_compiles``.
"""
from __future__ import annotations

import threading
import time

from repro.obs import metrics as _metrics
from repro.obs import recorder as _recorder
from repro.obs import state as _state
from repro.obs import trace as _trace

# --------------------------------------------------------------------------
# Metric catalog (names, types, labels).  docs/observability.md documents
# every entry; tests/test_obs.py asserts the two stay in sync.
# --------------------------------------------------------------------------
SEARCH_HARD_EVALS = _metrics.counter(
    "repro_search_hard_evals", "Whole-model hard cost evaluations consumed",
    labels=("engine",))
SEARCH_CHUNKS = _metrics.counter(
    "repro_search_chunks", "Engine chunks executed", labels=("engine",))
SEARCH_CHUNK_SECONDS = _metrics.histogram(
    "repro_search_chunk_seconds", "Wall-clock per engine chunk",
    labels=("engine",))
SEARCH_STEP_SECONDS = _metrics.histogram(
    "repro_search_step_seconds",
    "Wall-clock per host-evaluated engine generation", labels=("engine",))
SEARCH_EVAL_WAIT_SECONDS = _metrics.histogram(
    "repro_search_eval_wait_seconds",
    "Per-generation wait on the injected eval_fn", labels=("engine",))
JIT_SECONDS = _metrics.histogram(
    "repro_jit_seconds",
    "JAX trace / lower / backend-compile wall-clock, nested events excluded",
    labels=("phase",))   # phase: trace|lower|compile
JIT_COMPILES = _metrics.counter(
    "repro_jit_compiles", "Backend compiles reported by JAX",
    labels=("result",))   # result: compiled|cache_hit
ENGINE_PROGRAMS = _metrics.counter(
    "repro_engine_programs",
    "Engine program-cache lookups, one per search",
    labels=("engine", "result"))   # result: reused|built
DISPATCH_SECONDS = _metrics.histogram(
    "repro_dispatch_seconds", "XLA/Pallas dispatch wall-clock",
    labels=("program",))

BATCHER_DISPATCHES = _metrics.counter(
    "repro_batcher_dispatches", "Fused-dispatch rounds executed")
BATCHER_POINTS = _metrics.counter(
    "repro_batcher_points", "Per-layer points through the batcher",
    labels=("kind",))   # kind: submitted|unique|fresh
BATCHER_AGGREGATED_ITEMS = _metrics.counter(
    "repro_batcher_aggregated_items",
    "Eval requests aggregated to their answers, by where",
    labels=("path",))   # path: host (NumPy) | jit (blend fitness)
BATCHER_FRESH_POINTS = _metrics.counter(
    "repro_batcher_fresh_points",
    "Fresh points handed to the cost evaluator, by layer type",
    labels=("ltype",))   # ltype: conv|dwconv|gemm|bmm|experts
BATCHER_QUEUE_DEPTH = _metrics.gauge(
    "repro_batcher_queue_depth", "Eval requests awaiting dispatch")
BATCHER_FUSE_WIDTH = _metrics.histogram(
    "repro_batcher_fuse_width", "Requests fused per dispatch",
    buckets=_metrics.DEFAULT_SIZE_BUCKETS)
BATCHER_QUEUE_WAIT = _metrics.histogram(
    "repro_batcher_queue_wait_seconds",
    "Submit-to-dispatch-start wait per eval request")
BATCHER_DISPATCH_SECONDS = _metrics.histogram(
    "repro_batcher_dispatch_seconds", "Fused dispatch wall-clock")
BATCHER_PHASE_SECONDS = _metrics.histogram(
    "repro_batcher_phase_seconds", "Host wall-clock per fused-dispatch phase",
    labels=("phase",))   # phase: dedup|lookup|eval|fill|aggregate

CACHE_LOOKUPS = _metrics.counter(
    "repro_cache_lookups", "Cost-memo lookups", labels=("result",))
CACHE_EVICTIONS = _metrics.counter(
    "repro_cache_evictions", "Cost-memo LRU evictions")
CACHE_LOOKUP_SECONDS = _metrics.histogram(
    "repro_cache_lookup_seconds", "Batched cache lookup latency")

SERVICE_ACTIVE = _metrics.gauge(
    "repro_service_active_searches", "Searches currently executing")
SERVICE_REQUESTS = _metrics.counter(
    "repro_service_requests", "Search tickets finished",
    labels=("status",))   # status: completed|cancelled|failed
SERVICE_QUEUE_WAIT = _metrics.histogram(
    "repro_service_queue_wait_seconds", "Ticket submit-to-start wait")

HTTP_REQUESTS = _metrics.counter(
    "repro_http_requests", "HTTP front-door requests served",
    labels=("route", "code"))   # route is the template, not the raw path
HTTP_REQUEST_SECONDS = _metrics.histogram(
    "repro_http_request_seconds", "HTTP request handling wall-clock",
    labels=("route",))
HTTP_QUEUE_DEPTH = _metrics.gauge(
    "repro_http_queue_depth", "Front-door jobs awaiting a worker slot")

METRIC_NAMES = tuple(sorted(
    m.name for m in _metrics.REGISTRY.metrics()))

# Span taxonomy (documented in docs/observability.md).
SPAN_NAMES = (
    "service.search",     # one ticket end-to-end (uid, method, status)
    "search.run",         # one api.run_search call (method, eps, seed)
    "search.chunk",       # one engine chunk (engine, start, steps, evals)
    "search.step",        # one host-evaluated generation (engine)
    "search.eval",        # its wait on the injected eval_fn (engine)
    "batcher.dispatch",   # one fused dispatch (items, points, unique, fresh)
    "batcher.dedup",      # concat + dedup by row bytes + keys
    "batcher.lookup",     # memo-cache get_many
    "batcher.eval",       # the fresh points' evaluation
    "batcher.fill",       # memo-cache put_many + per-point stack
    "batcher.aggregate",  # the items' aggregation to their answers
    "xla.dispatch",       # one device program dispatch (program)
    "jit.trace",          # JAX traced a jaxpr (fun_name)
    "jit.lower",          # JAX lowered a jaxpr to MLIR (fun_name)
    "jit.compile",        # XLA compiled or loaded it from cache (fun_name)
)


class _Timed:
    """A span that also observes its duration into a histogram (and, given
    ``record``, into the current flight recorder)."""

    __slots__ = ("_span", "_hist", "_labels", "_record", "_t0")

    def __init__(self, name: str, hist, labels, record=None):
        self._span = _trace.span(name, **labels)
        self._hist = hist
        self._labels = labels
        self._record = record

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        self._hist.observe(dt, **self._labels)
        if self._record is not None:
            _recorder.observe(self._record, dt)
        return False


def timed(name: str, hist, **labels):
    """``with timed("batcher.dedup", BATCHER_PHASE_SECONDS, phase="dedup"):``
    -- span ``name`` plus one ``hist`` observation (``labels`` double as the
    span's attrs).  Telemetry off: the shared null span."""
    if not _state.enabled:
        return _trace.NULL_SPAN
    return _Timed(name, hist, labels)


def dispatch_span(program: str):
    """Time one device dispatch: ``with dispatch_span("cost_eval_kernel"):``
    records an ``xla.dispatch`` span, a ``repro_dispatch_seconds``
    observation and the current flight recorder's ``<program>_dispatch_s``.
    Telemetry off: the shared null span."""
    if not _state.enabled:
        return _trace.NULL_SPAN
    return _Timed("xla.dispatch", DISPATCH_SECONDS, {"program": program},
                  record=f"{program}_dispatch_s")


# --------------------------------------------------------------------------
# JIT events, as JAX reports them.
# --------------------------------------------------------------------------
_JIT_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_jit_tls = threading.local()
_jit_lock = threading.Lock()
_jit_watching = False


def watch_jit() -> None:
    """Listen to JAX's compile events (registered once per process; the
    listeners do nothing while telemetry is off)."""
    global _jit_watching
    with _jit_lock:
        if _jit_watching:
            return
        from jax import monitoring
        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_event_time_span_listener(_on_jit_span)
        _jit_watching = True


def _on_jax_event(event: str, **_) -> None:
    if _state.enabled and event == _CACHE_HIT:
        _jit_tls.cache_hit = True   # inside this thread's backend compile


def _on_jit_span(event: str, start: float, end: float,
                 fun_name: str = "", **_) -> None:
    """One finished JAX compile event (``time.time()`` seconds).

    Events nest on their thread: tracing a function traces the jitted
    functions it calls, and lowering traces some more.  Each event arrives
    at its end, after its children, so the children still on this thread's
    ``done`` list are exactly the most recent entries that started inside
    and ended inside it; it counts its own time without theirs, and the
    phases sum to the time spent in JAX's compile path."""
    phase = _JIT_PHASES.get(event)
    if phase is None or not _state.enabled:
        return
    done = getattr(_jit_tls, "done", None)
    if done is None:
        done = _jit_tls.done = []
    inner = 0.0
    while done and done[-1][0] >= start and done[-1][1] <= end:
        s, e = done.pop()
        inner += e - s
    done.append((start, end))
    if len(done) > 1024:
        del done[:-1024]
    own = max(end - start - inner, 0.0)
    JIT_SECONDS.observe(own, phase=phase)
    _recorder.observe("jit_s", own)
    if phase == "compile":
        hit = getattr(_jit_tls, "cache_hit", False)
        _jit_tls.cache_hit = False
        JIT_COMPILES.inc(result="cache_hit" if hit else "compiled")
        if not hit:
            _recorder.record("jit_compiles")
    tracer = _state.tracer
    if tracer is not None:
        tracer.add(f"jit.{phase}", int(start * 1e9),
                   int((end - start) * 1e9), fun_name=fun_name)


def chunk_metrics(engine: str, steps: int, evals: int,
                  seconds: float) -> None:
    """One chunk finished: registry counters + flight-recorder entries."""
    SEARCH_CHUNKS.inc(engine=engine)
    SEARCH_HARD_EVALS.inc(evals, engine=engine)
    SEARCH_CHUNK_SECONDS.observe(seconds, engine=engine)
    _recorder.record("chunks")
    _recorder.record("hard_evals", evals)
    _recorder.observe("chunk_s", seconds)


def hard_evals(engine: str, n: int) -> None:
    """Count ``n`` hard evaluations outside the chunk loop (the host-batch
    baselines -- random/grid/bo -- burn their budget in plain batched loops).
    Self-gated: free while telemetry is off."""
    if not _state.enabled:
        return
    SEARCH_HARD_EVALS.inc(n, engine=engine)
    _recorder.record("hard_evals", n)
