"""The ``repro.obs`` telemetry layer: metrics registry, tracer, flight
recorder, batcher/cache instrumentation, and the docs catalog sync.

Byte-identity of instrumented vs plain searches is covered registry-wide in
tests/test_optimizer_conformance.py::test_telemetry_is_observational; this
file unit-tests the obs primitives themselves plus the serving-stack
accounting (including a multi-thread batcher hammer with exact counter
assertions).
"""
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from repro import api, obs
from repro.core import env as env_lib
from repro.costmodel import workloads
from repro.obs import instrument, metrics, recorder, state as obs_state
from repro.obs import trace as trace_mod
from repro.serving.batcher import CostEvalBatcher
from repro.serving.cost_cache import CostMemoCache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ECFG = env_lib.EnvConfig(platform="cloud")


@pytest.fixture(autouse=True)
def _clean_obs():
    """Telemetry is process-global: every test starts and ends disabled
    with zeroed metrics, whatever it does in between."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _enabled():
    obs.enable(trace=True)


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------
def test_counter_counts_and_is_gated():
    c = metrics.counter("t_obs_counter", "x", labels=("k",))
    c.inc(k="a")                      # disabled -> dropped
    assert c.value(k="a") == 0.0
    _enabled()
    c.inc(k="a")
    c.inc(2.5, k="a")
    c.inc(k="b")
    assert c.value(k="a") == 3.5 and c.value(k="b") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1.0, k="a")            # counters only go up
    with pytest.raises(ValueError):
        c.inc(wrong="label")


def test_gauge_up_down():
    g = metrics.gauge("t_obs_gauge", "x")
    _enabled()
    g.set(5.0)
    g.inc()
    g.dec(2.0)
    assert g.value() == 4.0


def test_histogram_stats_and_buckets():
    h = metrics.histogram("t_obs_hist", "x", buckets=(1.0, 10.0))
    _enabled()
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    st = h.stats()
    assert st["count"] == 3 and st["max"] == 50.0
    assert st["sum"] == pytest.approx(55.5)
    # Exposition: cumulative le buckets ending at +Inf == _count.
    text = obs.REGISTRY.prometheus_text()
    assert 't_obs_hist_bucket{le="1.0"} 1' in text
    assert 't_obs_hist_bucket{le="10.0"} 2' in text
    assert 't_obs_hist_bucket{le="+Inf"} 3' in text
    assert "t_obs_hist_count 3" in text


def test_registry_get_or_create_and_conflicts():
    a = metrics.counter("t_obs_same", "x", labels=("k",))
    b = metrics.counter("t_obs_same", "x", labels=("k",))
    assert a is b
    with pytest.raises(ValueError):
        metrics.gauge("t_obs_same")                   # kind conflict
    with pytest.raises(ValueError):
        metrics.counter("t_obs_same", labels=("other",))   # label conflict


def test_counters_expose_total_suffix_and_reset_zeroes():
    c = metrics.counter("t_obs_totaled", "x")
    _enabled()
    c.inc(3)
    text = obs.REGISTRY.prometheus_text()
    assert "t_obs_totaled_total 3.0" in text
    assert "\nt_obs_totaled 3.0" not in text          # only the _total form
    snap = obs.REGISTRY.snapshot()["t_obs_totaled"]
    assert snap["kind"] == "counter" and snap["values"][""] == 3.0
    obs.REGISTRY.reset()
    assert c.value() == 0.0


def test_exposition_passes_the_telemetry_checker(tmp_path):
    """The registry's own output must satisfy tools/check_telemetry.py --
    the exact validation CI runs on real artifacts."""
    spec = importlib.util.spec_from_file_location(
        "check_telemetry", os.path.join(REPO, "tools", "check_telemetry.py"))
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)

    _enabled()
    instrument.SEARCH_HARD_EVALS.inc(100, engine="ga")
    instrument.SEARCH_CHUNK_SECONDS.observe(0.5, engine="ga")
    instrument.BATCHER_QUEUE_DEPTH.set(3)
    path = tmp_path / "m.prom"
    obs.write_prometheus(str(path))
    n = checker.check_metrics(str(path), ["repro_search_hard_evals"])
    assert n > 0


# ---------------------------------------------------------------------------
# Tracer.
# ---------------------------------------------------------------------------
def test_spans_nest_with_depth_and_parent():
    t = trace_mod.Tracer()
    with t.span("outer", k=1):
        with t.span("inner"):
            pass
    inner, outer = t.spans()
    assert inner["name"] == "inner" and inner["depth"] == 1
    assert inner["parent"] == "outer"
    assert outer["name"] == "outer" and outer["depth"] == 0
    assert "parent" not in outer
    assert outer["attrs"] == {"k": 1}
    assert outer["dur_us"] >= inner["dur_us"] >= 0


def test_ring_bounds_and_counts_drops():
    t = trace_mod.Tracer(ring=2)
    for i in range(5):
        with t.span(f"s{i}"):
            pass
    assert [r["name"] for r in t.spans()] == ["s3", "s4"]
    assert t.dropped == 3


def test_disabled_span_is_the_shared_null(tmp_path):
    assert trace_mod.span("x") is trace_mod.NULL_SPAN
    with trace_mod.span("x", a=1) as sp:
        assert sp.set(b=2) is sp      # chaining-safe on the disabled path
    _enabled()
    with trace_mod.span("real") as sp:
        assert sp is not trace_mod.NULL_SPAN


def test_jsonl_sink_and_chrome_export(tmp_path):
    jsonl = tmp_path / "t.jsonl"
    t = trace_mod.Tracer(jsonl_path=str(jsonl))
    with t.span("a", n=3):
        pass
    t.close()
    recs = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    assert len(recs) == 1 and recs[0]["name"] == "a"
    assert recs[0]["attrs"] == {"n": 3}
    ct = t.chrome_trace()
    (ev,) = ct["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "a" and ev["dur"] >= 0
    # save() picks the format from the extension.
    out = tmp_path / "t.json"
    t.save(str(out))
    assert json.loads(out.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# Flight recorder.
# ---------------------------------------------------------------------------
def test_recorder_summary_counts_series_and_ratios():
    r = recorder.FlightRecorder(engine="ga")
    r.add("points", 10)
    r.add("cached_points", 4)
    r.add("fresh_points", 6)
    r.observe("dispatch_s", 0.2)
    r.observe("dispatch_s", 0.4)
    s = r.summary()
    assert s["engine"] == "ga" and s["points"] == 10
    assert s["cache_hit_rate"] == pytest.approx(0.4)
    assert s["fresh_frac"] == pytest.approx(0.6)
    d = s["dispatch_s"]
    assert d["count"] == 2 and d["max"] == pytest.approx(0.4)
    assert d["mean"] == pytest.approx(0.3)


def test_recording_is_thread_local_and_gated():
    r = recorder.FlightRecorder()
    recorder.record("k")              # no recorder, disabled -> no-op
    _enabled()
    with recorder.recording(r):
        recorder.record("k", 2)
        seen = []
        th = threading.Thread(
            target=lambda: seen.append(recorder.current_recorder()))
        th.start()
        th.join()
        assert seen == [None]         # other threads see no recorder
    recorder.record("k")              # uninstalled again
    assert r.count("k") == 2.0


# ---------------------------------------------------------------------------
# Dispatch timing + JAX's own compile events.
# ---------------------------------------------------------------------------
def test_dispatch_span_times_every_dispatch():
    _enabled()
    rec = recorder.FlightRecorder()
    with recorder.recording(rec):
        for _ in range(4):
            with instrument.dispatch_span("t_prog"):
                pass
    assert instrument.DISPATCH_SECONDS.stats(program="t_prog")["count"] == 4
    assert rec.summary()["t_prog_dispatch_s"]["count"] == 4
    assert "jit_compiles" not in rec.summary()       # timing is no compile
    spans = [s for s in obs.tracer().spans() if s["name"] == "xla.dispatch"]
    assert [s["attrs"] for s in spans] == [{"program": "t_prog"}] * 4


def test_jit_watcher_counts_real_compiles():
    """A fresh jax.jit is one backend compile with positive seconds; its
    second call compiles nothing and counts nothing."""
    import jax

    _enabled()
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.arange(7, dtype=np.float32)
    rec = recorder.FlightRecorder()
    with recorder.recording(rec):
        f(x).block_until_ready()
    compiled = instrument.JIT_COMPILES.value(result="compiled")
    compile_s = instrument.JIT_SECONDS.stats(phase="compile")
    assert compiled == 1.0
    assert instrument.JIT_COMPILES.value(result="cache_hit") == 0.0
    assert compile_s["count"] == 1 and compile_s["sum"] > 0.0
    for phase in ("trace", "lower"):
        assert instrument.JIT_SECONDS.stats(phase=phase)["count"] >= 1
    summary = rec.summary()
    assert summary["jit_compiles"] == 1 and summary["jit_s"]["sum"] > 0.0
    names = [s["name"] for s in obs.tracer().spans()]
    assert names.count("jit.compile") == 1
    (sp,) = [s for s in obs.tracer().spans() if s["name"] == "jit.compile"]
    assert "lambda" in sp["attrs"]["fun_name"] and sp["dur_us"] > 0

    f(x + 1.0).block_until_ready()
    assert instrument.JIT_COMPILES.value(result="compiled") == compiled
    assert instrument.JIT_SECONDS.stats(phase="compile") == compile_s


def test_jit_watcher_cache_hits_and_nested_events():
    """JAX's persistent-cache hit inside a backend compile counts as
    ``cache_hit``; an event nested in another counts once, in the inner
    one; nothing counts while telemetry is off."""
    from jax import monitoring

    trace_ev = "/jax/core/compile/jaxpr_trace_duration"
    lower_ev = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    compile_ev = "/jax/core/compile/backend_compile_duration"
    obs.enable(trace=False)
    t = time.time()
    monitoring.record_event_time_span(trace_ev, t + 0.2, t + 0.5,
                                      fun_name="inner")
    monitoring.record_event_time_span(trace_ev, t + 0.0, t + 1.0,
                                      fun_name="outer")
    monitoring.record_event_time_span(trace_ev, t + 1.5, t + 1.75,
                                      fun_name="in_lowering")
    monitoring.record_event_time_span(lower_ev, t + 1.0, t + 2.0,
                                      fun_name="outer")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_time_span(compile_ev, t + 2.0, t + 2.5,
                                      fun_name="outer")
    monitoring.record_event_time_span(compile_ev, t + 3.0, t + 5.0,
                                      fun_name="other")
    tr = instrument.JIT_SECONDS.stats(phase="trace")
    assert tr["count"] == 3 and tr["sum"] == pytest.approx(1.25)
    assert instrument.JIT_SECONDS.stats(phase="lower")["sum"] == \
        pytest.approx(0.75)
    assert instrument.JIT_SECONDS.stats(phase="compile")["sum"] == \
        pytest.approx(2.5)
    assert instrument.JIT_COMPILES.value(result="cache_hit") == 1.0
    assert instrument.JIT_COMPILES.value(result="compiled") == 1.0

    obs.disable()
    monitoring.record_event_time_span(compile_ev, t + 6.0, t + 7.0,
                                      fun_name="off")
    assert instrument.JIT_COMPILES.value(result="compiled") == 1.0
    assert instrument.JIT_SECONDS.stats(phase="compile")["count"] == 2


def test_hard_evals_helper_feeds_registry_and_recorder():
    instrument.hard_evals("random", 50)      # disabled -> free no-op
    assert instrument.SEARCH_HARD_EVALS.value(engine="random") == 0.0
    _enabled()
    rec = recorder.FlightRecorder()
    with recorder.recording(rec):
        instrument.hard_evals("random", 50)
    assert instrument.SEARCH_HARD_EVALS.value(engine="random") == 50.0
    assert rec.count("hard_evals") == 50.0


# ---------------------------------------------------------------------------
# Cache + batcher accounting.
# ---------------------------------------------------------------------------
def test_empty_cache_hit_rate_is_zero():
    cache = CostMemoCache()
    assert cache.hit_rate == 0.0
    assert cache.stats()["hit_rate"] == 0.0


def test_batcher_cache_stats_merge_asserts_disjoint_keys():
    b = CostEvalBatcher()
    try:
        s = b.stats()
        assert s["cache_hits"] == 0           # cache_ namespaced in
        assert "dispatches" in s
        # A batcher-native key colliding with the cache_ namespace must
        # fail loudly, not silently shadow.
        with b._stats_lock:
            b._stats["cache_hits"] = 99
        with pytest.raises(AssertionError):
            b.stats()
    finally:
        with b._stats_lock:
            b._stats.pop("cache_hits", None)
        b.close()


def test_batcher_hammer_exact_counters_and_attribution():
    """Satellite: N searches hammer one batcher from worker threads; every
    process-wide counter and per-search flight-recorder count must come out
    exact (no lost updates), and concurrency stays within the pool."""
    _enabled()
    env = env_lib.make_env(workloads.get_workload("ncf"), ECFG)
    layers = np.asarray(env.layers, np.float32)
    N = layers.shape[0]
    T, K, B = 4, 3, 8            # threads x submits x genomes-per-submit
    workers = 2
    b = CostEvalBatcher(window_ms=1.0, dispatch_workers=workers)
    recs = [recorder.FlightRecorder(engine=f"t{i}") for i in range(T)]
    fits = [None] * T
    errors = []

    def worker(i):
        rng = np.random.default_rng(i)
        try:
            with recorder.recording(recs[i]):
                out = []
                for _ in range(K):
                    pe = rng.integers(1, 64, (B, N)).astype(np.float32)
                    kt = rng.integers(1, 64, (B, N)).astype(np.float32)
                    out.append(b.evaluate(layers, pe, kt, 0.0, ECFG,
                                          env.budget))
                fits[i] = out
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert not errors
        s = b.stats()
        assert s["items"] == T * K
        assert s["points"] == T * K * B * N
        assert 1 <= s["dispatches"] <= T * K
        # The cache is consulted once per unique row per dispatch.
        assert s["cache_hits"] + s["cache_misses"] == s["unique_points"]
        assert s["fresh_points"] == s["cache_misses"]
        assert s["max_concurrent_dispatches"] <= workers
        assert s["dispatch_workers"] == workers

        # Process-wide metrics agree with the batcher's own ledger.
        pts = instrument.BATCHER_POINTS
        assert pts.value(kind="submitted") == s["points"]
        assert pts.value(kind="unique") == s["unique_points"]
        assert pts.value(kind="fresh") == s["fresh_points"]
        assert instrument.BATCHER_DISPATCHES.value() == s["dispatches"]
        assert instrument.BATCHER_FUSE_WIDTH.stats()["count"] == \
            s["dispatches"]
        assert instrument.BATCHER_QUEUE_WAIT.stats()["count"] == T * K

        # Per-search attribution: each rider credited exactly its share.
        for r in recs:
            t = r.summary()
            assert t["eval_batches"] == K
            assert t["points"] == K * B * N
            assert t["fresh_points"] + t["cached_points"] == t["points"]
            assert t["queue_wait_s"]["count"] == K
        assert sum(r.count("fresh_points") for r in recs) == \
            s["fresh_points"]

        # Sanity: results are real fitness vectors.
        for out in fits:
            assert len(out) == K and all(f.shape == (B,) for f in out)
    finally:
        b.close()


def test_batcher_phases_sum_to_dispatch_seconds():
    """dedup + lookup + eval + fill is the dispatch histogram's interval;
    aggregate follows it, inside the ``batcher.dispatch`` span."""
    _enabled()
    env = env_lib.make_env(workloads.get_workload("resnet50"), ECFG)
    layers = np.asarray(env.layers, np.float32)
    N = layers.shape[0]
    rng = np.random.default_rng(5)
    b = CostEvalBatcher(window_ms=0.0)
    try:
        for _ in range(6):
            pe = rng.integers(1, 64, (64, N)).astype(np.float32)
            kt = rng.integers(1, 64, (64, N)).astype(np.float32)
            b.evaluate(layers, pe, kt, 0.0, ECFG, env.budget)
        n = b.stats()["dispatches"]
    finally:
        b.close()
    ph = instrument.BATCHER_PHASE_SECONDS
    parts = sum(ph.stats(phase=p)["sum"]
                for p in ("dedup", "lookup", "eval", "fill"))
    whole = instrument.BATCHER_DISPATCH_SECONDS.stats()
    assert whole["count"] == n
    assert parts == pytest.approx(whole["sum"], rel=0.05)
    for p in ("dedup", "lookup", "eval", "fill", "aggregate"):
        assert ph.stats(phase=p)["count"] == n, p
    spans = obs.tracer().spans()
    assert sum(s["name"] == "batcher.aggregate" for s in spans) == n
    assert all(s["parent"] == "batcher.dispatch" for s in spans
               if s["name"].startswith("batcher.")
               and s["name"] != "batcher.dispatch")


def test_service_queue_wait_records_the_second_tickets_wait():
    from repro.serving import SearchService, ServiceConfig

    _enabled()
    req = [api.SearchRequest(workload="ncf", env=ECFG, eps=40, seed=s,
                             method="ga", options={"population": 20})
           for s in (0, 1)]
    with SearchService(ServiceConfig(max_workers=1)) as svc:
        t1, t2 = svc.submit(req[0]), svc.submit(req[1])
        t1.result()
        t2.result()
    waits = [t.started_at - t.submitted_at for t in (t1, t2)]
    assert t2.started_at >= t1.submitted_at + t1.wall_seconds  # t1's end
    st = instrument.SERVICE_QUEUE_WAIT.stats()
    assert st["count"] == 2
    assert st["sum"] == pytest.approx(sum(waits))
    assert st["max"] == pytest.approx(waits[1]) and waits[1] > waits[0]


def test_engine_steps_split_eval_wait_from_host_time():
    from repro.serving import SearchService, ServiceConfig

    _enabled()
    reqs = [api.SearchRequest(workload="ncf", env=ECFG, eps=eps, seed=0,
                              method=m, options={"population": pop})
            for m, eps, pop in (("ga", 60, 20), ("nsga2", 64, 16))]
    with SearchService(ServiceConfig(max_workers=2)) as svc:
        svc.run_all(reqs)
    for engine, gens in (("ga", 3), ("nsga2", 4)):
        step = instrument.SEARCH_STEP_SECONDS.stats(engine=engine)
        wait = instrument.SEARCH_EVAL_WAIT_SECONDS.stats(engine=engine)
        assert step["count"] == wait["count"] == gens, engine
        assert 0.0 < wait["sum"] < step["sum"]
    spans = obs.tracer().spans()
    assert all(s["parent"] == "search.step" for s in spans
               if s["name"] == "search.eval")
    assert all(s["parent"] == "search.chunk" for s in spans
               if s["name"] == "search.step")


# ---------------------------------------------------------------------------
# Spans on the JAX profiler's timeline.
# ---------------------------------------------------------------------------
PROFILED = ("service.search", "search.run", "search.chunk", "search.step",
            "search.eval", "batcher.dispatch", "batcher.dedup",
            "batcher.lookup", "batcher.eval", "batcher.fill",
            "batcher.aggregate")


def _profiled_service_run(log_dir):
    """One GA ticket through a service, under a profiler session; returns
    the profile's program-span events {name: [(start, end, line)]} (ns
    from the session's start) and the session's start on time.time_ns."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from repro.serving import SearchService, ServiceConfig

    req = [api.SearchRequest(workload="ncf", env=ECFG, eps=60, seed=s,
                             method="ga", options={"population": 20})
           for s in (4, 5)]
    with SearchService(ServiceConfig(max_workers=1)) as svc:
        svc.run_all(req[:1])            # compiles outside the session
        with jax.profiler.trace(str(log_dir)):
            svc.run_all(req[1:])        # fresh points: batcher.eval runs
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    events = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in instrument.SPAN_NAMES:
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns, (plane.name, i)))
    return events, trace_mod.profile_start_ns(pd)


def test_spans_on_the_profiler_timeline_match_the_ring(tmp_path):
    """Every program span of a service run is in the .xplane.pb host plane,
    as often as in the ring, nested as in the ring, starting at the ring's
    time less one constant (the session's start) and lasting as long."""
    _enabled()
    events, start_ns = _profiled_service_run(tmp_path)
    assert start_ns is not None
    t0 = start_ns / 1e3
    ring = [s for s in obs.tracer().spans()
            if s["name"] in PROFILED and s["ts_us"] >= t0]
    for name in PROFILED:
        got = sorted(events.get(name, []))
        want = sorted((s for s in ring if s["name"] == name),
                      key=lambda s: s["ts_us"])
        assert got and len(got) == len(want), name
        for (s, e, _), r in zip(got, want):
            dur_ns = r["dur_us"] * 1e3
            assert abs((e - s) - dur_ns) <= max(0.1 * dur_ns, 1e6), name
            assert abs(s - (r["ts_us"] * 1e3 - start_ns)) <= 1e6, name
    parents = {r["name"]: r["parent"] for r in ring if "parent" in r}
    for child, parent in parents.items():
        outer = events[parent]
        for s, e, line in events[child]:
            assert any(ps <= s and e <= pe and pl == line
                       for ps, pe, pl in outer), (child, parent)
    assert parents["batcher.aggregate"] == "batcher.dispatch"
    assert parents["search.eval"] == "search.step"


@pytest.mark.parametrize("on", [True, False])
def test_profiler_spans_follow_the_switch_alone(tmp_path, on):
    """``obs.enable(trace=False)`` installs no ring, and the profiler still
    gets every span; ``obs.disable()`` leaves none in it."""
    obs_state.tracer = None             # as in a process that never traced
    if on:
        obs.enable(trace=False)
    events, _ = _profiled_service_run(tmp_path)
    assert obs.tracer() is None
    for name in PROFILED:
        assert bool(events.get(name)) is on, name


# ---------------------------------------------------------------------------
# Outcome summary + docs catalog sync.
# ---------------------------------------------------------------------------
def test_outcome_summary_renders_telemetry():
    req = api.SearchRequest(workload="ncf", env=ECFG, eps=20, seed=3,
                            method="random")
    plain = api.run_search(req)
    text = plain.summary()
    assert "method=random" in text and "seed=3" in text
    assert f"best_value={plain.best_value:.6g}" in text
    assert "telemetry" not in text
    _enabled()
    traced = api.run_search(req)
    text = traced.summary()
    assert "telemetry: " in text and "hard_evals=20" in text


def test_docs_document_every_metric_and_span():
    doc = open(os.path.join(REPO, "docs", "observability.md")).read()
    for name in instrument.METRIC_NAMES:
        assert f"`{name}`" in doc, f"{name} missing from docs/observability.md"
    for name in instrument.SPAN_NAMES:
        assert f"`{name}`" in doc, f"{name} missing from docs/observability.md"
