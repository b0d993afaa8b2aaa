"""Search-as-a-service throughput: concurrent multiplexing vs serial dispatch.

A fleet of "users" submits the SAME kind of traffic a deployed ConfuciuX
endpoint would see: a mix of methods over a couple of popular workloads,
with some users submitting identical queries (resubmissions / defaults).
Since the chunked-GA/SA work, the mix includes ``ga`` and ``sa`` -- GA
populations are the largest eval batches in the system and now route
through the cross-request batcher like everyone else.  We measure:

  * serial    -- ``api.run_search`` over the requests one after another,
                 every search driving its own jit-dispatch loop (the PR-1
                 deployment story);
  * service   -- the same requests through :class:`SearchService` with the
                 single-thread fused dispatcher (the PR-3 configuration);
  * service (pool) -- the same service with ``dispatch_workers > 1``: up to
                 N fused dispatches execute concurrently;
  * persistent restart -- a service with ``cache_dir`` set runs the mix
                 cold (writing cache shards), closes, and a FRESH service
                 on the same directory reruns it: the warm-restart wave
                 must evaluate zero fresh points (100% hit rate straight
                 from disk) while staying bit-identical.

Every outcome is asserted bit-identical across all paths (the service is an
execution strategy, not an approximation).  Reported: wall-clock speedup,
searches/sec, cache hit rate, and batcher fusion stats.  A warm wave (the
same traffic again) shows the steady-state regime where the cache has
saturated the popular workloads' point space.

A final *telemetry probe* wave re-runs reinforce/ga/nsga2/relaxed through
the service with ``repro.obs`` enabled: each outcome's flight-recorder
summary lands in the results JSON, the span trace is written to
``results/search_service_trace.jsonl`` and the metrics registry to
``results/search_service_metrics.prom`` (the artifacts
``tools/check_telemetry.py`` validates in CI).  The timed phases above run
with telemetry off, so the headline numbers measure the un-instrumented
fast path.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmarks import common
from repro import api, obs
from repro.serving import SearchService, ServiceConfig

POOL_WORKERS = 2  # sized for the 2-core dev container; raise on real hosts


def _mix(eps: int, n_users: int):
    """n_users requests: methods x workloads round-robin, 2 users/seed."""
    workloads = ("ncf", "mobilenet_v2")
    methods = ("random", "grid", "bo", "ga", "sa", "random", "ga", "sa")
    reqs = []
    for u in range(n_users):
        method = methods[u % len(methods)]
        reqs.append(api.SearchRequest(
            workload=workloads[u % 2],
            env=api.EnvConfig(platform="cloud"),
            eps=eps, seed=u // 2,             # 2 users share each seed
            method=method,
            options={"population": 50} if method == "ga" else {}))
    return reqs


def _assert_identical(serial, outs, exact):
    for a, b in zip(serial, outs):
        if exact:
            assert a.best_value == b.best_value, \
                (a.method, a.best_value, b.best_value)
            assert np.array_equal(a.history, b.history), a.method
        else:
            np.testing.assert_allclose(a.best_value, b.best_value, rtol=1e-5)


def _telemetry_probe(eps: int):
    """Instrumented wave: the chunked-engine quartet through the service.

    Returns (per-method telemetry summaries, trace path, metrics path,
    metrics snapshot) and leaves the artifacts in ``results/`` for
    ``tools/check_telemetry.py``.
    """
    os.makedirs(common.RESULTS_DIR, exist_ok=True)
    trace_path = os.path.join(common.RESULTS_DIR,
                              "search_service_trace.jsonl")
    obs.reset()
    obs.enable(trace=True, jsonl_path=trace_path)
    reqs = [api.SearchRequest(workload="ncf",
                              env=api.EnvConfig(platform="cloud"),
                              eps=eps, seed=0, method=m)
            for m in ("reinforce", "ga", "nsga2", "relaxed")]
    with SearchService(ServiceConfig(max_workers=4)) as svc:
        outs = svc.run_all(reqs)
    telemetry = {o.method: o.telemetry for o in outs}
    for m, t in telemetry.items():
        assert t is not None and t.get("hard_evals", 0) > 0, (m, t)
    prom_path = common.write_metrics_prom("search_service_metrics")
    snapshot = obs.REGISTRY.snapshot()
    obs.tracer().close()   # the JSONL sink already streamed every span
    obs.disable()
    common.print_table(
        "Telemetry probe (instrumented service wave)",
        ["method", "hard evals", "chunks", "cache hit rate", "jit compiles",
         "jit s"],
        [[m, t.get("hard_evals"), t.get("chunks"),
          t.get("cache_hit_rate"), t.get("jit_compiles", 0),
          t.get("jit_s", {}).get("sum", 0.0)]
         for m, t in telemetry.items()])
    return telemetry, trace_path, prom_path, snapshot


def run(budget_name: str = "quick") -> dict:
    eps = 400 if budget_name == "quick" else 2000
    n_users = 8 if budget_name == "quick" else 16
    reqs = _mix(eps, n_users)

    with common.Timer() as t_serial:
        serial = [api.run_search(r) for r in reqs]

    # CPU/GPU route the batcher through the jnp oracle -> bit-exact parity.
    # On TPU the batcher evaluates on the Pallas kernel while serial
    # random/grid/bo/sa evaluate on the jnp model; the two agree to float32
    # allclose (1.2e-7 relative at most on a v5e), not bit for bit, so the
    # parity assertion relaxes accordingly.
    import jax

    exact = jax.default_backend() != "tpu"

    svc = SearchService(ServiceConfig(max_workers=n_users))
    with common.Timer() as t_cold:
        cold = svc.run_all(_mix(eps, n_users))
    stats_cold = svc.stats()
    with common.Timer() as t_warm:
        warm = svc.run_all(_mix(eps, n_users))
    stats_warm = svc.stats()
    svc.close()
    _assert_identical(serial, cold, exact)
    _assert_identical(serial, warm, exact)

    pool = SearchService(ServiceConfig(max_workers=n_users,
                                       dispatch_workers=POOL_WORKERS))
    with common.Timer() as t_pool_cold:
        pool_cold = pool.run_all(_mix(eps, n_users))
    stats_pool_cold = pool.stats()
    with common.Timer() as t_pool_warm:
        pool_warm = pool.run_all(_mix(eps, n_users))
    stats_pool = pool.stats()
    pool.close()
    _assert_identical(serial, pool_cold, exact)
    _assert_identical(serial, pool_warm, exact)

    # Persistent-cache restart: same mix, cold service writes shards on
    # close; a brand-new service on the same cache_dir serves the whole
    # rerun from disk (zero fresh evaluations, still bit-identical).
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="repro-bench-cache-")
    pers1 = SearchService(ServiceConfig(max_workers=n_users,
                                        cache_dir=cache_dir))
    with common.Timer() as t_pers_cold:
        pers_cold = pers1.run_all(_mix(eps, n_users))
    stats_pers_cold = pers1.stats()
    pers1.close()
    pers2 = SearchService(ServiceConfig(max_workers=n_users,
                                        cache_dir=cache_dir))
    with common.Timer() as t_pers_warm:
        pers_warm = pers2.run_all(_mix(eps, n_users))
    stats_pers_warm = pers2.stats()
    pers2.close()
    _assert_identical(serial, pers_cold, exact)
    _assert_identical(serial, pers_warm, exact)
    assert stats_pers_warm["cache_misses"] == 0, \
        f"warm restart missed {stats_pers_warm['cache_misses']} points"

    def warm_rate(warm_stats, cold_stats):
        hits = warm_stats["cache_hits"] - cold_stats["cache_hits"]
        misses = warm_stats["cache_misses"] - cold_stats["cache_misses"]
        return hits / max(hits + misses, 1)

    rows = [
        ["serial", t_serial.seconds, 1.0, n_users / t_serial.seconds, None],
        ["service (cold cache)", t_cold.seconds,
         t_serial.seconds / t_cold.seconds, n_users / t_cold.seconds,
         stats_cold["cache_hit_rate"]],
        ["service (warm cache)", t_warm.seconds,
         t_serial.seconds / t_warm.seconds, n_users / t_warm.seconds,
         warm_rate(stats_warm, stats_cold)],
        [f"pool x{POOL_WORKERS} (cold cache)", t_pool_cold.seconds,
         t_serial.seconds / t_pool_cold.seconds,
         n_users / t_pool_cold.seconds, stats_pool_cold["cache_hit_rate"]],
        [f"pool x{POOL_WORKERS} (warm cache)", t_pool_warm.seconds,
         t_serial.seconds / t_pool_warm.seconds,
         n_users / t_pool_warm.seconds,
         warm_rate(stats_pool, stats_pool_cold)],
        ["persistent (cold, writes shards)", t_pers_cold.seconds,
         t_serial.seconds / t_pers_cold.seconds,
         n_users / t_pers_cold.seconds, stats_pers_cold["cache_hit_rate"]],
        ["persistent (warm RESTART)", t_pers_warm.seconds,
         t_serial.seconds / t_pers_warm.seconds,
         n_users / t_pers_warm.seconds, stats_pers_warm["cache_hit_rate"]],
    ]
    common.print_table(
        f"Search service: {n_users} concurrent searches (incl. ga/sa), "
        f"eps={eps}, identical outcomes vs serial (asserted)",
        ["dispatch", "seconds", "speedup", "searches/sec", "cache hit rate"],
        rows)
    common.print_table(
        "Batcher fusion (cumulative)",
        ["config", "dispatches", "fused", "max fused reqs", "points",
         "fresh evals", "max concurrent"],
        [["single, cold", stats_cold["dispatches"],
          stats_cold["fused_dispatches"],
          stats_cold["max_items_per_dispatch"], stats_cold["points"],
          stats_cold["fresh_points"],
          stats_cold["max_concurrent_dispatches"]],
         ["single, cold+warm", stats_warm["dispatches"],
          stats_warm["fused_dispatches"],
          stats_warm["max_items_per_dispatch"], stats_warm["points"],
          stats_warm["fresh_points"],
          stats_warm["max_concurrent_dispatches"]],
         [f"pool x{POOL_WORKERS}, cold+warm", stats_pool["dispatches"],
          stats_pool["fused_dispatches"],
          stats_pool["max_items_per_dispatch"], stats_pool["points"],
          stats_pool["fresh_points"],
          stats_pool["max_concurrent_dispatches"]]])

    telemetry, trace_path, prom_path, metrics_snapshot = _telemetry_probe(
        eps)

    return {
        "n_users": n_users, "eps": eps,
        "telemetry_probe": telemetry,
        "trace_path": trace_path,
        "metrics_path": prom_path,
        "metrics": metrics_snapshot,
        "pool_workers": POOL_WORKERS,
        "serial_seconds": t_serial.seconds,
        "service_cold_seconds": t_cold.seconds,
        "service_warm_seconds": t_warm.seconds,
        "pool_cold_seconds": t_pool_cold.seconds,
        "pool_warm_seconds": t_pool_warm.seconds,
        "speedup_cold": t_serial.seconds / t_cold.seconds,
        "speedup_warm": t_serial.seconds / t_warm.seconds,
        "speedup_pool_cold": t_serial.seconds / t_pool_cold.seconds,
        "speedup_pool_warm": t_serial.seconds / t_pool_warm.seconds,
        "searches_per_sec_warm": n_users / t_warm.seconds,
        "searches_per_sec_pool_warm": n_users / t_pool_warm.seconds,
        "cache_hit_rate_cold": stats_cold["cache_hit_rate"],
        "cache_hit_rate_warm_wave": warm_rate(stats_warm, stats_cold),
        "persistent_cold_seconds": t_pers_cold.seconds,
        "persistent_warm_restart_seconds": t_pers_warm.seconds,
        "speedup_persistent_warm_restart":
            t_serial.seconds / t_pers_warm.seconds,
        "persistent_warm_restart_hit_rate":
            stats_pers_warm["cache_hit_rate"],
        "persistent_warm_restart_fresh_points":
            stats_pers_warm["fresh_points"],
        "persistent_entries_loaded": stats_pers_warm["cache_entries"],
        "persistent_shards_loaded": stats_pers_warm["cache_shards_loaded"],
        "max_concurrent_dispatches_pool":
            stats_pool["max_concurrent_dispatches"],
        "outcomes_identical": True,
        "stats_single": stats_warm,
        "stats_pool": stats_pool,
    }


if __name__ == "__main__":
    common.save_json("search_service", run())
