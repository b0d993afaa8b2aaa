"""Search service: serial parity, cache accounting, cancellation, batcher.

The load-bearing guarantee is EXACTNESS: a search routed through the
service -- cross-request fusion, per-point dedup and memo-cache hits
included -- returns bit-identical outcomes to the same ``api.run_search``
call executed serially.  Everything else (hit/miss bookkeeping, ticket
lifecycle, a cancelled request never stalling the batcher) is what makes
the service operable.
"""
import threading
import time

import numpy as np
import pytest

from repro import api
from repro.core import env as env_lib
from repro.serving import (BATCHED_METHODS, COSTS_BATCHED_METHODS,
                           RAW_BATCHED_METHODS, CostEvalBatcher,
                           CostMemoCache, PersistentCostCache,
                           SearchCancelled, SearchService, SearchTicket,
                           ServiceConfig)
from repro.serving.batcher import ROW_WIDTH

ECFG = env_lib.EnvConfig(platform="cloud")


def _req(method, eps=200, seed=0, wl="ncf", **kw):
    return api.SearchRequest(workload=wl, env=ECFG, eps=eps, seed=seed,
                             method=method, **kw)


@pytest.fixture
def svc():
    s = SearchService(ServiceConfig(max_workers=4,
                                    default_progress_every=50))
    yield s
    s.close()


# ---------------------------------------------------------------------------
# Exact parity with serial dispatch.
# ---------------------------------------------------------------------------
def test_concurrent_batched_methods_identical_to_serial(svc):
    """random/grid/bo through the fused batcher == serial, bit for bit."""
    reqs = [_req(m, eps=200, seed=3) for m in ("random", "grid", "bo")]
    serial = [api.run_search(_req(m, eps=200, seed=3))
              for m in ("random", "grid", "bo")]
    tickets = [svc.submit(r) for r in reqs]
    for t, want in zip(tickets, serial):
        got = t.result(timeout=300)
        assert got.best_value == want.best_value
        assert got.history.tobytes() == want.history.tobytes()
        np.testing.assert_array_equal(got.pe, want.pe)
        np.testing.assert_array_equal(got.kt, want.kt)
    assert svc.stats()["completed"] == 3
    # The fused path actually ran: points flowed through the batcher.
    assert svc.stats()["points"] > 0


def test_chunked_engine_identical_to_serial(svc):
    """reinforce multiplexes at chunk granularity, still bit-identical."""
    want = api.run_search(_req("reinforce", eps=60, seed=7))
    got = svc.submit(_req("reinforce", eps=60, seed=7)).result(timeout=300)
    assert got.best_value == pytest.approx(want.best_value)
    np.testing.assert_allclose(got.history, want.history)


def test_ga_sa_through_service_byte_identical_to_serial(svc):
    """ga/sa route their fitness through the fused batcher (raw eval_fn);
    outcomes must equal the serial in-graph runs byte for byte."""
    cases = [("ga", {"population": 40}), ("sa", {})]
    serial = [api.run_search(_req(m, eps=200, seed=3, options=dict(o)))
              for m, o in cases]
    points_before = svc.stats()["points"]
    tickets = [svc.submit(_req(m, eps=200, seed=3, options=dict(o)))
               for m, o in cases]
    for t, want in zip(tickets, serial):
        got = t.result(timeout=300)
        assert got.best_value == want.best_value
        assert got.history.tobytes() == want.history.tobytes()
        np.testing.assert_array_equal(got.pe, want.pe)
        np.testing.assert_array_equal(got.kt, want.kt)
    # The fused path actually ran: GA/SA points flowed through the batcher.
    assert svc.stats()["points"] > points_before


def test_dispatch_pool_byte_identical_to_single_thread():
    """A multi-worker dispatch pool returns the same bytes as one thread."""
    reqs = [("random", {}), ("ga", {"population": 30}), ("sa", {}),
            ("grid", {})]
    outs = {}
    for workers in (1, 3):
        svc = SearchService(ServiceConfig(max_workers=4,
                                          dispatch_workers=workers))
        try:
            outs[workers] = svc.run_all(
                [_req(m, eps=200, seed=2, options=dict(o)) for m, o in reqs])
            assert svc.stats()["dispatch_workers"] == workers
        finally:
            svc.close()
    for a, b in zip(outs[1], outs[3]):
        assert a.best_value == b.best_value
        assert a.history.tobytes() == b.history.tobytes()
        np.testing.assert_array_equal(a.pe, b.pe)
        np.testing.assert_array_equal(a.kt, b.kt)


def test_same_seed_concurrent_duplicates_agree(svc):
    """Identical queries racing each other return identical outcomes."""
    tickets = [svc.submit(_req("random", eps=300, seed=5)) for _ in range(4)]
    outs = [t.result(timeout=300) for t in tickets]
    for o in outs[1:]:
        assert o.best_value == outs[0].best_value
        assert o.history.tobytes() == outs[0].history.tobytes()


def test_run_all_preserves_request_order(svc):
    outs = svc.run_all([_req("random", eps=150, seed=s) for s in range(3)])
    assert [o.seed for o in outs] == [0, 1, 2]
    assert all(o.method == "random" for o in outs)


# Which eval_fn the service injects into each registered method.
ROUTES = {"random": "genome", "grid": "genome", "bo": "genome",
          "ga": "raw", "sa": "raw", "relaxed": "raw", "nsga2": "costs",
          "reinforce": None, "two_stage": None, "a2c": None, "ppo2": None,
          "fanout": None, "dist_reinforce": None}


@pytest.mark.parametrize("method", api.registry.list_optimizers())
def test_instrument_routes_each_method(svc, method, monkeypatch):
    """``_instrument`` injects the genome-level, raw or costs ``eval_fn``
    by the module constants, and none where a method is in none of them."""
    for maker, kind in (("_make_eval_fn", "genome"),
                        ("_make_raw_eval_fn", "raw"),
                        ("_make_costs_eval_fn", "costs")):
        monkeypatch.setattr(svc, maker, lambda ticket, kind=kind: kind)
    req = svc._instrument(SearchTicket(0, _req(method)))
    assert req.options.get("eval_fn") == ROUTES[method]
    assert sum(method in t for t in (BATCHED_METHODS, RAW_BATCHED_METHODS,
                                     COSTS_BATCHED_METHODS)) <= 1


# ---------------------------------------------------------------------------
# Cache accounting.
# ---------------------------------------------------------------------------
def test_cache_hit_miss_accounting_is_consistent(svc):
    svc.submit(_req("random", eps=200, seed=1)).result(timeout=300)
    s1 = svc.stats()
    # Every unique point was either a hit or a fresh (miss) evaluation.
    assert s1["cache_hits"] + s1["cache_misses"] == s1["unique_points"]
    assert s1["cache_misses"] == s1["fresh_points"] > 0
    assert s1["cache_entries"] == s1["cache_misses"]  # nothing evicted

    # Resubmitting the identical query evaluates NOTHING fresh.
    svc.submit(_req("random", eps=200, seed=1)).result(timeout=300)
    s2 = svc.stats()
    assert s2["cache_misses"] == s1["cache_misses"]
    assert s2["fresh_points"] == s1["fresh_points"]
    assert s2["cache_hits"] > s1["cache_hits"]
    assert s2["cache_hit_rate"] > s1["cache_hit_rate"]


def test_cache_shared_across_objectives():
    """The point key excludes the objective: latency and energy users on
    the same workload reuse each other's evaluations."""
    svc = SearchService(ServiceConfig(max_workers=2))
    try:
        svc.submit(_req("random", eps=200, seed=2)).result(timeout=300)
        misses = svc.stats()["cache_misses"]
        env2 = env_lib.EnvConfig(platform="cloud", objective="energy",
                                 constraint="power")
        svc.submit(api.SearchRequest(workload="ncf", env=env2, eps=200,
                                     seed=2, method="random")
                   ).result(timeout=300)
        assert svc.stats()["cache_misses"] == misses  # same points, 0 fresh
    finally:
        svc.close()


def test_cache_lru_eviction_accounting():
    cache = CostMemoCache(capacity=4)
    keys = [bytes([i]) for i in range(6)]
    vals = np.arange(24, dtype=np.float32).reshape(6, 4)
    cache.put_many(keys, list(vals))
    assert len(cache) == 4 and cache.evictions == 2
    values, miss = cache.get_many(keys)
    assert miss == [0, 1]                      # oldest two evicted
    np.testing.assert_array_equal(values[5], vals[5])
    assert cache.hits == 4 and cache.misses == 2


def test_cache_rejects_bad_capacity():
    with pytest.raises(ValueError, match="capacity"):
        CostMemoCache(capacity=0)


# ---------------------------------------------------------------------------
# Cancellation.
# ---------------------------------------------------------------------------
def test_cancel_mid_stream_chunked_engine(svc):
    got = []
    t = svc.submit(_req("reinforce", eps=100000, on_progress=got.append,
                        progress_every=10))
    deadline = time.time() + 120
    while not got and time.time() < deadline:
        time.sleep(0.02)
    assert got, "no progress streamed before deadline"
    t.cancel()
    with pytest.raises(SearchCancelled):
        t.result(timeout=120)
    assert t.status == "cancelled"
    assert svc.stats()["cancelled"] == 1


@pytest.mark.parametrize("method,opts,chunk_samples", [
    ("ga", {"population": 50}, 100),   # progress_every=100 -> 2-gen chunks
    ("sa", {}, 100),                   # progress_every=100 -> 100-step chunks
])
def test_cancel_ga_sa_within_one_chunk(svc, method, opts, chunk_samples):
    """GA/SA cancel at chunk granularity now, not at run end: submit an
    effectively unbounded search, cancel after the first progress event,
    and require the engine to stop within one further chunk."""
    eps = 10_000_000
    got = []
    t = svc.submit(_req(method, eps=eps, on_progress=got.append,
                        progress_every=chunk_samples, options=dict(opts)))
    deadline = time.time() + 120
    while not got and time.time() < deadline:
        time.sleep(0.02)
    assert got, "no progress streamed before deadline"
    t.cancel()
    # Baseline AFTER cancel(): the flag is set, so the engine can append at
    # most the in-flight chunk plus one boundary that races the flag.
    # (Reading before cancel() would let a main-thread stall between the
    # read and the cancel inflate the gap and flake the bound.)
    at_cancel = t.trials[-1].step
    with pytest.raises(SearchCancelled):
        t.result(timeout=120)
    assert t.status == "cancelled"
    # Stopped within one chunk of the cancel (+ one chunk of slack for a
    # boundary that races the cancel flag) -- nowhere near the 10M-sample
    # budget the old run-to-completion engines would have burned.
    last = t.trials[-1].step
    assert last <= at_cancel + 2 * chunk_samples


def test_cancelled_request_does_not_stall_batcher(svc):
    """Cancel a batched-method request mid-flight; the batcher keeps
    serving everyone else and fresh requests still complete."""
    victim = svc.submit(_req("random", eps=500000, seed=9))
    survivor = svc.submit(_req("random", eps=200, seed=4))
    deadline = time.time() + 120
    while svc.stats()["dispatches"] == 0 and time.time() < deadline:
        time.sleep(0.02)
    victim.cancel()
    with pytest.raises(SearchCancelled):
        victim.result(timeout=120)
    want = api.run_search(_req("random", eps=200, seed=4))
    got = survivor.result(timeout=120)
    assert got.best_value == want.best_value
    late = svc.submit(_req("grid", eps=150, seed=1)).result(timeout=120)
    assert late.eps == 150
    assert svc.stats()["cancelled"] == 1
    assert svc.stats()["completed"] == 2


def test_cancel_while_queued_never_runs():
    """A ticket cancelled before a worker picks it up is never executed."""
    svc = SearchService(ServiceConfig(max_workers=1))
    try:
        blocker = svc.submit(_req("random", eps=2000, seed=0))
        queued = svc.submit(_req("random", eps=150, seed=1))
        queued.cancel()          # still waiting behind the 1-worker pool
        with pytest.raises(SearchCancelled):
            queued.result(timeout=300)
        assert queued.status == "cancelled"
        assert blocker.result(timeout=300).feasible
        s = svc.stats()
        assert s["cancelled"] == 1 and s["completed"] == 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Ticket / service lifecycle.
# ---------------------------------------------------------------------------
def test_failed_request_reports_error_not_hang(svc):
    t = svc.submit(_req("random", eps=100, wl="no_such_workload"))
    with pytest.raises(Exception, match="no_such_workload"):
        t.result(timeout=120)
    assert t.status == "failed"
    assert svc.stats()["failed"] == 1


def test_progress_recorded_on_ticket(svc):
    t = svc.submit(_req("reinforce", eps=60))
    t.result(timeout=300)
    steps = [tr.step for tr in t.trials]
    assert steps and steps == sorted(steps) and steps[-1] == 60


def test_closed_service_rejects_submissions():
    svc = SearchService(ServiceConfig(max_workers=1))
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(_req("random"))


def test_submit_vs_close_race_every_ticket_terminates():
    """Hammer submit() from several threads while close() runs.  Every
    ticket submit() RETURNED must terminate -- the old unlocked _closed
    check could count a ticket, hit the shut-down pool's RuntimeError and
    leave result() blocking forever."""
    svc = SearchService(ServiceConfig(max_workers=2))
    tickets: list = []
    tlock = threading.Lock()
    stop = threading.Event()

    def spam():
        while not stop.is_set():
            try:
                t = svc.submit(_req("random", eps=30, seed=1))
            except RuntimeError:
                return          # service closed: the legal rejection path
            with tlock:
                tickets.append(t)

    threads = [threading.Thread(target=spam) for _ in range(4)]
    for th in threads:
        th.start()
    time.sleep(0.05)            # let submissions overlap the close
    svc.close()
    stop.set()
    for th in threads:
        th.join(timeout=60)
        assert not th.is_alive()
    assert tickets, "race window produced no accepted submissions"
    for t in tickets:
        assert t.done(), f"ticket {t.uid} leaked: status={t.status}"
        assert t.status in ("done", "failed", "cancelled")
        try:
            t.result(timeout=1)     # must never block post-close
        except Exception:  # noqa: BLE001 -- failed/cancelled is fine
            pass
    # Conservation: every accepted ticket finished exactly one way.
    s = svc.stats()
    assert s["submitted"] == len(tickets)
    assert s["completed"] + s["failed"] + s["cancelled"] == len(tickets)


def test_queued_cancel_finishes_without_waiting_for_worker():
    """cancel() on a still-queued ticket resolves IMMEDIATELY -- not when
    the saturated pool finally dequeues work it will only throw away."""
    svc = SearchService(ServiceConfig(max_workers=1,
                                      default_progress_every=50))
    try:
        blocker = svc.submit(_req("reinforce", eps=10_000_000))
        queued = svc.submit(_req("random", eps=150, seed=1))
        t0 = time.time()
        queued.cancel()
        with pytest.raises(SearchCancelled):
            queued.result(timeout=5)
        assert time.time() - t0 < 5.0
        assert queued.status == "cancelled" and queued.done()
        # The proof we didn't wait: the worker is still busy with the
        # effectively-unbounded blocker.
        assert not blocker.done()
        blocker.cancel()
        with pytest.raises(SearchCancelled):
            blocker.result(timeout=120)
        s = svc.stats()
        assert s["cancelled"] == 2 and s["completed"] == 0
    finally:
        svc.close()


def test_result_error_isolated_per_caller(svc):
    """Concurrent result() callers each raise their OWN exception object:
    re-raising one shared instance would let the callers mutate each
    other's __traceback__ mid-flight."""
    t = svc.submit(_req("random", eps=50, wl="no_such_workload"))
    caught = []
    clock = threading.Lock()

    def grab():
        try:
            t.result(timeout=120)
        except Exception as e:  # noqa: BLE001 -- the point of the test
            with clock:
                caught.append(e)

    threads = [threading.Thread(target=grab) for _ in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert len(caught) == 2
    e1, e2 = caught
    assert e1 is not e2                      # per-caller copies ...
    assert e1 is not t._error and e2 is not t._error
    assert type(e1) is type(t._error) and e1.args == t._error.args
    assert e1.__cause__ is t._error          # ... chained to the original,
    assert e2.__cause__ is t._error          # whose traceback stays pinned
    assert "no_such_workload" in str(e1)


def test_batcher_close_fails_pending_when_dispatch_hangs():
    """A dispatch thread hung inside _dispatch must not turn close() into
    a silent strand: still-queued evaluations get a RuntimeError and the
    leak is reported in stats."""
    b = CostEvalBatcher(window_ms=0.0, dispatch_workers=1,
                        join_timeout_s=0.2)
    entered = threading.Event()
    release = threading.Event()

    def stuck_dispatch(items):
        entered.set()
        release.wait(60)            # simulates a wedged device dispatch
        for it in items:
            it.error = RuntimeError("released")
            it.event.set()

    b._dispatch = stuck_dispatch
    errs = {}

    def submit(name):
        try:
            b.evaluate(np.ones((1, 8), np.float32),
                       np.ones((1, 1), np.float32),
                       np.ones((1, 1), np.float32), np.float32(0), ECFG,
                       np.float32(1.0))
        except BaseException as e:  # noqa: BLE001
            errs[name] = e

    ta = threading.Thread(target=submit, args=("hung",))
    ta.start()
    assert entered.wait(timeout=60)      # dispatcher is now wedged
    tb = threading.Thread(target=submit, args=("stranded",))
    tb.start()
    deadline = time.time() + 60
    while time.time() < deadline:        # wait for b's item to queue up
        with b._cv:
            if b._pending:
                break
        time.sleep(0.005)
    b.close()
    assert b.stats()["leaked_dispatch_threads"] == 1
    tb.join(timeout=60)
    assert isinstance(errs["stranded"], RuntimeError)
    assert "hung dispatch" in str(errs["stranded"])
    release.set()                        # unwedge; the hung item resolves
    ta.join(timeout=60)
    assert "released" in str(errs["hung"])


def test_batcher_clean_close_reports_zero_leaks():
    b = CostEvalBatcher(dispatch_workers=2)
    b.close()
    assert b.stats()["leaked_dispatch_threads"] == 0


# ---------------------------------------------------------------------------
# Persistent cost cache.
# ---------------------------------------------------------------------------
def test_persistent_cache_round_trip(tmp_path):
    """Entries written by one cache incarnation are served by the next:
    flush on close, vectorized reload on open, 100% hit rate."""
    d = str(tmp_path / "cache")
    keys = [np.arange(i, i + 3, dtype=np.float32).tobytes()
            for i in range(10)]
    vals = [np.arange(4, dtype=np.float32) + i for i in range(10)]
    c = PersistentCostCache(d, version="v1", flush_every=1000)
    c.put_many(keys, vals)
    assert c.stats()["pending_flush"] == 10      # buffered, not yet on disk
    c.close()
    assert c.stats()["pending_flush"] == 0 and c.persisted == 10

    c2 = PersistentCostCache(d, version="v1")
    assert len(c2) == 10 and c2.shards_loaded == 1
    values, miss = c2.get_many(keys)
    assert miss == [] and c2.hit_rate == 1.0
    for v, want in zip(values, vals):
        np.testing.assert_array_equal(v, want)

    # Re-inserting loaded entries is not "fresh": nothing new flushes.
    c2.put_many(keys, vals)
    assert c2.stats()["pending_flush"] == 0
    c2.close()


def test_persistent_cache_version_invalidates(tmp_path):
    """The version namespace is the directory: a cost-model edit opens an
    empty store instead of serving stale tuples."""
    d = str(tmp_path / "cache")
    keys = [bytes([i, i + 1]) for i in range(4)]
    vals = [np.full(4, i, np.float32) for i in range(4)]
    c = PersistentCostCache(d, version="model-a")
    c.put_many(keys, vals)
    c.close()
    other = PersistentCostCache(d, version="model-b")
    assert len(other) == 0 and other.shards_loaded == 0
    _, miss = other.get_many(keys)
    assert miss == list(range(4))
    other.close()


def test_persistent_cache_skips_corrupt_shards(tmp_path):
    import os

    d = str(tmp_path / "cache")
    keys = [bytes([i, i, i]) for i in range(6)]
    vals = [np.full(4, float(i), np.float32) for i in range(6)]
    c = PersistentCostCache(d, version="v1")
    c.put_many(keys[:3], vals[:3])
    c.flush()
    c.put_many(keys[3:], vals[3:])
    c.flush()
    c.close()
    shard_dir = os.path.join(d, "v1")
    shards = sorted(n for n in os.listdir(shard_dir) if n.endswith(".bin"))
    assert len(shards) == 2
    # Truncate one shard mid-body and drop in one garbage file.
    victim = os.path.join(shard_dir, shards[0])
    with open(victim, "rb") as f:
        blob = f.read()
    with open(victim, "wb") as f:
        f.write(blob[:-5])
    with open(os.path.join(shard_dir, "shard-999-000000.bin"), "wb") as f:
        f.write(b"not a shard at all")

    c2 = PersistentCostCache(d, version="v1")
    assert c2.corrupt_shards == 2
    assert c2.shards_loaded == 1 and len(c2) == 3    # survivors still serve
    values, miss = c2.get_many(keys)
    assert len(miss) == 3
    for i in (3, 4, 5):
        np.testing.assert_array_equal(values[i], vals[i])
    c2.close()


def test_service_warm_restart_serves_fully_from_disk(tmp_path):
    """ServiceConfig.cache_dir end to end: a restarted service re-runs the
    same query with ZERO fresh evaluations and identical bytes."""
    d = str(tmp_path / "cache")
    svc1 = SearchService(ServiceConfig(max_workers=2, cache_dir=d))
    try:
        want = svc1.submit(_req("random", eps=200, seed=5)).result(
            timeout=300)
        s1 = svc1.stats()
        assert s1["fresh_points"] > 0
        assert isinstance(svc1.cache, PersistentCostCache)
    finally:
        svc1.close()          # final flush happens here
    assert s1["fresh_points"] >= 0

    svc2 = SearchService(ServiceConfig(max_workers=2, cache_dir=d))
    try:
        assert len(svc2.cache) > 0               # warm from disk
        got = svc2.submit(_req("random", eps=200, seed=5)).result(
            timeout=300)
        s2 = svc2.stats()
        assert s2["cache_misses"] == 0 and s2["fresh_points"] == 0
        assert s2["cache_hit_rate"] == 1.0       # 100% warm
        assert got.best_value == want.best_value
        assert got.history.tobytes() == want.history.tobytes()
        np.testing.assert_array_equal(got.pe, want.pe)
        np.testing.assert_array_equal(got.kt, want.kt)
    finally:
        svc2.close()


# ---------------------------------------------------------------------------
# Batcher internals.
# ---------------------------------------------------------------------------
def test_batcher_direct_matches_genome_cost():
    """CostEvalBatcher.evaluate == the serial jitted genome evaluation."""
    from repro.core.baselines import _decode_and_eval
    import jax
    import jax.numpy as jnp

    wl = api.SearchRequest(workload="ncf", env=ECFG).resolve_workload()
    env = env_lib.make_env(wl, ECFG)
    rng = np.random.default_rng(0)
    g = rng.integers(0, ECFG.levels, size=(64, env.num_layers, 2))
    want, _, _ = jax.jit(lambda g: _decode_and_eval(env, ECFG, g))(
        jnp.asarray(g))
    pe = np.asarray(env.pe_table)[g[..., 0]]
    kt = np.asarray(env.kt_table)[g[..., 1]]
    b = CostEvalBatcher(window_ms=0.0)
    try:
        got = b.evaluate(np.asarray(env.layers), pe, kt,
                         np.float32(ECFG.dataflow), ECFG,
                         np.float32(env.budget))
        assert got.tobytes() == np.asarray(want).tobytes()
        # A second identical call is served fully from cache -- still exact.
        again = b.evaluate(np.asarray(env.layers), pe, kt,
                           np.float32(ECFG.dataflow), ECFG,
                           np.float32(env.budget))
        assert again.tobytes() == got.tobytes()
        assert b.stats()["fresh_points"] == b.stats()["cache_misses"]
    finally:
        b.close()


def test_batcher_point_row_width_covers_all_fields():
    from repro.costmodel.layers import NUM_FIELDS

    assert ROW_WIDTH == NUM_FIELDS + 3  # fields + pe + kt + df


def test_closed_batcher_rejects_evaluations():
    b = CostEvalBatcher()
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.evaluate(np.ones((1, 8), np.float32), np.ones((1, 1), np.float32),
                   np.ones((1, 1), np.float32), np.float32(0), ECFG,
                   np.float32(1.0))


# ---------------------------------------------------------------------------
# Multi-objective (nsga2) through the service.
# ---------------------------------------------------------------------------
def test_nsga2_through_service_byte_identical_to_serial(svc):
    """nsga2 routes (b, 4)-cost batches through evaluate_costs; outcomes
    (history, assignment AND frontier) equal the serial run byte for byte."""
    opt = {"population": 15}
    want = api.run_search(_req("nsga2", eps=120, seed=3, options=dict(opt)))
    got = svc.submit(_req("nsga2", eps=120, seed=3,
                          options=dict(opt))).result(timeout=300)
    assert got.best_value == want.best_value
    assert got.history.tobytes() == want.history.tobytes()
    np.testing.assert_array_equal(got.pe, want.pe)
    np.testing.assert_array_equal(got.kt, want.kt)
    for k in ("lat", "en", "area", "pw"):
        np.testing.assert_array_equal(got.frontier[k], want.frontier[k])
    assert svc.stats()["points"] > 0


def test_evaluate_costs_matches_local_eval_and_shares_cache():
    """Batcher evaluate_costs == the serial make_local_costs_eval bytes,
    and its per-point cache entries are shared with scalar evaluate()."""
    from repro.costmodel import workloads
    from repro.serving.batcher import make_local_costs_eval

    env = env_lib.make_env(workloads.get_workload("ncf"), ECFG)
    layers = np.asarray(env.layers, np.float32)
    rng = np.random.default_rng(0)
    b, N = 9, env.num_layers
    pe = env.pe_table[rng.integers(0, 12, (b, N))].astype(np.float32)
    kt = env.kt_table[rng.integers(0, 12, (b, N))].astype(np.float32)
    df = np.full((b, N), ECFG.dataflow, np.float32)

    bat = CostEvalBatcher()
    try:
        costs = bat.evaluate_costs(layers, pe, kt, df, ECFG,
                                   float(env.budget))
        assert costs.shape == (b, 4)
        local = make_local_costs_eval(env, ECFG)
        np.testing.assert_array_equal(costs,
                                      np.asarray(local(pe, kt, df)))
        # Scalar fitness over the same points: all cache hits, zero fresh.
        misses = bat.cache.misses
        fit = bat.evaluate(layers, pe, kt, df, ECFG, float(env.budget))
        assert bat.cache.misses == misses
        # And the scalar view agrees with the multi view's objective.
        feasible = np.isfinite(fit)
        np.testing.assert_array_equal(fit[feasible], costs[feasible, 0])
    finally:
        bat.close()


def test_cache_keys_never_collide_across_workloads():
    """Two different layer descriptors with the SAME (pe, kt, df) must
    occupy distinct cache entries -- the key covers the full point row."""
    from repro.costmodel import layers_to_array
    from repro.costmodel.layers import LayerSpec
    from repro.serving.batcher import pack_point_rows

    a = layers_to_array([LayerSpec.gemm(64, 64, 64)])
    c = layers_to_array([LayerSpec.conv(16, 16, 14, 14, 3, 3)])
    pe = np.asarray([[32.0]], np.float32)
    kt = np.asarray([[4.0]], np.float32)
    df = np.asarray([[0.0]], np.float32)
    rows_a = pack_point_rows(a, pe, kt, df)
    rows_c = pack_point_rows(c, pe, kt, df)
    assert rows_a.tobytes() != rows_c.tobytes()

    bat = CostEvalBatcher()
    try:
        budget = 1e18
        fa = bat.evaluate(a, pe, kt, df, ECFG, budget)
        fc = bat.evaluate(c, pe, kt, df, ECFG, budget)
        assert len(bat.cache) == 2          # one entry per distinct row
        assert bat.cache.misses == 2        # no cross-workload hit
        assert fa[0] != fc[0]               # and genuinely different costs
    finally:
        bat.close()
