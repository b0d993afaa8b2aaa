"""Cross-request cost-eval batcher: one dispatch stream for N searches.

Concurrent searches running on worker threads each produce host-side batches
of genome evaluations (random/grid/bo route their ``eval_fn`` here).  Instead
of every search driving its own serial jit-dispatch loop, evaluations are
funneled through one dispatcher thread that:

  1. flattens every pending request's genomes into per-layer *points*
     ``(layer fields, pe, kt, df)`` -- the cost model is per-point, so points
     from different workloads concatenate freely (multi-tenant batching);
  2. dedupes identical points across (and within) requests by their bytes
     (:func:`dedup_point_rows`), the memo cache's own identity;
  3. consults the :class:`~repro.serving.cost_cache.CostMemoCache` and
     evaluates only the genuinely new points in ONE fused call -- the Pallas
     per-row-layers kernel (``ops.batched_cost_multi``) when
     ``kernels.ops.on_tpu()``, the jitted jnp oracle elsewhere;
  4. aggregates every request on the host in one NumPy pass per group of
     requests with the same layer count, through the same
     :func:`repro.core.env.aggregate_costs_multi` that
     :func:`repro.core.env.genome_cost` reduces with in the graph (a
     ``blend`` request's scalar fitness keeps a jitted program: NumPy's
     ``pow`` is not XLA's to the bit).

Exactness: per-point cost values are bit-identical whatever batch they are
computed in (the model is elementwise), and the final per-genome reduction
makes the same f32 adds in the same order as the serial engine's (per row,
so rows of other requests beside it change nothing) -- so a
search through the batcher returns bit-identical fitness to the same search
run serially, cache hits and cross-request fusion included.  This is the
property ``tests/test_search_service.py`` locks in.  (It holds on the jnp
oracle path, i.e. everywhere but TPU; the TPU Pallas kernel agrees with
the oracle to float32 allclose, like every kernel/oracle pair here.)
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import env as env_lib
from repro.costmodel import maestro
from repro.costmodel.layers import F_TYPE, NUM_FIELDS, TYPE_NAMES
from repro.kernels import ops
from repro.kernels.costmodel_eval import TN
from repro.obs import instrument as obs_instrument
from repro.obs import recorder as obs_recorder
from repro.obs import state as obs_state
from repro.obs import trace as obs_trace
from repro.serving.cost_cache import CostMemoCache

_PE_COL = NUM_FIELDS
_KT_COL = NUM_FIELDS + 1
_DF_COL = NUM_FIELDS + 2
ROW_WIDTH = NUM_FIELDS + 3   # layer fields + pe + kt + df


@functools.lru_cache(maxsize=None)
def _agg_fn(ecfg: "env_lib.EnvConfig"):
    """Jitted (b, N, 4) -> (b,) fitness: the SAME ``env.aggregate_costs``
    reduction ``genome_cost``/``_decode_and_eval`` run.  Only a ``blend``
    request's scalar fitness takes it (see :func:`_aggregate`); every
    other request is aggregated on the host by :func:`_host_totals`."""

    @jax.jit
    def f(vals, budget):
        perf, _, feas = env_lib.aggregate_costs(
            vals[..., 0], vals[..., 1], vals[..., 2], vals[..., 3],
            ecfg, budget)
        return jnp.where(feas, perf, jnp.inf)

    return f


def _host_totals(vals: np.ndarray, ecfg: "env_lib.EnvConfig", budget):
    """(B, N, 4) f32 per-layer costs -> ``env.aggregate_costs_multi``'s
    (total_lat, total_en, total_area, total_pw, feasible), each (B,),
    reduced by NumPy.  ``budget`` is a scalar or one per row."""
    cols = np.ascontiguousarray(np.moveaxis(vals, -1, 0))   # (4, B, N)
    return env_lib.aggregate_costs_multi(cols[0], cols[1], cols[2],
                                         cols[3], ecfg, budget)


def _host_result(totals, rows: slice, ecfg: "env_lib.EnvConfig",
                 multi: bool) -> np.ndarray:
    """One request's answer from its ``rows`` of :func:`_host_totals`:
    (b, 4) (lat, en, area, pw) for ``evaluate_costs``, else (b,) fitness,
    +inf where infeasible -- the bytes ``jnp.where(feas, perf, inf)``
    gives."""
    tl, te, ta, tp, feas = (t[rows] for t in totals)
    if multi:
        return np.stack([tl, te, ta, tp], axis=-1)
    return np.where(feas, env_lib.select_objective(tl, te, ecfg),
                    np.float32(np.inf))


@jax.jit
def _flat_cost(layers, pe, kt, df):
    """(M, NUM_FIELDS) x (M,) -> (M, 4) point costs via the jnp oracle."""
    out = maestro.evaluate(layers, pe, kt, df)
    return jnp.stack([out.latency, out.energy, out.area, out.power], axis=-1)


def _phase(name: str):
    """One phase of a fused dispatch: span ``batcher.<name>`` plus
    ``repro_batcher_phase_seconds{phase=<name>}``."""
    return obs_instrument.timed(f"batcher.{name}",
                                obs_instrument.BATCHER_PHASE_SECONDS,
                                phase=name)


def _next_pow2(n: int, lo: int = 256) -> int:
    m = lo
    while m < n:
        m *= 2
    return m


class _Item:
    """One in-flight eval request: points + how to aggregate them."""

    __slots__ = ("points", "shape", "agg_key", "budget", "multi", "event",
                 "fit", "error", "recorder", "t_enqueue")

    def __init__(self, points, shape, agg_key, budget, multi=False):
        self.points = points          # (b*N, ROW_WIDTH) f32
        self.shape = shape            # (b, N)
        self.agg_key = agg_key        # the request's EnvConfig (hashable)
        self.budget = budget          # f32 scalar
        self.multi = multi            # (b, 4) aggregated costs vs (b,) fit
        self.event = threading.Event()
        self.fit: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        # Telemetry attribution: the submitting search's flight recorder is
        # captured at submit time (on the search worker's thread), so the
        # dispatcher thread credits queue-wait / fuse / cache stats to the
        # right search even when one dispatch fuses N searches' requests.
        self.recorder = None
        self.t_enqueue = 0.0


def _aggregate(items: List[_Item], per_point: np.ndarray) -> None:
    """Set every item's ``fit`` from the dispatch's (P, 4) per-point costs
    (the items' points, concatenated in order).

    Items with the same layer count ``N``, scenario and constraint -- the
    only fields of the EnvConfig that ``aggregate_costs_multi`` reads --
    are reduced together in one NumPy pass over their rows, each row
    against its own item's budget; each item then takes its objective
    from its own rows.  Blend fitness, ``lat ** w * en ** (1 - w)``, goes
    through :func:`_agg_fn` on the device: NumPy's ``pow`` and XLA's differ
    in the last bit, while adds, maxima and comparisons are the same."""
    groups: Dict[tuple, list] = {}
    jit = []
    off = 0
    for it in items:
        n = it.points.shape[0]
        seg = (it, per_point[off:off + n])
        off += n
        if not it.multi and it.agg_key.objective == "blend":
            jit.append(seg)
        else:
            ecfg = it.agg_key
            groups.setdefault((it.shape[1], ecfg.scenario, ecfg.constraint),
                              []).append(seg)
    for (N, _, _), segs in groups.items():
        if len(segs) == 1:
            vals, budget = segs[0][1], segs[0][0].budget
        else:
            vals = np.concatenate([v for _, v in segs], axis=0)
            budget = np.repeat(np.array([it.budget for it, _ in segs],
                                        np.float32),
                               [it.shape[0] for it, _ in segs])
        totals = _host_totals(vals.reshape(-1, N, 4), segs[0][0].agg_key,
                              budget)
        row = 0
        for it, _ in segs:
            b = it.shape[0]
            it.fit = _host_result(totals, slice(row, row + b), it.agg_key,
                                  it.multi)
            row += b
    for it, v in jit:
        it.fit = np.asarray(_agg_fn(it.agg_key)(
            jnp.asarray(v.reshape(it.shape + (4,))), it.budget))
    if len(items) > len(jit):
        obs_instrument.BATCHER_AGGREGATED_ITEMS.inc(len(items) - len(jit),
                                                    path="host")
    if jit:
        obs_instrument.BATCHER_AGGREGATED_ITEMS.inc(len(jit), path="jit")


class CostEvalBatcher:
    """Fuses concurrent searches' cost evaluations into single dispatches.

    ``window_ms`` is the accumulation window after the first pending item;
    while a dispatch executes, new arrivals queue up naturally, so steady-
    state fusion widths track the number of concurrently evaluating
    searches.  Fresh points go to :func:`eval_point_rows`.

    ``dispatch_workers`` sizes the dispatch pool: with N > 1, up to N fused
    dispatches execute concurrently (XLA releases the GIL during execution,
    and the host-side flatten/unique/reassemble work overlaps too).  Fusion
    grouping never changes values -- the cost model is elementwise per point
    and each item aggregates only its own points -- so pooled dispatch stays
    bit-identical to the single-thread dispatcher, cache races included
    (two workers evaluating the same point store the same bytes).
    """

    def __init__(self, cache: Optional[CostMemoCache] = None,
                 window_ms: float = 2.0,
                 dispatch_workers: int = 1,
                 join_timeout_s: float = 5.0):
        self.cache = cache if cache is not None else CostMemoCache()
        self._window_s = max(window_ms, 0.0) / 1e3
        self._join_timeout_s = float(join_timeout_s)
        self._pending: List[_Item] = []
        self._cv = threading.Condition()
        self._closed = False
        self._stats_lock = threading.Lock()
        self._active = 0
        self._stats = {
            "dispatches": 0, "fused_dispatches": 0, "items": 0,
            "points": 0, "unique_points": 0, "fresh_points": 0,
            "max_items_per_dispatch": 0, "max_points_per_dispatch": 0,
            "dispatch_workers": max(int(dispatch_workers), 1),
            "max_concurrent_dispatches": 0,
            "leaked_dispatch_threads": 0,
        }
        self._threads = [
            threading.Thread(target=self._loop,
                             name=f"cost-eval-batcher-{i}", daemon=True)
            for i in range(max(int(dispatch_workers), 1))]
        for t in self._threads:
            t.start()

    # -- client side --------------------------------------------------------
    def evaluate(self, layers, pe, kt, df, ecfg, budget) -> np.ndarray:
        """Blocking genome-batch evaluation; safe from any thread.

        layers: (N, NUM_FIELDS); pe/kt: (b, N) raw f32 values; df: scalar or
        (b, N); ecfg: the request's EnvConfig; budget: the env's constraint
        budget.  Returns (b,) f32 fitness (+inf = infeasible), bit-identical
        to ``_decode_and_eval`` on the same genomes.
        """
        return self._submit(layers, pe, kt, df, ecfg, budget, multi=False)

    def evaluate_costs(self, layers, pe, kt, df, ecfg, budget) -> np.ndarray:
        """Like :meth:`evaluate` but returns (b, 4) aggregated whole-model
        (lat, en, area, pw) costs instead of scalar fitness -- the eval hook
        of the multi-objective ``nsga2`` engine.  Bit-identical to the
        engine's in-graph ``fitness`` on the same genomes; shares the same
        per-point dedup, memo cache and fused dispatch as everything else.
        """
        return self._submit(layers, pe, kt, df, ecfg, budget, multi=True)

    def _submit(self, layers, pe, kt, df, ecfg, budget,
                multi: bool) -> np.ndarray:
        if self._closed:
            raise RuntimeError("CostEvalBatcher is closed")
        pe = np.asarray(pe, np.float32)
        points = pack_point_rows(layers, pe, kt, df)
        item = _Item(points, pe.shape, ecfg, np.float32(budget), multi=multi)
        if obs_state.enabled:
            item.recorder = obs_recorder.current_recorder()
            item.t_enqueue = time.perf_counter()
        with self._cv:
            if self._closed:
                raise RuntimeError("CostEvalBatcher is closed")
            self._pending.append(item)
            obs_instrument.BATCHER_QUEUE_DEPTH.set(len(self._pending))
            self._cv.notify()
        item.event.wait()
        if item.error is not None:
            raise item.error
        return item.fit

    def stats(self) -> Dict[str, float]:
        with self._stats_lock:
            s = dict(self._stats)
        cache = {f"cache_{k}": v for k, v in self.cache.stats().items()}
        # The cache_ prefix must keep the two stat families disjoint: a
        # batcher-native key that ever starts with cache_ would silently
        # shadow (or be shadowed by) a cache stat in this merge.
        overlap = set(s) & set(cache)
        assert not overlap, f"batcher/cache stats keys collide: {overlap}"
        s.update(cache)
        return s

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        leaked = 0
        for t in self._threads:
            t.join(timeout=self._join_timeout_s)
            # join() returning proves nothing by itself: with a timeout it
            # returns whether or not the thread died.  A still-alive worker
            # is hung inside a dispatch -- it will never drain _pending, so
            # every queued waiter would block forever if we stayed silent.
            if t.is_alive():
                leaked += 1
        if leaked:
            with self._cv:
                stranded, self._pending = self._pending, []
            err = RuntimeError(
                f"CostEvalBatcher closed with {leaked} hung dispatch "
                f"thread(s); pending evaluations abandoned")
            for it in stranded:
                if not it.event.is_set():
                    it.error = err
                    it.event.set()
        with self._stats_lock:
            self._stats["leaked_dispatch_threads"] = leaked

    # -- dispatcher side ----------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._closed:
                    self._cv.wait()
                if self._closed and not self._pending:
                    return
            if self._window_s:
                time.sleep(self._window_s)
            with self._cv:
                items, self._pending = self._pending, []
                obs_instrument.BATCHER_QUEUE_DEPTH.set(0)
            if not items:
                continue
            with self._stats_lock:
                self._active += 1
                self._stats["max_concurrent_dispatches"] = max(
                    self._stats["max_concurrent_dispatches"], self._active)
            try:
                self._dispatch(items)
            except BaseException as e:  # noqa: BLE001 -- never stall waiters
                for it in items:
                    if not it.event.is_set():
                        it.error = e
                        it.event.set()
            finally:
                with self._stats_lock:
                    self._active -= 1

    def _dispatch(self, items: List[_Item]) -> None:
        t0 = time.perf_counter() if obs_state.enabled else 0.0
        with obs_trace.span("batcher.dispatch") as sp:
            with _phase("dedup"):
                rows = (items[0].points if len(items) == 1
                        else np.concatenate([it.points for it in items],
                                            axis=0))
                uniq, inv, keys = dedup_point_rows(rows)
            with _phase("lookup"):
                values, miss_index = self.cache.get_many(keys)
            t_eval = 0.0
            fresh_rows = None
            if miss_index:
                with _phase("eval"):
                    te = time.perf_counter() if obs_state.enabled else 0.0
                    fresh_rows = uniq[miss_index]
                    fresh = self._eval_points(fresh_rows)
                    if obs_state.enabled:
                        t_eval = time.perf_counter() - te
            with _phase("fill"):
                if miss_index:
                    # Cache per-row COPIES: a row view would pin the whole
                    # dispatch's result array in memory for as long as any
                    # one point stays hot.
                    self.cache.put_many([keys[i] for i in miss_index],
                                        [f.copy() for f in fresh])
                    for i, v in zip(miss_index, fresh):
                        values[i] = v
                per_point = np.stack(values)[inv]          # (P, 4)
            sp.set(items=len(items), points=len(rows), unique=len(uniq),
                   fresh=len(miss_index))
            if obs_state.enabled:
                # repro_batcher_dispatch_seconds ends here, before the
                # aggregation (batcher.aggregate times that part).
                self._record_dispatch(items, t0, time.perf_counter() - t0,
                                      t_eval, len(uniq), miss_index, inv,
                                      fresh_rows)

            with self._stats_lock:
                s = self._stats
                s["dispatches"] += 1
                s["fused_dispatches"] += len(items) > 1
                s["items"] += len(items)
                s["points"] += len(rows)
                s["unique_points"] += len(uniq)
                s["fresh_points"] += len(miss_index)
                s["max_items_per_dispatch"] = max(
                    s["max_items_per_dispatch"], len(items))
                s["max_points_per_dispatch"] = max(
                    s["max_points_per_dispatch"], len(rows))

            with _phase("aggregate"):
                try:
                    _aggregate(items, per_point)
                finally:
                    # Set the events only once every answer is computed:
                    # each wakes a search thread that contends for the
                    # interpreter lock.  On an error the answers already
                    # made still go out; _loop hands it to the rest.
                    for it in items:
                        if it.fit is not None:
                            it.event.set()

    def _record_dispatch(self, items: List[_Item], t0: float, dt: float,
                         t_eval: float, n_uniq: int, miss_index, inv,
                         fresh_rows) -> None:
        """Telemetry for one finished dispatch: process-wide metrics plus
        per-item flight-recorder attribution (each rider is credited its own
        share of the fused batch, including its own cached-vs-fresh split).

        Fresh credit is *first-claim*: when several submitted points (same
        item or different riders) collapse onto one fresh unique row, only
        the first submitted occurrence is credited ``fresh`` -- the rest
        ride the same evaluation and count ``cached``.  That keeps
        ``sum(per-rider fresh) == dispatcher fresh_points`` exact instead
        of drifting whenever duplicates happen to fuse."""
        n_points = sum(it.points.shape[0] for it in items)
        obs_instrument.BATCHER_DISPATCHES.inc()
        obs_instrument.BATCHER_POINTS.inc(n_points, kind="submitted")
        obs_instrument.BATCHER_POINTS.inc(n_uniq, kind="unique")
        obs_instrument.BATCHER_POINTS.inc(len(miss_index), kind="fresh")
        if fresh_rows is not None:
            by_type = np.bincount(fresh_rows[:, F_TYPE].astype(np.int64),
                                  minlength=len(TYPE_NAMES))
            for ltype, n in zip(TYPE_NAMES, by_type):
                if n:
                    obs_instrument.BATCHER_FRESH_POINTS.inc(int(n),
                                                            ltype=ltype)
        obs_instrument.BATCHER_FUSE_WIDTH.observe(len(items))
        obs_instrument.BATCHER_DISPATCH_SECONDS.observe(dt)
        fresh_pp = None
        if any(it.recorder is not None for it in items):
            first = np.full(n_uniq, len(inv), dtype=np.int64)
            np.minimum.at(first, inv, np.arange(len(inv)))
            fresh_pp = np.zeros(len(inv), bool)   # per submitted point
            fresh_pp[first[miss_index]] = True    # first claimant only
        off = 0
        for it in items:
            n = it.points.shape[0]
            wait = (t0 - it.t_enqueue) if it.t_enqueue else 0.0
            obs_instrument.BATCHER_QUEUE_WAIT.observe(max(wait, 0.0))
            rec = it.recorder
            if rec is not None:
                n_fresh = int(fresh_pp[off:off + n].sum())
                rec.add("eval_batches")
                rec.add("points", n)
                rec.add("fresh_points", n_fresh)
                rec.add("cached_points", n - n_fresh)
                if it.t_enqueue:
                    rec.observe("queue_wait_s", max(wait, 0.0))
                rec.observe("dispatch_s", dt)
                rec.observe("device_s", t_eval)
                rec.observe("fuse_width", len(items))
            off += n

    def _eval_points(self, rows: np.ndarray) -> np.ndarray:
        return eval_point_rows(rows)


def eval_point_rows(rows: np.ndarray,
                    use_kernel: Optional[bool] = None) -> np.ndarray:
    """Evaluate (M, ROW_WIDTH) fresh points -> (M, 4) f32 costs.

    ``use_kernel`` picks the shape: True tiles the rows to multiples of TN
    for ``ops.batched_cost_multi`` (the per-row kernel on a TPU), False pads
    them to a power of two for the jitted oracle, which bounds recompiles.
    None, the default, is ``ops.on_tpu()``.

    Per-row results are bit-stable across batch size and padding (the
    computation is elementwise per row), so any caller packing the same row
    gets the same bytes -- the property both the memo cache and serial ==
    service-batched byte-identity rest on.
    """
    M = rows.shape[0]
    if use_kernel is None:
        use_kernel = ops.on_tpu()
    if use_kernel:
        # Tile the flat point list into the kernel's (B', TN) lanes.
        Mp = -(-M // TN) * TN
        pad = np.ones((Mp - M, ROW_WIDTH), np.float32)
        pad[:, NUM_FIELDS - 1] = 0.0            # repeat=0: benign rows
        rp = np.concatenate([rows, pad], axis=0) if Mp > M else rows
        with obs_instrument.dispatch_span("cost_eval_kernel"):
            lat, en, area, pw = ops.batched_cost_multi(
                rp[:, :NUM_FIELDS].reshape(-1, TN, NUM_FIELDS),
                rp[:, _PE_COL].reshape(-1, TN),
                rp[:, _KT_COL].reshape(-1, TN),
                rp[:, _DF_COL].reshape(-1, TN))
        out = np.stack([np.asarray(lat), np.asarray(en),
                        np.asarray(area), np.asarray(pw)],
                       axis=-1).reshape(Mp, 4)
        return out[:M]
    Mp = _next_pow2(M)
    rp = np.ones((Mp, ROW_WIDTH), np.float32)
    rp[:M] = rows
    with obs_instrument.dispatch_span("cost_eval_jnp"):
        out = _flat_cost(rp[:, :NUM_FIELDS], rp[:, _PE_COL],
                         rp[:, _KT_COL], rp[:, _DF_COL])
        out = np.asarray(out)
    return out[:M]


_ROW_VOID = np.dtype((np.void, 4 * ROW_WIDTH))


def dedup_point_rows(rows: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray, List[bytes]]:
    """(P, ROW_WIDTH) f32 rows -> ``(uniq, inv, keys)`` with
    ``uniq[inv] == rows`` and ``keys[i] == uniq[i].tobytes()``.

    Identity is the row's own bytes -- the memo cache's key -- so each row
    is one fixed-width void scalar and one 1-D ``np.unique`` sorts them by
    ``memcmp``; a float ``np.unique(axis=0)`` compares field by field, at
    several times the cost.  For the rows :func:`pack_point_rows`
    makes (finite, never ``-0.0`` or NaN) byte identity is float equality.
    Unique rows come out in byte order.
    """
    v = np.ascontiguousarray(rows, np.float32).view(_ROW_VOID).ravel()
    uv, inv = np.unique(v, return_inverse=True)
    return (uv.view(np.float32).reshape(-1, ROW_WIDTH), inv.ravel(),
            uv.tolist())


def pack_point_rows(layers: np.ndarray, pe, kt, df) -> np.ndarray:
    """(N, NUM_FIELDS) layers x (b, N) assignments -> (b*N, ROW_WIDTH) rows
    in the batcher/cache key format."""
    layers = np.asarray(layers, np.float32)
    pe = np.asarray(pe, np.float32)
    b, N = pe.shape
    kt = np.broadcast_to(np.asarray(kt, np.float32), (b, N))
    df = np.broadcast_to(np.asarray(df, np.float32), (b, N))
    points = np.empty((b * N, ROW_WIDTH), np.float32)
    points[:, :NUM_FIELDS] = np.broadcast_to(
        layers, (b, N, NUM_FIELDS)).reshape(-1, NUM_FIELDS)
    points[:, _PE_COL] = pe.ravel()
    points[:, _KT_COL] = kt.ravel()
    points[:, _DF_COL] = df.ravel()
    return points


def make_local_costs_eval(env, ecfg):
    """Serial nsga2's default fitness hook: ``eval_fn(pe, kt, df) -> (b, 4)``
    running the EXACT per-point program and host aggregation a
    :class:`CostEvalBatcher` dispatch runs -- minus the queue, fusion
    window and memo cache.  Because ``eval_point_rows`` is bit-stable per
    row and ``_host_totals`` reduces each row alone, a serial
    ``run_search`` and a service-batched one produce byte-identical
    outcomes by construction (benchmarks/bench_frontier.py asserts it end
    to end).
    """
    layers = np.asarray(env.layers, np.float32)
    budget = np.float32(env.budget)

    def eval_fn(pe, kt, df):
        pe = np.asarray(pe, np.float32)
        b, N = pe.shape
        rows = pack_point_rows(layers, pe, kt, df)
        vals = eval_point_rows(rows).reshape(b, N, 4)
        return _host_result(_host_totals(vals, ecfg, budget), slice(None),
                            ecfg, multi=True)

    return eval_fn
