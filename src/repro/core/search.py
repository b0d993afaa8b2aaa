"""ConfuciuX two-stage orchestration (Fig. 3): RL global search -> GA local
fine-tune, plus the LS per-layer analysis of SIV-B.

The launcher, examples and benchmarks now drive this through the unified
optimizer API (``repro.api``, method name "two_stage"); ``confuciux_search``
remains the underlying engine and a thin legacy entry point.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import env as env_lib
from repro.core import ga as ga_lib
from repro.core import policy as policy_lib
from repro.core import reinforce
from repro.costmodel import dataflows as dfl
from repro.costmodel import workloads as workloads_lib
from repro.kernels import ref as kref


@dataclasses.dataclass
class SearchResult:
    best_value: float                 # objective after both stages
    stage1_value: float               # after global RL search
    initial_valid_value: float        # first feasible value seen (Table VII)
    pe: np.ndarray                    # (N,) raw per-layer PE assignment
    kt: np.ndarray                    # (N,) raw per-layer tile counts
    df: np.ndarray                    # (N,) per-layer dataflow style
    history: Dict[str, np.ndarray]    # stage-1 convergence traces
    ga_history: np.ndarray            # stage-2 best-so-far trace
    wall_seconds: float
    epochs: int


def confuciux_search(workload, ecfg: env_lib.EnvConfig,
                     rcfg: reinforce.ReinforceConfig = None,
                     gcfg: ga_lib.LocalGAConfig = None,
                     pcfg: policy_lib.PolicyConfig = None,
                     fine_tune: bool = True,
                     chunk: int = 500,
                     on_chunk=None,
                     ga_chunk: Optional[int] = None,
                     ga_on_chunk=None) -> SearchResult:
    """Run the full two-stage ConfuciuX pipeline on a workload.

    chunk / on_chunk are forwarded to the stage-1 ``reinforce.run_search``
    so callers (the unified API) can stream global-search progress live;
    ga_chunk / ga_on_chunk do the same for the stage-2 local-GA fine-tune
    (``ga_lib.run_local_ga``), which makes stage 2 preemptible at chunk
    granularity too instead of one opaque scan.
    """
    if isinstance(workload, str):
        workload = workloads_lib.get_workload(workload)
    rcfg = rcfg or reinforce.ReinforceConfig()
    gcfg = gcfg or ga_lib.LocalGAConfig()
    t0 = time.time()

    state, hist = reinforce.run_search(workload, ecfg, rcfg, pcfg,
                                       chunk=chunk, on_chunk=on_chunk)
    env = env_lib.make_env(workload, ecfg)
    pe1, kt1, df1 = reinforce.solution_arrays(state, env)
    stage1 = float(state.best_value)
    finite = hist["best_value"][np.isfinite(hist["best_value"])]
    initial_valid = float(finite[0]) if len(finite) else float("inf")

    if fine_tune and np.isfinite(stage1):
        ga_state, ga_hist = ga_lib.run_local_ga(
            workload, ecfg, pe1, kt1, df1, gcfg, chunk=ga_chunk,
            on_chunk=ga_on_chunk, env=env)
        if float(ga_state.best_val) < stage1:
            pe = np.asarray(ga_state.best_genome[..., 0], np.float32)
            kt = np.asarray(ga_state.best_genome[..., 1], np.float32)
            df = np.asarray(df1)
            best = float(ga_state.best_val)
        else:  # GA never improves past the seed by construction, but guard.
            pe, kt, df, best = (np.asarray(pe1), np.asarray(kt1),
                                np.asarray(df1), stage1)
        ga_hist = np.asarray(ga_hist)
    else:
        pe, kt, df, best = (np.asarray(pe1), np.asarray(kt1),
                            np.asarray(df1), stage1)
        ga_hist = np.asarray([])

    return SearchResult(
        best_value=best, stage1_value=stage1,
        initial_valid_value=initial_valid,
        pe=pe, kt=kt, df=df, history=hist, ga_history=ga_hist,
        wall_seconds=time.time() - t0, epochs=rcfg.epochs)


def per_layer_optima(workload, ecfg: env_lib.EnvConfig):
    """SIV-B LS study: the full (L x L) action-pair sweep for every layer.

    Returns dict with the (N, L, L) latency/energy grids and per-layer argmin
    pairs -- the data behind Fig. 5's heatmaps.  One batched call of the jnp
    cost model (``kernels/ref.py``, on every platform) evaluates all
    N * L * L cells.
    """
    if isinstance(workload, str):
        workload = workloads_lib.get_workload(workload)
    env = env_lib.make_env(workload, ecfg)
    N = env.num_layers
    L = ecfg.levels
    pe_g, kt_g = jnp.meshgrid(env.pe_table, env.kt_table, indexing="ij")
    # (L*L, N) design batch: same pair applied to each layer independently.
    pe = jnp.tile(pe_g.reshape(-1, 1), (1, N))
    kt = jnp.tile(kt_g.reshape(-1, 1), (1, N))
    df = jnp.full(pe.shape, ecfg.dataflow, jnp.float32)
    lat, en, area, power = kref.cost_eval_ref(env.layers.T, pe, kt, df)
    lat = np.asarray(lat).reshape(L, L, N).transpose(2, 0, 1)
    en = np.asarray(en).reshape(L, L, N).transpose(2, 0, 1)
    area = np.asarray(area).reshape(L, L, N).transpose(2, 0, 1)
    feasible = area <= float(env.budget)
    masked_lat = np.where(feasible, lat, np.inf)
    masked_en = np.where(feasible, en, np.inf)
    opt_lat = np.array([np.unravel_index(np.argmin(m), m.shape)
                        for m in masked_lat])
    opt_en = np.array([np.unravel_index(np.argmin(m), m.shape)
                       for m in masked_en])
    return {"latency": lat, "energy": en, "area": area,
            "optima_latency": opt_lat, "optima_energy": opt_en,
            "pe_table": np.asarray(env.pe_table),
            "kt_table": np.asarray(env.kt_table)}


def heuristic_a(workload, ecfg: env_lib.EnvConfig) -> Dict[str, Any]:
    """Fig. 5 'Heuristic A': tune on the most compute-intensive layer, apply
    that (PE, Buf) pair to every layer."""
    grids = per_layer_optima(workload, ecfg)
    if isinstance(workload, str):
        workload = workloads_lib.get_workload(workload)
    macs = np.array([l.macs() for l in workload])
    hot = int(np.argmax(macs))
    key = "optima_latency" if ecfg.objective == "latency" else "optima_energy"
    pi, ki = grids[key][hot]
    env = env_lib.make_env(workload, ecfg)
    N = env.num_layers
    pe = jnp.full((N,), env.pe_table[pi])
    kt = jnp.full((N,), env.kt_table[ki])
    perf, cons, feas = env_lib.genome_cost(env, ecfg, pe, kt, ecfg.dataflow)
    return {"value": float(perf) if bool(feas) else float("inf"),
            "pe": np.asarray(pe), "kt": np.asarray(kt),
            "hot_layer": hot}


def heuristic_b(workload, ecfg: env_lib.EnvConfig) -> Dict[str, Any]:
    """Fig. 5 'Heuristic B': the single uniform (PE, Buf) pair that optimizes
    the end-to-end whole-model objective."""
    if isinstance(workload, str):
        workload = workloads_lib.get_workload(workload)
    env = env_lib.make_env(workload, ecfg)
    N = env.num_layers
    L = ecfg.levels
    pe_g, kt_g = jnp.meshgrid(env.pe_table, env.kt_table, indexing="ij")
    pe = jnp.tile(pe_g.reshape(-1, 1), (1, N))
    kt = jnp.tile(kt_g.reshape(-1, 1), (1, N))
    perf, cons, feas = env_lib.genome_cost(env, ecfg, pe, kt, ecfg.dataflow)
    fit = np.asarray(jnp.where(feas, perf, jnp.inf))
    i = int(fit.argmin())
    return {"value": float(fit[i]), "pe": np.asarray(pe[i]),
            "kt": np.asarray(kt[i])}


def scalarized_frontier_sweep(workload, ecfg: env_lib.EnvConfig,
                              eps: int, weights=(0.0, 0.25, 0.5, 0.75, 1.0),
                              method: str = "ga", seed: int = 0,
                              options: Optional[Dict[str, Any]] = None):
    """Approximate the latency-energy frontier with k scalarized searches.

    The classic alternative to native multi-objective search: split the
    eval budget across ``len(weights)`` single-objective runs, each
    minimizing the blended objective ``lat^w * en^(1-w)`` (a weighted sum
    in log space -- every minimizer is Pareto-optimal), and collect the
    feasible (lat, en, area, pw) points the winners realize.  Any
    single-objective registry method works; this is the baseline
    ``benchmarks/bench_frontier.py`` pits NSGA-II against at equal budget.

    Returns ``{"points": (k', 4) array, "weights", "outcomes"}`` with one
    row per *feasible* winner (k' <= k).
    """
    from repro import api   # lazy: api itself imports this module

    if isinstance(workload, str):
        workload = workloads_lib.get_workload(workload)
    env = env_lib.make_env(workload, ecfg)
    per_run = max(eps // len(weights), 1)
    points, outcomes = [], []
    for w in weights:
        wcfg = dataclasses.replace(ecfg, objective="blend", blend_weight=w)
        out = api.run_search(api.SearchRequest(
            workload=workload, env=wcfg, eps=per_run, seed=seed,
            method=method, options=dict(options or {})))
        outcomes.append(out)
        if not out.feasible:
            continue
        tl, te, ta, tp, feas = env_lib.genome_costs_multi(
            env, wcfg, jnp.asarray(out.pe, jnp.float32),
            jnp.asarray(out.kt, jnp.float32),
            jnp.asarray(out.df))
        if bool(feas):
            points.append([float(tl), float(te), float(ta), float(tp)])
    pts = (np.asarray(points, np.float64) if points
           else np.empty((0, 4), np.float64))
    return {"points": pts, "weights": list(weights), "outcomes": outcomes}
