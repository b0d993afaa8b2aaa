"""The interactive environment (SIII-F): workload + constraints + objective.

The Env wraps the analytical cost model.  Everything is a device array so a
whole episode -- and in fact the whole multi-thousand-epoch search -- stays
inside one XLA program (DESIGN.md S3 "Env-in-the-graph").

Observation (Eq. 1): O_t = (K,C,Y,X,R,S,T, A^PE_{t-1}, A^Buf_{t-1}, t),
every dimension normalized to [-1, 1].  The static 7-dim layer part is
precomputed here; the dynamic 3 dims (previous actions + time) are appended
by the rollout.  The MIX agent appends the previous dataflow choice as an
11th dimension.

Platform constraints (Table II): budget = frac * C_max, where C_max is the
constraint consumption of the whole model under the uniform maximum action
pair (p_12th, b_12th) -- measured exactly as the paper measures it.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

from repro.costmodel import dataflows as dfl
from repro.costmodel import maestro
from repro.costmodel.layers import NUM_FIELDS, layers_to_array

PLATFORM_FRACTIONS = {
    "unlimited": float("inf"),
    "cloud": 0.50,
    "iot": 0.10,
    "iotx": 0.05,
}

# "blend" is the scalarization objective for frontier sweeps: the whole-
# model value is total_lat**w * total_en**(1-w) (w = EnvConfig.blend_weight),
# i.e. a weighted sum in log space, so any single-objective engine can walk
# the latency/energy trade-off one weight at a time.  It is whole-model only:
# the per-layer RL reward path (``layer_cost``) rejects it.
OBJECTIVES = ("latency", "energy", "blend")
CONSTRAINTS = ("area", "power")


@dataclasses.dataclass(frozen=True)
class EnvConfig:
    """Static (trace-time) environment configuration."""

    objective: str = "latency"
    constraint: str = "area"
    platform: str = "iot"
    scenario: str = "LP"
    dataflow: int = dfl.DLA    # ignored when mix=True
    mix: bool = False
    levels: int = 12
    blend_weight: float = 0.5  # only read when objective == "blend"

    def __post_init__(self):
        assert self.objective in OBJECTIVES
        assert self.constraint in CONSTRAINTS
        assert self.platform in PLATFORM_FRACTIONS
        assert self.scenario in ("LP", "LS")
        assert 0.0 <= self.blend_weight <= 1.0

    @property
    def obs_dim(self) -> int:
        return 11 if self.mix else 10


class EnvArrays(NamedTuple):
    """Device-array environment state (jit-traceable)."""

    layers: jnp.ndarray      # (N, NUM_FIELDS) f32
    static_obs: jnp.ndarray  # (N, 7) normalized layer observation
    pe_table: jnp.ndarray    # (L,) f32
    kt_table: jnp.ndarray    # (L,) f32
    budget: jnp.ndarray      # () f32 (inf for unlimited)

    @property
    def num_layers(self) -> int:
        return self.layers.shape[0]


def _normalize_obs(arr: np.ndarray) -> np.ndarray:
    """Per-model max-normalization of (K,C,Y,X,R,S,type) into [-1, 1]."""
    obs = arr[:, :7].astype(np.float64)
    maxes = np.maximum(obs.max(axis=0), 1.0)
    return (2.0 * obs / maxes - 1.0).astype(np.float32)


def max_constraint(layers_arr, cfg: EnvConfig) -> float:
    """C_max: whole-model consumption at the uniform max action (Table II)."""
    N = layers_arr.shape[0]
    pe_max = float(dfl.pe_levels(cfg.levels)[-1])
    kt_max = float(dfl.kt_levels(cfg.levels)[-1])
    df = cfg.dataflow if not cfg.mix else dfl.DLA
    out = maestro.model_cost(
        jnp.asarray(layers_arr, jnp.float32),
        jnp.full((N,), pe_max), jnp.full((N,), kt_max), df, cfg.scenario)
    val = out.area if cfg.constraint == "area" else out.power
    return float(val)


def make_env(workload, cfg: EnvConfig) -> EnvArrays:
    """Build the Env from a workload (list of LayerSpec or (N,8) array)."""
    if isinstance(workload, (list, tuple)):
        arr = layers_to_array(workload)
    else:
        arr = np.asarray(workload)
    assert arr.ndim == 2 and arr.shape[1] == NUM_FIELDS
    frac = PLATFORM_FRACTIONS[cfg.platform]
    budget = (np.float32(np.inf) if np.isinf(frac)
              else np.float32(frac * max_constraint(arr, cfg)))
    return EnvArrays(
        layers=jnp.asarray(arr, jnp.float32),
        static_obs=jnp.asarray(_normalize_obs(arr)),
        pe_table=jnp.asarray(dfl.pe_levels(cfg.levels), jnp.float32),
        kt_table=jnp.asarray(dfl.kt_levels(cfg.levels), jnp.float32),
        budget=jnp.asarray(budget),
    )


def layer_cost(env: EnvArrays, cfg: EnvConfig, t, pe, kt, df):
    """Per-layer (objective value, constraint consumption) at step t."""
    if cfg.objective == "blend":
        raise ValueError(
            "objective='blend' is a whole-model scalarization; the per-layer"
            " RL reward path cannot decompose it per step -- use a"
            " population/sampling method (random/grid/sa/ga/bo/relaxed) or"
            " the native multi-objective engine (nsga2) instead")
    out = maestro.evaluate(env.layers[t], pe, kt, df)
    perf = out.latency if cfg.objective == "latency" else out.energy
    cons = out.area if cfg.constraint == "area" else out.power
    return perf, cons


def select_objective(total_lat, total_en, cfg: EnvConfig):
    """Whole-model objective from the aggregated (latency, energy) pair."""
    if cfg.objective == "latency":
        return total_lat
    if cfg.objective == "energy":
        return total_en
    xp = _xp(total_lat)
    w = xp.float32(cfg.blend_weight)
    return total_lat ** w * total_en ** (xp.float32(1.0) - w)


def _xp(x):
    """The array module that reduces ``x``: NumPy for a NumPy array, ``jnp``
    for a jax array or tracer."""
    return np if isinstance(x, np.ndarray) else jnp


def sum_layers(x):
    """Sum over the last (layer) axis in one fixed pairwise order.

    ``jnp.sum`` leaves the f32 association order to the compiler, which
    picks it per program: the GA's in-graph fitness, a standalone
    aggregation program and a reduction fused after a kernel can then
    differ by an ulp for the same per-layer values.  Explicit adds of
    contiguous halves are never reassociated, so every program that
    aggregates the same values gets the same bits -- and NumPy, given a
    NumPy array, makes the same adds in the same order.
    """
    xp = _xp(x)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        head = x[..., :h] + x[..., h:2 * h]
        x = (head if x.shape[-1] % 2 == 0
             else xp.concatenate([head, x[..., 2 * h:]], axis=-1))
    return x[..., 0]


def aggregate_costs_multi(lat, en, area, pw, cfg: EnvConfig, budget):
    """Per-layer costs (..., N) -> whole-model
    (total_lat, total_en, total_area, total_pw, feasible).

    THE one definition of the aggregation semantics -- objectives summed
    over layers, constraints summed (LP: one partition per layer) or maxed
    (LS: one shared design), feasible iff the configured constraint metric
    fits the platform budget -- shared by :func:`genome_cost`, the GA's
    Pallas-kernel fitness path, the NSGA-II engine and the serving batcher,
    so none of them can drift apart.  ``aggregate_costs`` below is the
    scalar-objective view of this same definition.

    It runs on the array module of its input: jax arrays and tracers are
    reduced by ``jnp`` inside the caller's program, NumPy arrays by NumPy
    on the host (the batcher's aggregation), with the same f32 adds in the
    same order and the same maximum, so both give the same bits.
    """
    xp = _xp(lat)
    total_lat = sum_layers(lat)
    total_en = sum_layers(en)
    if cfg.scenario == "LP":
        total_area = sum_layers(area)
        total_pw = sum_layers(pw)
    else:
        total_area = xp.max(area, axis=-1)
        total_pw = xp.max(pw, axis=-1)
    total_cons = total_area if cfg.constraint == "area" else total_pw
    return total_lat, total_en, total_area, total_pw, total_cons <= budget


def aggregate_costs(lat, en, area, pw, cfg: EnvConfig, budget):
    """Per-layer costs (..., N) -> whole-model (objective, constraint,
    feasible): the single-objective view of :func:`aggregate_costs_multi`
    (bit-identical to the pre-frontier definition -- the same jnp
    reductions over the same arrays; XLA prunes the unselected metric)."""
    tl, te, ta, tp, feas = aggregate_costs_multi(lat, en, area, pw, cfg,
                                                 budget)
    total_perf = select_objective(tl, te, cfg)
    total_cons = ta if cfg.constraint == "area" else tp
    return total_perf, total_cons, feas


def genome_cost(env: EnvArrays, cfg: EnvConfig, pe, kt, df):
    """Whole-model (objective, constraint, feasible) for per-layer arrays.

    pe/kt: (..., N) raw values;  df: scalar or (..., N).
    LP: constraint = sum over layers; LS: constraint = max over layers.
    """
    out = maestro.evaluate(env.layers, pe, kt, df)
    return aggregate_costs(out.latency, out.energy, out.area, out.power,
                           cfg, env.budget)


def genome_costs_multi(env: EnvArrays, cfg: EnvConfig, pe, kt, df):
    """Whole-model (total_lat, total_en, total_area, total_pw, feasible)
    for per-layer arrays -- the multi-objective sibling of
    :func:`genome_cost` (same model eval, same reductions)."""
    out = maestro.evaluate(env.layers, pe, kt, df)
    return aggregate_costs_multi(out.latency, out.energy, out.area,
                                 out.power, cfg, env.budget)


def feasibility_mask(env: EnvArrays, cfg: EnvConfig, pe, kt, df):
    """First-class feasibility of per-layer assignments: (...,) bool True
    where the aggregated platform constraint (Table II) fits the budget.

    This is the mask every optimizer's reported ``best`` must satisfy
    (enforced registry-wide by tests/test_optimizer_conformance.py):
    infeasible candidates are never reported as best, they surface only as
    the paper's "NAN" (best_value = +inf, feasible=False).
    """
    return genome_costs_multi(env, cfg, pe, kt, df)[4]


def action_tables(cfg: EnvConfig) -> Sequence[np.ndarray]:
    return dfl.pe_levels(cfg.levels), dfl.kt_levels(cfg.levels)
