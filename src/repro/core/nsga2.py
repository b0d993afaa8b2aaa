"""NSGA-II: constrained multi-objective search over (latency, energy).

ConfuciuX optimizes latency *or* energy under a hard area/power budget
(Table II); this engine searches the latency-energy *trade-off curve* in
one run instead of one scalarized point per run.  Same genome space as the
baseline GA -- per-layer (PE, Buf) level indices plus the dataflow gene for
MIX -- with NSGA-II's selection machinery (Deb et al. 2002):

  * **constrained dominance**: any lower-violation point dominates a
    higher-violation one; at equal violation (in particular 0 == feasible
    vs feasible) Pareto dominance on (total latency, total energy) decides.
    Budgets are first-class feasibility masks
    (:func:`repro.core.env.aggregate_costs_multi`), not reward penalties.
  * **non-dominated sorting** via a vectorized (M, M) dominance matrix and
    front peeling inside ``lax.fori_loop`` -- the whole generation is one
    XLA program, like every other engine here.
  * **crowding distance** computed with same-front masks (no data-dependent
    sort), boundary points at +inf, used for survival truncation and binary
    tournaments.
  * a fixed-capacity **Pareto archive** rides in the scan carry: every
    evaluated feasible point competes for one of ``archive`` slots
    (non-dominated filter + objective-space dedup + one-shot crowding
    truncation), so the frontier is available at every chunk boundary
    without host round-trips.  While the archive is below capacity its
    hypervolume is monotone non-decreasing in evals (no point is ever
    dropped except by a dominating one); at capacity, crowding truncation
    may trade boundary-interior points and the guarantee becomes
    approximate -- size ``archive`` generously.

The engine fills the :class:`repro.core.ga.GAEngine` contract with a
(P, 4) multi-cost fitness, so :func:`repro.core.ga.run_chunked_engine`
drives it unchanged: chunked, resumable, cancellable, and ``eval_fn``-
injectable (the search service routes whole populations through the
cross-request :class:`~repro.serving.batcher.CostEvalBatcher`; outcomes
are byte-identical to the in-graph path).

The pure-numpy Pareto helpers (``non_dominated_mask``, ``pareto_insert``,
``hypervolume_2d``) are the reference semantics the property tests in
tests/test_pareto_properties.py pin the engine against.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import env as env_lib
from repro.core import ga as ga_lib
from repro.costmodel import maestro
from repro.kernels import ops as kops

_BIG = jnp.float32(1e30)   # finite stand-in for +inf crowding in sort keys


# ---------------------------------------------------------------------------
# Pure Pareto helpers (numpy reference semantics; minimization throughout).
# ---------------------------------------------------------------------------
def pareto_dominates(a, b) -> bool:
    """True iff point ``a`` Pareto-dominates ``b`` (<= everywhere, < once)."""
    a = np.asarray(a, float)
    b = np.asarray(b, float)
    return bool(np.all(a <= b) and np.any(a < b))


def non_dominated_mask(costs) -> np.ndarray:
    """(M, k) cost points -> (M,) bool mask of the non-dominated subset."""
    c = np.asarray(costs, float)
    if c.size == 0:
        return np.zeros((0,), bool)
    le = np.all(c[:, None, :] <= c[None, :, :], axis=-1)
    lt = np.any(c[:, None, :] < c[None, :, :], axis=-1)
    dom = le & lt                 # dom[i, j]: i dominates j
    return ~dom.any(axis=0)


def pareto_insert(front, point):
    """Insert ``point`` into a non-dominated ``front`` (list of points).

    Returns the new front: unchanged (same points) when ``point`` is
    dominated by -- or equal to -- a member; otherwise ``point`` joins and
    every member it dominates leaves.  A dominated insertion therefore
    never grows the front (property-tested).
    """
    pt = np.asarray(point, float)
    front = [np.asarray(p, float) for p in front]
    for p in front:
        if np.array_equal(p, pt) or pareto_dominates(p, pt):
            return front
    return [p for p in front if not pareto_dominates(pt, p)] + [pt]


def hypervolume_2d(points, ref) -> float:
    """Dominated hypervolume of 2-D minimization points w.r.t. ``ref``.

    Points not strictly dominating the reference point contribute nothing.
    Monotone under set union: adding points never decreases it.
    """
    ref = np.asarray(ref, float)
    pts = np.asarray(points, float).reshape(-1, 2)
    pts = pts[np.all(np.isfinite(pts), axis=1)]
    pts = pts[np.all(pts < ref, axis=1)]
    if len(pts) == 0:
        return 0.0
    pts = pts[non_dominated_mask(pts)]
    order = np.argsort(pts[:, 0], kind="stable")
    pts = pts[order]                      # x ascending => y descending
    hv = 0.0
    for i, (x, y) in enumerate(pts):
        x_next = pts[i + 1, 0] if i + 1 < len(pts) else ref[0]
        hv += (x_next - x) * (ref[1] - y)
    return float(hv)


# ---------------------------------------------------------------------------
# Jitted selection machinery (shapes are static; everything scans).
# ---------------------------------------------------------------------------
def _violation(costs, cons_col: int, budget):
    """(M, 4) aggregated costs -> (M,) constraint violation (0 = feasible)."""
    cons = costs[:, cons_col]
    return jnp.where(cons <= budget, jnp.float32(0.0), cons - budget)


def _constrained_dominance(costs, viol):
    """(M, 4) costs + (M,) violation -> (M, M) bool [i, j]: i dominates j.

    Deb's constrained dominance: strictly smaller violation dominates;
    equal violation (both feasible included) falls back to Pareto dominance
    on the (latency, energy) objective pair.
    """
    obj = costs[:, :2]
    le = jnp.all(obj[:, None, :] <= obj[None, :, :], axis=-1)
    lt = jnp.any(obj[:, None, :] < obj[None, :, :], axis=-1)
    pdom = le & lt
    v_lt = viol[:, None] < viol[None, :]
    v_eq = viol[:, None] == viol[None, :]
    return v_lt | (v_eq & pdom)


def _front_ranks(dom):
    """(M, M) dominance matrix -> (M,) front index (0 = non-dominated)."""
    M = dom.shape[0]
    big = jnp.int32(M + 1)
    n_dom = jnp.sum(dom, axis=0).astype(jnp.int32)

    def body(r, carry):
        rank, rem = carry
        front = (rem == 0) & (rank == big)
        rank = jnp.where(front, jnp.int32(r), rank)
        freed = jnp.sum(jnp.where(front[:, None], dom, False),
                        axis=0).astype(jnp.int32)
        rem = jnp.where(front, big, rem - freed)
        return rank, rem

    rank, _ = jax.lax.fori_loop(
        0, M, body, (jnp.full((M,), M + 1, jnp.int32), n_dom))
    return rank


def _crowding(obj, rank):
    """(M, 2) objectives + (M,) front ranks -> (M,) crowding distance.

    Mask-based (no data-dependent sort): a point's gap along one objective
    is (nearest strictly-larger value) - (nearest strictly-smaller value)
    within its front, normalized by the front's span; front boundary points
    get +inf.  Deterministic under ties by construction.
    """
    same = rank[:, None] == rank[None, :]
    d = jnp.zeros(obj.shape[0], jnp.float32)
    for k in range(obj.shape[1]):
        v = obj[:, k]
        vmax = jnp.max(jnp.where(same, v[None, :], -jnp.inf), axis=1)
        vmin = jnp.min(jnp.where(same, v[None, :], jnp.inf), axis=1)
        span = jnp.maximum(vmax - vmin, jnp.float32(1e-12))
        gt = same & (v[None, :] > v[:, None])
        lt = same & (v[None, :] < v[:, None])
        upper = jnp.min(jnp.where(gt, v[None, :], jnp.inf), axis=1)
        lower = jnp.max(jnp.where(lt, v[None, :], -jnp.inf), axis=1)
        interior = jnp.isfinite(upper) & jnp.isfinite(lower)
        gap = jnp.where(interior, (upper - lower) / span, jnp.inf)
        d = d + gap
    return d


def _select_best(rank, crowd, n):
    """Indices of the n best by (rank asc, crowding desc, index asc)."""
    crowd_f = jnp.where(jnp.isfinite(crowd), crowd, _BIG)
    return jnp.lexsort((-crowd_f, rank))[:n]


def _tournament(key, rank, crowd, n, pool_size):
    """(n,) winner indices of binary tournaments on (rank, crowding)."""
    k1, k2 = jax.random.split(key)
    i = jax.random.randint(k1, (n,), 0, pool_size)
    j = jax.random.randint(k2, (n,), 0, pool_size)
    crowd_f = jnp.where(jnp.isfinite(crowd), crowd, _BIG)
    ci, cj = crowd_f[i], crowd_f[j]
    ri, rj = rank[i], rank[j]
    i_wins = (ri < rj) | ((ri == rj) & (ci > cj)) | \
        ((ri == rj) & (ci == cj) & (i <= j))
    return jnp.where(i_wins, i, j)


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NSGA2Config:
    population: int = 64
    generations: int = 50
    mutation_rate: float = 0.05
    crossover_rate: float = 0.5   # per-gene uniform-crossover swap prob
    archive: int = 128            # Pareto-archive capacity (frontier slots)
    seed: int = 0


class NSGA2State(NamedTuple):
    """Scan carry: everything a resumed run needs.

    ``pop`` leads (like :class:`~repro.core.ga.GAState`) so the shared
    chunk driver's host-eval loop decodes the right field: it holds the
    *candidates awaiting evaluation*; ``parents``/``parent_costs`` hold the
    current survivors (cost sentinel +inf before the first generation --
    sentinels lose every constrained-dominance comparison against any
    evaluated point, so the first survival keeps exactly the first
    evaluated population).
    """

    pop: jnp.ndarray            # (P, N, genes) int32 candidates to evaluate
    parents: jnp.ndarray        # (P, N, genes) int32 current survivors
    parent_costs: jnp.ndarray   # (P, 4) f32 (lat, en, area, pw) aggregated
    best_val: jnp.ndarray       # () f32 best feasible primary objective
    best_genome: jnp.ndarray    # (N, genes) int32
    arch_genomes: jnp.ndarray   # (A, N, genes) int32 Pareto archive
    arch_costs: jnp.ndarray     # (A, 4) f32; +inf latency = empty slot
    key: jnp.ndarray
    generation: jnp.ndarray     # () int32 generations completed


def _multi_costs(env, ecfg, pe, kt, df):
    """(..., N) raw assignment -> (..., 4) aggregated whole-model costs.

    On a TPU a population-sized batch takes the Pallas broadcast kernel.
    Elsewhere this evaluates through FLAT per-point rows (layer fields
    materialized per point) rather than broadcasting the (N, F) layer table
    against (..., N) assignments: with the broadcast shape XLA hoists
    layer-only subexpressions and reassociates the f32 products, drifting
    an ulp from the serving batcher's flat per-point evaluation.  The flat
    shape is bit-stable across batch sizes, which is what keeps serial
    nsga2 byte-identical to service-batched nsga2 (asserted by
    benchmarks/bench_frontier.py and tests/test_nsga2.py).
    """
    if kops.on_tpu() and getattr(pe, "ndim", 0) == 2:
        lat, en, area, pw = kops.batched_cost(env.layers, pe, kt, df)
    else:
        F = env.layers.shape[-1]
        df = jnp.broadcast_to(jnp.asarray(df, jnp.float32), pe.shape)
        flat = jnp.broadcast_to(env.layers, pe.shape + (F,)).reshape(-1, F)
        out = maestro.evaluate(flat, pe.reshape(-1), kt.reshape(-1),
                               df.reshape(-1))
        lat, en, area, pw = jax.lax.optimization_barrier(
            tuple(a.reshape(pe.shape) for a in
                  (out.latency, out.energy, out.area, out.power)))
    tl, te, ta, tp, _ = env_lib.aggregate_costs_multi(
        lat, en, area, pw, ecfg, env.budget)
    return jnp.stack([tl, te, ta, tp], axis=-1)


def make_nsga2_engine(env: env_lib.EnvArrays, ecfg: env_lib.EnvConfig,
                      cfg: NSGA2Config) -> ga_lib.GAEngine:
    """NSGA-II as a :class:`~repro.core.ga.GAEngine`: same contract, (P, 4)
    fitness.  ``run_chunked_engine`` drives it exactly like the GAs."""
    N = env.num_layers
    P = cfg.population
    A = cfg.archive
    L = ecfg.levels
    n_df = 3 if ecfg.mix else 1
    genes = 3 if ecfg.mix else 2
    cons_col = 2 if ecfg.constraint == "area" else 3

    def decode(genome):
        pe = env.pe_table[genome[..., 0]]
        kt = env.kt_table[genome[..., 1]]
        df = (genome[..., 2] if ecfg.mix
              else jnp.asarray(ecfg.dataflow, jnp.int32))
        return pe, kt, df

    def fitness(pop):
        pe, kt, df = decode(pop)
        return _multi_costs(env, ecfg, pe, kt, df)   # (P, 4)

    def _update_archive(arch_genomes, arch_costs, pop, fit):
        """Archive ∪ newly evaluated pop -> non-dominated feasible top-A."""
        pool_g = jnp.concatenate([arch_genomes, pop], axis=0)    # (A+P,...)
        pool_c = jnp.concatenate([arch_costs, fit], axis=0)      # (A+P, 4)
        viol = _violation(pool_c, cons_col, env.budget)
        valid = (viol == 0) & jnp.isfinite(pool_c[:, 0])
        obj = jnp.where(valid[:, None], pool_c[:, :2], jnp.inf)
        le = jnp.all(obj[:, None, :] <= obj[None, :, :], axis=-1)
        lt = jnp.any(obj[:, None, :] < obj[None, :, :], axis=-1)
        dominated = jnp.any(le & lt & valid[:, None], axis=0)
        # Dedup identical objective pairs (keep the lowest index).
        idx = jnp.arange(obj.shape[0])
        eq = jnp.all(obj[:, None, :] == obj[None, :, :], axis=-1)
        dup = jnp.any(eq & (idx[None, :] < idx[:, None]), axis=1)
        keep = valid & ~dominated & ~dup
        # One-shot crowding truncation to A slots (rank 0 = the keepers).
        crowd = _crowding(obj, jnp.where(keep, 0, 1).astype(jnp.int32))
        crowd_f = jnp.where(jnp.isfinite(crowd), crowd, _BIG)
        score = jnp.where(keep, -crowd_f, jnp.inf)
        sel = jnp.argsort(score)[:A]
        kept = keep[sel]
        new_g = jnp.where(kept[:, None, None], pool_g[sel], 0)
        new_c = jnp.where(kept[:, None], pool_c[sel], jnp.inf)
        return new_g.astype(jnp.int32), new_c

    def evolve(state: NSGA2State, fit):
        (pop, parents, parent_costs, best_val, best_genome,
         arch_genomes, arch_costs, key, gen) = state
        # 1. Environmental selection over parents ∪ evaluated children.
        cand = jnp.concatenate([parents, pop], axis=0)           # (2P,...)
        costs = jnp.concatenate([parent_costs, fit], axis=0)     # (2P, 4)
        viol = _violation(costs, cons_col, env.budget)
        rank = _front_ranks(_constrained_dominance(costs, viol))
        crowd = _crowding(costs[:, :2], rank)
        sel = _select_best(rank, crowd, P)
        parents = cand[sel]
        parent_costs = costs[sel]
        # 2. Scalar best-so-far (the unified history/best_value contract:
        #    the env's primary objective over feasible points only).
        child_viol = _violation(fit, cons_col, env.budget)
        child_obj = env_lib.select_objective(fit[:, 0], fit[:, 1], ecfg)
        child_val = jnp.where(child_viol == 0, child_obj, jnp.inf)
        i_best = jnp.argmin(child_val)
        better = child_val[i_best] < best_val
        best_val = jnp.where(better, child_val[i_best], best_val)
        best_genome = jnp.where(better, pop[i_best], best_genome)
        # 3. Pareto archive update from the newly evaluated points.
        arch_genomes, arch_costs = _update_archive(
            arch_genomes, arch_costs, pop, fit)
        # 4. Breed the next candidate population by binary tournament on
        #    the survivors' (rank, crowding), uniform crossover, mutation.
        key, k1, k2, k3, k4, k5 = jax.random.split(key, 6)
        rank_p, crowd_p = rank[sel], crowd[sel]
        pa = _tournament(k1, rank_p, crowd_p, P, P)
        pb = _tournament(k2, rank_p, crowd_p, P, P)
        cx = jax.random.uniform(k3, (P, N, genes)) < cfg.crossover_rate
        children = jnp.where(cx, parents[pb], parents[pa])
        mut = jax.random.uniform(k4, children.shape) < cfg.mutation_rate
        rand = jax.random.randint(k5, children.shape, 0, L)
        if ecfg.mix:
            rand = rand.at[..., 2].set(
                jax.random.randint(jax.random.fold_in(k5, 1),
                                   children.shape[:-1], 0, n_df))
        children = jnp.where(mut, rand, children)
        return NSGA2State(children, parents, parent_costs, best_val,
                          best_genome, arch_genomes, arch_costs, key,
                          gen + 1), best_val

    def gen_step(carry: NSGA2State, _):
        # The barrier pins each generation's arithmetic: XLA unrolls short
        # scans and would otherwise fuse across iterations, so a chunk=1
        # run could drift an ulp from a one-shot run of the same seed.
        state, best = evolve(carry, fitness(carry.pop))
        return jax.lax.optimization_barrier(state), best

    def init_carry(seed) -> NSGA2State:
        key = jax.random.PRNGKey(seed)
        key, k0 = jax.random.split(key)
        pop = jax.random.randint(k0, (P, N, genes), 0, L)
        if ecfg.mix:
            pop = pop.at[..., 2].set(
                jax.random.randint(jax.random.fold_in(k0, 7), (P, N), 0,
                                   n_df))
        return NSGA2State(
            pop=pop,
            parents=jnp.zeros((P, N, genes), jnp.int32),
            parent_costs=jnp.full((P, 4), jnp.inf, jnp.float32),
            best_val=jnp.float32(jnp.inf),
            best_genome=jnp.zeros((N, genes), jnp.int32),
            arch_genomes=jnp.zeros((A, N, genes), jnp.int32),
            arch_costs=jnp.full((A, 4), jnp.inf, jnp.float32),
            key=key,
            generation=jnp.zeros((), jnp.int32))

    return ga_lib.GAEngine(init_carry, gen_step, decode, fitness, evolve)


def run_nsga2_search(workload, ecfg: env_lib.EnvConfig,
                     cfg: NSGA2Config = NSGA2Config(),
                     state: Optional[NSGA2State] = None,
                     chunk: Optional[int] = None,
                     on_chunk=None,
                     eval_fn=None,
                     env: Optional[env_lib.EnvArrays] = None):
    """Chunked, resumable NSGA-II.  Returns (NSGA2State, (gens,) history).

    Same lifecycle as :func:`repro.core.ga.run_ga_search`: runs
    ``cfg.generations`` *more* generations from ``state`` (fresh when
    None) in ``chunk``-sized pieces, firing ``on_chunk(state, hist,
    gens_done)`` between them; ``eval_fn(pe, kt, df) -> (P, 4) aggregated
    costs`` moves fitness evaluation to the host (the search service
    injects its cross-request batcher).  Chunk boundaries and the eval
    path never change the result -- byte-identical states/histories.
    """
    if env is None:
        env = env_lib.make_env(workload, ecfg)
    progs = ga_lib.engine_programs("nsga2", make_nsga2_engine, env, ecfg,
                                   cfg=cfg)
    if state is None:
        state = progs.engine.init_carry(cfg.seed)
    return ga_lib.run_chunked_engine(env, ecfg, progs, state,
                                     cfg.generations, chunk, on_chunk,
                                     eval_fn, mix_df=ecfg.mix)


def frontier_points(state: NSGA2State) -> np.ndarray:
    """The archive's live frontier as an (F, 4) float array sorted by
    latency (the per-chunk snapshot the outcome's frontier trace records)."""
    costs = np.asarray(state.arch_costs, np.float64)
    costs = costs[np.isfinite(costs[:, 0])]
    return costs[np.argsort(costs[:, 0], kind="stable")]


def nsga2_frontier(env: env_lib.EnvArrays, ecfg: env_lib.EnvConfig,
                   state: NSGA2State) -> Dict[str, np.ndarray]:
    """Decode the final archive: the non-dominated feasible designs.

    Returns arrays sorted by latency -- ``lat``/``en``/``area``/``pw`` of
    shape (F,) plus the raw per-layer assignments ``pe``/``kt``/``df`` of
    shape (F, N) that realize each point.
    """
    costs = np.asarray(state.arch_costs, np.float64)
    genomes = np.asarray(state.arch_genomes)
    valid = np.isfinite(costs[:, 0])
    costs, genomes = costs[valid], genomes[valid]
    order = np.argsort(costs[:, 0], kind="stable")
    costs, genomes = costs[order], genomes[order]
    pe = np.asarray(env.pe_table, np.float32)[genomes[..., 0]]
    kt = np.asarray(env.kt_table, np.float32)[genomes[..., 1]]
    if ecfg.mix:
        df = genomes[..., 2].astype(np.int32)
    else:
        df = np.full(genomes.shape[:2], ecfg.dataflow, np.int32)
    return {"lat": costs[:, 0], "en": costs[:, 1], "area": costs[:, 2],
            "pw": costs[:, 3], "pe": pe, "kt": kt, "df": df}


def nsga2_solution(env: env_lib.EnvArrays, ecfg: env_lib.EnvConfig,
                   state: NSGA2State):
    """Decode the best-primary-objective genome to raw (pe, kt, df)."""
    return ga_lib.ga_solution(env, ecfg, state)
