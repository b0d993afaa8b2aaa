"""Genetic algorithms: the stage-2 local fine-tuner (SIII-G) and the
general-GA baseline (SIV-A3).

Both operate on genomes of 2N genes -- per-layer (PE, Buf) -- plus an
optional dataflow gene for MIX.  The baseline GA works in the coarse L-level
space; the local fine-tuner works in the *raw* integer space around the
stage-1 solution with the paper's conservative operators:

  * local mutation   -- a gene moves at most +-step from its current value
                        (SIII-G "for a gene representing PE=64 ... mutate to
                        value in the range [60, 68] when the step is 4")
  * local crossover  -- *within* one genome: swap the (PE, Buf) pairs of two
                        layers, preserving the learnt budget split

Fitness = whole-model objective, +inf when the platform constraint is
violated.  Fully vectorized: one generation = one batched cost-model call.

Both GAs are **chunked, resumable engines** with the same lifecycle as
``reinforce.run_search``/``rl_baselines.run_ac_search``: the generation scan
runs in fixed-size chunks, ``on_chunk(state, chunk_hist, gens_done)`` fires
between chunks (the unified API streams progress and observes cancellation
there), and the returned :class:`GAState` feeds back in via ``state=`` to
continue a run bit-identically.  Each engine splits one generation into a
*fitness* half and an *evolve* half so a host-side ``eval_fn`` (the search
service's cross-request :class:`~repro.serving.batcher.CostEvalBatcher`) can
own the fitness evaluation; the fitness values are bit-identical whichever
path computes them, so batched outcomes equal in-graph ones byte for byte.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chunk as chunk_lib
from repro.core import env as env_lib
from repro.core import programs
from repro.obs import instrument as obs_instrument
from repro.costmodel import dataflows as dfl
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class GAConfig:
    population: int = 100
    generations: int = 50
    mutation_rate: float = 0.05
    crossover_rate: float = 0.05
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class LocalGAConfig:
    population: int = 20
    generations: int = 2000
    mutation_rate: float = 0.05
    crossover_rate: float = 0.2
    mutation_step: int = 4       # raw-space +-step (PE); kt uses step 1
    seed: int = 0


class GAState(NamedTuple):
    """Scan carry of either GA: everything a resumed run needs."""

    pop: jnp.ndarray             # (P, N, genes) int32
    best_val: jnp.ndarray        # () f32 best feasible objective so far
    best_genome: jnp.ndarray     # (N, genes) int32
    key: jnp.ndarray
    generation: jnp.ndarray      # () int32 generations completed


class GAEngine(NamedTuple):
    """Building blocks of one GA run.

    ``gen_step(state, _) == evolve(state, fitness(state.pop))`` -- the scan
    body of the in-graph path.  The split exists so a host-side ``eval_fn``
    can own the fitness half (search-service batching) while ``evolve``
    stays the one compiled selection/breeding program either way.
    """

    init_carry: Callable         # seed -> GAState
    gen_step: Callable           # (GAState, _) -> (GAState, best_val)
    decode: Callable             # genome levels -> (pe, kt, df) raw
    fitness: Callable            # pop -> (P,) objective-or-inf
    evolve: Callable             # (GAState, fit) -> (GAState, best_val)


class GAResult(NamedTuple):
    best_value: jnp.ndarray      # () objective; inf if nothing feasible
    best_pe: jnp.ndarray         # (N,) raw PE counts
    best_kt: jnp.ndarray         # (N,) raw tile counts
    best_df: jnp.ndarray         # (N,) dataflow ids
    history: jnp.ndarray         # (generations,) best-so-far trace
    evals: int


def _fitness(env, ecfg, pe, kt, df):
    # On a TPU a population is the Pallas kernel's shape: (B, N) design
    # points against the (N, NUM_FIELDS) workload.
    if kops.on_tpu() and getattr(pe, "ndim", 0) == 2:
        lat, en, area, pw = kops.batched_cost(env.layers, pe, kt, df)
        perf, _, feas = env_lib.aggregate_costs(lat, en, area, pw, ecfg,
                                                env.budget)
        return jnp.where(feas, perf, jnp.inf)
    return _oracle_fitness(env, ecfg, pe, kt, df)


def _oracle_fitness(env, ecfg, pe, kt, df):
    perf, _, feas = env_lib.genome_cost(env, ecfg, pe, kt, df)
    return jnp.where(feas, perf, jnp.inf)


# ---------------------------------------------------------------------------
# Baseline GA (coarse level space).
# ---------------------------------------------------------------------------
def make_ga_engine(env: env_lib.EnvArrays, ecfg: env_lib.EnvConfig,
                   cfg: GAConfig) -> GAEngine:
    """The baseline GA's :class:`GAEngine` for one environment.

    ``init_carry(seed)`` builds the scan carry for one independent GA run;
    ``gen_step`` is seed-free, so the fanout device backend can shard_map one
    compiled generation scan across devices whose carries differ only in
    their seed.  ``run_ga_search`` below is the chunked single-run driver.
    """
    N = env.num_layers
    P = cfg.population
    L = ecfg.levels
    n_df = 3 if ecfg.mix else 1
    genes = 3 if ecfg.mix else 2

    def decode(genome):
        pe = env.pe_table[genome[..., 0]]
        kt = env.kt_table[genome[..., 1]]
        df = (genome[..., 2] if ecfg.mix
              else jnp.asarray(ecfg.dataflow, jnp.int32))
        return pe, kt, df

    def fitness(pop):
        pe, kt, df = decode(pop)
        return _fitness(env, ecfg, pe, kt, df)   # (P,)

    def evolve(state: GAState, fit):
        pop, best_val, best_genome, key, gen = state
        order = jnp.argsort(fit)
        pop = pop[order]
        fit = fit[order]
        better = fit[0] < best_val
        best_val = jnp.where(better, fit[0], best_val)
        best_genome = jnp.where(better, pop[0], best_genome)
        # Elitist half survives; children from random parent pairs.
        half = P // 2
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        pa = jax.random.randint(k1, (P - half,), 0, half)
        pb = jax.random.randint(k2, (P - half,), 0, half)
        cx_mask = (jax.random.uniform(k3, (P - half, N, pop.shape[-1]))
                   < cfg.crossover_rate)
        children = jnp.where(cx_mask, pop[pb], pop[pa])
        mut_mask = (jax.random.uniform(k4, children.shape)
                    < cfg.mutation_rate)
        key, k5 = jax.random.split(key)
        rand = jax.random.randint(k5, children.shape, 0, L)
        if ecfg.mix:
            rand = rand.at[..., 2].set(
                jax.random.randint(jax.random.fold_in(k5, 1),
                                   children.shape[:-1], 0, n_df))
        children = jnp.where(mut_mask, rand, children)
        pop = jnp.concatenate([pop[:half], children], axis=0)
        return GAState(pop, best_val, best_genome, key, gen + 1), best_val

    def gen_step(carry: GAState, _):
        return evolve(carry, fitness(carry.pop))

    def init_carry(seed) -> GAState:
        key = jax.random.PRNGKey(seed)
        key, k0 = jax.random.split(key)
        pop = jax.random.randint(k0, (P, N, genes), 0, L)
        if ecfg.mix:
            pop = pop.at[..., 2].set(
                jax.random.randint(jax.random.fold_in(k0, 7), (P, N), 0, 3))
        return GAState(pop, jnp.float32(jnp.inf),
                       jnp.zeros((N, genes), jnp.int32), key,
                       jnp.zeros((), jnp.int32))

    return GAEngine(init_carry, gen_step, decode, fitness, evolve)


class EnginePrograms(NamedTuple):
    """A population engine and its jitted programs, kept per program key."""

    name: str                    # engine label of metrics and spans
    engine: GAEngine
    scan_chunk: Callable         # jit: (state, n) -> n-generation scan
    evolve: Callable             # jit: engine.evolve


def engine_programs(name: str, make_engine: Callable, env, ecfg, *args,
                    cfg) -> EnginePrograms:
    """``make_engine(env, ecfg, *args, cfg)`` and its jitted programs, from
    the ``name`` engine's process-wide program cache
    (:mod:`repro.core.programs`).

    The key is exactly what ``make_engine`` is given: ``ecfg``, ``cfg`` with
    its seed and number of generations zeroed (they reach the program only
    through its state and the static chunk length, and ``make_engine`` sees
    the zeroed copy), and the contents of ``env`` and ``args``.  A search of
    a key seen before gets the earlier search's engine and ``jax.jit``
    objects, so it traces nothing.
    """
    cfg = dataclasses.replace(cfg, seed=0, generations=0)
    key = (ecfg, cfg, programs.digest(*env, *args))

    def build():
        engine = make_engine(env, ecfg, *args, cfg)

        @functools.partial(jax.jit, static_argnames=("n",))
        def scan_chunk(state, n):
            return jax.lax.scan(engine.gen_step, state, None, length=n)

        return EnginePrograms(name, engine, scan_chunk,
                              jax.jit(engine.evolve))

    return programs.cache(name).get(key, build)


def run_chunked_engine(env, ecfg, progs: EnginePrograms, state,
                       generations: int, chunk: Optional[int], on_chunk,
                       eval_fn, mix_df: bool, raw_genome: bool = False,
                       fixed_df=None):
    """Shared chunk driver for every population engine.  Returns
    (state, (gens,) history).

    Drives both GAs here and the NSGA-II engine in ``core/nsga2.py``: any
    engine whose state leads with a ``pop`` field of candidates awaiting
    evaluation and whose ``evolve(state, fit)`` consumes their fitness
    (scalar (P,) or multi-objective (P, 4)) gets chunking, resume,
    cancellation and eval_fn injection from this one loop (via
    :func:`repro.core.chunk.drive`, which also tags each chunk's telemetry
    with ``progs.name`` -- one hard eval per population member per
    generation).

    ``eval_fn=None`` scans ``gen_step`` in jitted chunks (fitness stays in
    the XLA program); with ``eval_fn(pe, kt, df) -> (P,) fitness`` each
    generation decodes on the host, evaluates through the injected function
    (the service's cross-request batcher) and applies the same compiled
    ``evolve`` step; with telemetry on, each such generation is a
    ``search.step`` span around a ``search.eval`` span of its eval_fn
    wait.  Both paths produce byte-identical states/histories:
    the decode is the same table gather, the fitness values are bit-equal
    (asserted in tests/test_search_service.py), and every other op is the
    identical jnp program.

    ``progs`` comes from :func:`engine_programs`: the jitted ``gen_step``
    scan or ``evolve`` is the one every search of its key shares.
    """
    pop_size = int(state.pop.shape[0])
    engine_name = progs.name
    if eval_fn is None:
        def run_chunk(state, n):
            state, h = progs.scan_chunk(state, n)
            return state, np.asarray(h)

        state, hist = chunk_lib.drive(
            state, generations, chunk, run_chunk, on_chunk,
            engine=engine_name, evals_per_step=pop_size)
        return state, chunk_lib.concat_hist(hist)

    evolve = progs.evolve
    pe_table = np.asarray(env.pe_table, np.float32)
    kt_table = np.asarray(env.kt_table, np.float32)

    step_s = obs_instrument.SEARCH_STEP_SECONDS
    eval_s = obs_instrument.SEARCH_EVAL_WAIT_SECONDS

    def run_chunk(state, n):
        h = np.empty((n,), np.float32)
        for g in range(n):
            with obs_instrument.timed("search.step", step_s,
                                      engine=engine_name):
                pop = np.asarray(state.pop)
                if raw_genome:
                    pe = pop[..., 0].astype(np.float32)
                    kt = pop[..., 1].astype(np.float32)
                else:
                    pe = pe_table[pop[..., 0]]
                    kt = kt_table[pop[..., 1]]
                if fixed_df is not None:
                    df = fixed_df
                elif mix_df:
                    df = pop[..., 2].astype(np.float32)
                else:
                    df = np.float32(ecfg.dataflow)
                with obs_instrument.timed("search.eval", eval_s,
                                          engine=engine_name):
                    fit = np.asarray(eval_fn(pe, kt, df), np.float32)
                state, bv = evolve(state, jnp.asarray(fit))
                h[g] = np.float32(bv)
        return state, h

    state, hist = chunk_lib.drive(
        state, generations, chunk, run_chunk, on_chunk,
        engine=engine_name, evals_per_step=pop_size)
    return state, chunk_lib.concat_hist(hist)


def run_ga_search(workload, ecfg: env_lib.EnvConfig,
                  cfg: GAConfig = GAConfig(),
                  state: Optional[GAState] = None,
                  chunk: Optional[int] = None,
                  on_chunk=None,
                  eval_fn=None,
                  env: Optional[env_lib.EnvArrays] = None):
    """Chunked, resumable baseline GA.  Returns (GAState, (gens,) history).

    Runs ``cfg.generations`` *more* generations from ``state`` (fresh run
    when None), in chunks of ``chunk`` generations (default: one chunk).
    ``on_chunk(state, chunk_hist, gens_done)`` fires between chunks -- the
    unified API streams progress and observes cancellation there, exactly
    like ``reinforce.run_search``.  ``eval_fn(pe, kt, df) -> (P,) fitness``
    moves the per-generation fitness evaluation to the host (the search
    service injects its cross-request batcher); results are byte-identical
    either way.  Chunk boundaries never change the result.
    """
    if env is None:
        env = env_lib.make_env(workload, ecfg)
    progs = engine_programs("ga", make_ga_engine, env, ecfg, cfg=cfg)
    if state is None:
        state = progs.engine.init_carry(cfg.seed)
    return run_chunked_engine(env, ecfg, progs, state, cfg.generations,
                              chunk, on_chunk, eval_fn, mix_df=ecfg.mix)


def ga_solution(env: env_lib.EnvArrays, ecfg: env_lib.EnvConfig,
                state: GAState):
    """Decode a baseline-GA state's best genome to raw (pe, kt, df)."""
    pe = env.pe_table[state.best_genome[..., 0]]
    kt = env.kt_table[state.best_genome[..., 1]]
    df = (state.best_genome[..., 2] if ecfg.mix
          else jnp.asarray(ecfg.dataflow, jnp.int32))
    return pe, kt, jnp.broadcast_to(df, (env.num_layers,))


def baseline_ga(workload, ecfg: env_lib.EnvConfig,
                cfg: GAConfig = GAConfig()) -> GAResult:
    env = env_lib.make_env(workload, ecfg)
    state, hist = run_ga_search(workload, ecfg, cfg, env=env)
    pe, kt, df = ga_solution(env, ecfg, state)
    return GAResult(state.best_val, pe, kt, df, hist,
                    cfg.population * cfg.generations)


# ---------------------------------------------------------------------------
# Stage-2 local GA (fine-grained raw space, seeded by the RL solution).
# ---------------------------------------------------------------------------
def make_local_ga_engine(env: env_lib.EnvArrays, ecfg: env_lib.EnvConfig,
                         init_pe, init_kt, init_df,
                         cfg: LocalGAConfig) -> GAEngine:
    """The fine-tuner's :class:`GAEngine`: raw-space genomes, fixed df."""
    N = env.num_layers
    P = cfg.population

    init_genome = jnp.stack(
        [jnp.asarray(init_pe, jnp.int32), jnp.asarray(init_kt, jnp.int32)],
        axis=-1)                                         # (N, 2)
    df = jnp.asarray(init_df, jnp.int32)                 # (N,) fixed in stage 2

    def mutate(genome, key):
        k1, k2 = jax.random.split(key)
        mask = jax.random.uniform(k1, genome.shape) < cfg.mutation_rate
        step = jnp.stack([
            jax.random.randint(k2, genome.shape[:-1],
                               -cfg.mutation_step, cfg.mutation_step + 1),
            jax.random.randint(jax.random.fold_in(k2, 1), genome.shape[:-1],
                               -1, 2)], axis=-1)
        out = jnp.where(mask, genome + step, genome)
        lo = jnp.array([dfl.PE_MIN, dfl.KT_MIN])
        hi = jnp.array([dfl.PE_MAX, dfl.KT_MAX])
        return jnp.clip(out, lo, hi)

    def self_crossover(genome, key):
        """Swap the (PE, Buf) pairs of two random layers (SIII-G)."""
        k1, k2, k3 = jax.random.split(key, 3)
        i = jax.random.randint(k1, (), 0, N)
        j = jax.random.randint(k2, (), 0, N)
        do = jax.random.uniform(k3) < cfg.crossover_rate
        gi, gj = genome[i], genome[j]
        swapped = genome.at[i].set(gj).at[j].set(gi)
        return jnp.where(do, swapped, genome)

    def decode(genome):
        return (genome[..., 0].astype(jnp.float32),
                genome[..., 1].astype(jnp.float32), df)

    def fitness(pop):
        pe, kt, _ = decode(pop)
        return _oracle_fitness(env, ecfg, pe, kt, df)

    def evolve(state: GAState, fit):
        pop, best_val, best_genome, key, gen = state
        order = jnp.argsort(fit)
        pop, fit = pop[order], fit[order]
        better = fit[0] < best_val
        best_val = jnp.where(better, fit[0], best_val)
        best_genome = jnp.where(better, pop[0], best_genome)
        half = P // 2
        key, k1, k2, k3 = jax.random.split(key, 4)
        parents = pop[jax.random.randint(k1, (P - half,), 0, half)]
        children = jax.vmap(self_crossover)(
            parents, jax.random.split(k2, P - half))
        children = jax.vmap(mutate)(children, jax.random.split(k3, P - half))
        pop = jnp.concatenate([pop[:half], children], axis=0)
        return GAState(pop, best_val, best_genome, key, gen + 1), best_val

    def gen_step(carry: GAState, _):
        return evolve(carry, fitness(carry.pop))

    def init_carry(seed) -> GAState:
        pop = jnp.broadcast_to(init_genome, (P, N, 2)).astype(jnp.int32)
        return GAState(pop, jnp.float32(jnp.inf), init_genome,
                       jax.random.PRNGKey(seed), jnp.zeros((), jnp.int32))

    return GAEngine(init_carry, gen_step, decode, fitness, evolve)


def run_local_ga(workload, ecfg: env_lib.EnvConfig,
                 init_pe, init_kt, init_df,
                 cfg: LocalGAConfig = LocalGAConfig(),
                 state: Optional[GAState] = None,
                 chunk: Optional[int] = None,
                 on_chunk=None,
                 eval_fn=None,
                 env: Optional[env_lib.EnvArrays] = None):
    """Chunked, resumable stage-2 fine-tune; same contract as run_ga_search.

    The dataflow assignment is frozen at ``init_df`` (stage 2 fine-tunes
    only the budget split), so ``eval_fn`` always receives that fixed array.
    """
    if env is None:
        env = env_lib.make_env(workload, ecfg)
    progs = engine_programs("local_ga", make_local_ga_engine, env, ecfg,
                            init_pe, init_kt, init_df, cfg=cfg)
    if state is None:
        state = progs.engine.init_carry(cfg.seed)
    fixed_df = np.asarray(init_df, np.float32) if eval_fn is not None else None
    return run_chunked_engine(env, ecfg, progs, state, cfg.generations,
                              chunk, on_chunk, eval_fn, mix_df=False,
                              raw_genome=True, fixed_df=fixed_df)


def local_ga(workload, ecfg: env_lib.EnvConfig,
             init_pe, init_kt, init_df,
             cfg: LocalGAConfig = LocalGAConfig()) -> GAResult:
    state, hist = run_local_ga(workload, ecfg, init_pe, init_kt, init_df, cfg)
    df = jnp.asarray(init_df, jnp.int32)
    return GAResult(state.best_val,
                    state.best_genome[..., 0].astype(jnp.float32),
                    state.best_genome[..., 1].astype(jnp.float32),
                    df, hist, cfg.population * cfg.generations)
