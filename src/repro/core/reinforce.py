"""ConfuciuX stage 1: REINFORCE global search (SIII-A..F).

Faithful elements (paper section in brackets):
  * LSTM(128) policy, one (PE, Buf) action pair per layer [III-A2, III-C]
  * observation Eq. (1), normalized to [-1, 1]                       [III-B]
  * reward  R = P_t - P_min  with the *global* running minimum P_min
    tracked across all time-steps and epochs (P = -objective, so rewards
    are always >= 0 while feasible)                                  [III-E]
  * violation penalty = -(accumulated episode reward), episode ends  [III-E]
  * discount d = 0.9; per-episode reward standardization             [III-E]
  * episode terminates after 2N actions (N steps of action pairs) or on
    constraint violation                                             [III-A]
  * MIX: optional third per-layer action choosing the dataflow style [IV-D]

Beyond-paper (ablatable, see EXPERIMENTS.md SPerf): the environment is inside
the XLA program, episodes are batched with vmap (episodes_per_epoch = 1
reproduces the paper's setting), and whole epoch-chunks run under lax.scan.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import chunk as chunk_lib
from repro.core import env as env_lib
from repro.core import policy as policy_lib
from repro.core import programs
from repro.costmodel import maestro
from repro.training import optim


@dataclasses.dataclass(frozen=True)
class ReinforceConfig:
    epochs: int = 5000
    episodes_per_epoch: int = 1   # 1 == the paper's setting
    lr: float = 3e-3
    discount: float = 0.9         # the paper's d
    entropy_coef: float = 0.0     # 0.0 == faithful; >0 helps tiny workloads
    seed: int = 0


class SearchState(NamedTuple):
    params: dict
    opt_state: optim.OptState
    pmin: jnp.ndarray        # () running min of P_t across steps & epochs
    best_value: jnp.ndarray  # () best feasible objective so far
    best_pe_lvl: jnp.ndarray  # (N,) int32
    best_kt_lvl: jnp.ndarray  # (N,) int32
    best_df: jnp.ndarray      # (N,) int32
    key: jnp.ndarray
    epoch: jnp.ndarray


class RolloutOut(NamedTuple):
    rewards: jnp.ndarray   # (N,)
    logps: jnp.ndarray     # (N,)
    entropy: jnp.ndarray   # (N,)
    mask: jnp.ndarray      # (N,) 1.0 while alive at step entry
    perf: jnp.ndarray      # (N,) raw objective per layer (positive)
    actions: jnp.ndarray   # (N, 3) int32 (pe_lvl, kt_lvl, df)
    feasible: jnp.ndarray  # () bool -- never violated
    model_value: jnp.ndarray  # () sum of per-layer objective
    pmin: jnp.ndarray      # () updated running min


def make_rollout(ecfg: env_lib.EnvConfig, pcfg: policy_lib.PolicyConfig,
                 env: env_lib.EnvArrays, discount: float):
    """Build rollout(params, pmin, key) -> RolloutOut for a fixed env."""
    N = env.num_layers
    t_norm = 2.0 * jnp.arange(N, dtype=jnp.float32) / max(N - 1, 1) - 1.0
    Lm1 = max(pcfg.levels - 1, 1)

    def _make_step_fn(params):
      def step_fn(carry, xs):
        (pstate, prev_pe, prev_kt, prev_df, budget_left, alive, acc_r,
         pmin_run, key) = carry
        sobs, layer_t, tn = xs
        dyn = [prev_pe, prev_kt] + ([prev_df] if ecfg.mix else []) + [tn]
        obs = jnp.concatenate([sobs, jnp.stack(dyn)])
        logits, pstate2 = policy_lib.step(params, pcfg, obs, pstate)
        key, k1, k2, k3 = jax.random.split(key, 4)
        a_pe, lp_pe, ent_pe = policy_lib.sample_action(k1, logits[0])
        a_kt, lp_kt, ent_kt = policy_lib.sample_action(k2, logits[1])
        if ecfg.mix:
            a_df, lp_df, ent_df = policy_lib.sample_action(k3, logits[2])
        else:
            a_df = jnp.asarray(ecfg.dataflow, jnp.int32)
            lp_df = jnp.zeros(())
            ent_df = jnp.zeros(())
        pe = env.pe_table[a_pe]
        kt = env.kt_table[a_kt]
        out = maestro.evaluate(layer_t, pe, kt, a_df)
        perf_pos = (out.latency if ecfg.objective == "latency"
                    else out.energy)
        cons = out.area if ecfg.constraint == "area" else out.power
        P_t = -perf_pos  # higher is better
        if ecfg.scenario == "LP":
            budget_left2 = budget_left - cons
            viol = alive & (budget_left2 < 0)
        else:  # LS: the single design must fit the budget at every layer
            budget_left2 = budget_left
            viol = alive & (cons > env.budget)
        pmin2 = jnp.where(alive, jnp.minimum(pmin_run, P_t), pmin_run)
        r_ok = P_t - pmin2                       # >= 0 by construction
        r = jnp.where(viol, -acc_r, r_ok) * alive
        acc_r2 = acc_r + jnp.where(alive & ~viol, r, 0.0)
        mask = alive.astype(jnp.float32)
        alive2 = alive & ~viol
        carry2 = (pstate2,
                  2.0 * a_pe / Lm1 - 1.0, 2.0 * a_kt / Lm1 - 1.0,
                  a_df.astype(jnp.float32) - 1.0,
                  budget_left2, alive2, acc_r2, pmin2, key)
        outs = (r, lp_pe + lp_kt + lp_df, ent_pe + ent_kt + ent_df,
                mask, perf_pos,
                jnp.stack([a_pe, a_kt, a_df]).astype(jnp.int32))
        return carry2, outs

      return step_fn

    def rollout(params, pmin, key) -> RolloutOut:
        init = (policy_lib.init_state(pcfg),
                jnp.float32(-1.0), jnp.float32(-1.0), jnp.float32(-1.0),
                env.budget, jnp.asarray(True), jnp.float32(0.0),
                pmin, key)
        carry, outs = jax.lax.scan(
            _make_step_fn(params), init, (env.static_obs, env.layers, t_norm))
        (_, _, _, _, _, alive_end, _, pmin_out, _) = carry
        r, logps, ents, mask, perf, actions = outs
        return RolloutOut(
            rewards=r, logps=logps, entropy=ents, mask=mask, perf=perf,
            actions=actions, feasible=alive_end,
            model_value=jnp.sum(perf * mask), pmin=pmin_out)

    return rollout


def _discounted_returns(rewards, discount):
    def f(g, r_t):
        g2 = r_t + discount * g
        return g2, g2

    _, G = jax.lax.scan(f, jnp.float32(0.0), rewards[::-1])
    return G[::-1]


def make_epoch_fn(ecfg: env_lib.EnvConfig, pcfg: policy_lib.PolicyConfig,
                  rcfg: ReinforceConfig, env: env_lib.EnvArrays,
                  opt: optim.Adam):
    """Build the jitted epoch update: E episodes -> policy-gradient step."""
    rollout = make_rollout(ecfg, pcfg, env, rcfg.discount)
    E = rcfg.episodes_per_epoch

    def loss_fn(params, pmin, keys):
        rolls = jax.vmap(lambda k: rollout(params, pmin, k))(keys)
        G = jax.vmap(lambda r: _discounted_returns(r, rcfg.discount))(
            rolls.rewards * rolls.mask)
        n_valid = jnp.maximum(rolls.mask.sum(axis=1), 1.0)
        mean = (G * rolls.mask).sum(axis=1) / n_valid
        var = (jnp.square(G - mean[:, None]) * rolls.mask).sum(axis=1) / n_valid
        G_std = (G - mean[:, None]) / (jnp.sqrt(var)[:, None] + 1e-8)
        pg = -(rolls.logps * jax.lax.stop_gradient(G_std)
               * rolls.mask).sum(axis=1)
        ent = (rolls.entropy * rolls.mask).sum(axis=1)
        loss = jnp.mean(pg) - rcfg.entropy_coef * jnp.mean(ent)
        return loss, rolls

    def epoch_fn(state: SearchState, _):
        key, sub = jax.random.split(state.key)
        keys = jax.random.split(sub, E)
        (loss, rolls), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, state.pmin, keys)
        params, opt_state = opt.update(grads, state.opt_state, state.params)
        # Track the best feasible whole-model solution seen so far.
        values = jnp.where(rolls.feasible, rolls.model_value, jnp.inf)
        i = jnp.argmin(values)
        better = values[i] < state.best_value
        best_value = jnp.where(better, values[i], state.best_value)
        pick = lambda new, old: jnp.where(better, new, old)
        new_state = SearchState(
            params=params, opt_state=opt_state,
            pmin=jnp.min(rolls.pmin),
            best_value=best_value,
            best_pe_lvl=pick(rolls.actions[i, :, 0], state.best_pe_lvl),
            best_kt_lvl=pick(rolls.actions[i, :, 1], state.best_kt_lvl),
            best_df=pick(rolls.actions[i, :, 2], state.best_df),
            key=key, epoch=state.epoch + 1)
        metrics = {
            "loss": loss,
            "best_value": best_value,
            "mean_value": jnp.mean(rolls.model_value),
            "feasible_frac": jnp.mean(rolls.feasible.astype(jnp.float32)),
            "mean_return": jnp.mean((rolls.rewards * rolls.mask).sum(axis=1)),
        }
        return new_state, metrics

    return epoch_fn


def init_search(env: env_lib.EnvArrays, ecfg: env_lib.EnvConfig,
                pcfg: policy_lib.PolicyConfig, rcfg: ReinforceConfig,
                opt: optim.Adam) -> SearchState:
    key = jax.random.PRNGKey(rcfg.seed)
    key, pkey = jax.random.split(key)
    params = policy_lib.init_params(pkey, pcfg)
    N = env.num_layers
    return SearchState(
        params=params, opt_state=opt.init(params),
        pmin=jnp.asarray(jnp.inf, jnp.float32),
        best_value=jnp.asarray(jnp.inf, jnp.float32),
        best_pe_lvl=jnp.zeros((N,), jnp.int32),
        best_kt_lvl=jnp.zeros((N,), jnp.int32),
        best_df=jnp.full((N,), ecfg.dataflow, jnp.int32),
        key=key, epoch=jnp.zeros((), jnp.int32))


def _search_program(env: env_lib.EnvArrays, ecfg: env_lib.EnvConfig,
                    pcfg: policy_lib.PolicyConfig, rcfg: ReinforceConfig):
    """The jitted ``(state, n) -> n-epoch scan`` of one search, from the
    process-wide program cache (:mod:`repro.core.programs`).

    The key is exactly what the epoch function is built from: ``ecfg``,
    ``pcfg``, ``rcfg`` with its seed and epoch count zeroed (they reach the
    program only through its state and the static ``n``, and the builder
    sees the zeroed copy) and the contents of ``env``.
    """
    rcfg = dataclasses.replace(rcfg, epochs=0, seed=0)
    key = (ecfg, pcfg, rcfg, programs.digest(*env))

    def build():
        epoch_fn = make_epoch_fn(ecfg, pcfg, rcfg, env,
                                 optim.Adam(lr=rcfg.lr))

        @functools.partial(jax.jit, static_argnames=("n",))
        def scan_chunk(state, n):
            return jax.lax.scan(epoch_fn, state, None, length=n)

        return scan_chunk

    return programs.cache("reinforce").get(key, build)


def run_search(workload, ecfg: env_lib.EnvConfig,
               rcfg: ReinforceConfig = ReinforceConfig(),
               pcfg: policy_lib.PolicyConfig | None = None,
               state: SearchState | None = None,
               chunk: int = 500,
               on_chunk=None):
    """Full stage-1 search.  Returns (state, history dict of (epochs,) arrays).

    Runs in jitted lax.scan chunks so long searches can checkpoint between
    chunks.  ``on_chunk(state, chunk_history, epochs_done)`` fires after each
    chunk (the unified API streams progress through it); the compiled epoch
    function is reused across chunks, and across searches of the same
    program key (:mod:`repro.core.programs`).
    """
    env = env_lib.make_env(workload, ecfg)
    if pcfg is None:
        pcfg = policy_lib.PolicyConfig(obs_dim=ecfg.obs_dim, mix=ecfg.mix,
                                       levels=ecfg.levels)
    opt = optim.Adam(lr=rcfg.lr)
    if state is None:
        state = init_search(env, ecfg, pcfg, rcfg, opt)

    scan_chunk = _search_program(env, ecfg, pcfg, rcfg)

    def run_chunk(state, n):
        state, metrics = scan_chunk(state, n)
        return state, jax.tree.map(jax.device_get, metrics)

    state, history = chunk_lib.drive(
        state, rcfg.epochs, chunk, run_chunk, on_chunk,
        engine="reinforce", evals_per_step=rcfg.episodes_per_epoch)
    return state, chunk_lib.concat_hist_dict(history)


def solution_arrays(state: SearchState, env: env_lib.EnvArrays):
    """Decode the best solution's raw (pe, kt, df) arrays."""
    pe = env.pe_table[state.best_pe_lvl]
    kt = env.kt_table[state.best_kt_lvl]
    return pe, kt, state.best_df
