"""Distributed ConfuciuX search: the paper's algorithm at pod scale.

Three shard_map building blocks (DESIGN.md S3/S6):

  * episode-parallel REINFORCE -- every device runs E_local episodes with a
    device-folded RNG and computes a local policy gradient; gradients are
    psum'd (synchronous data-parallel RL).  Params stay replicated, so
    scaling from 1 device to 512 chips changes only the reduction tree.
  * int8-compressed gradient reduction -- across the ``pod`` axis (the slow
    inter-pod links) gradients are quantized to int8 with a per-leaf scale,
    psum'd in int32, and dequantized.  In-pod reduction stays f32.
  * straggler masking -- each shard carries a validity flag; dead/slow
    shards contribute zero gradient and the reduction renormalizes by the
    live count (drop-slowest semantics).  tests/test_distributed.py checks
    the search still converges with a masked shard.

Island-model GA: each device evolves its own subpopulation and the best
genomes are exchanged (all_gather) every ``exchange_every`` generations.

Unified-API wrappers (registered in the ``repro.api`` optimizer registry):

  * ``fanout``         -- seed-parallel fan-out of ANY registered optimizer:
    n shards run the inner method with distinct seeds and the results are
    merged (best value wins; the trace is the elementwise min, i.e. the
    wall-clock view of the parallel ensemble).  Three execution backends:
    ``device`` (one shard per local device, the whole fleet in one
    shard_map'd XLA program), ``threads`` (one host worker per shard), and
    ``serial`` (the debugging loop); all three produce identical outcomes,
    and live progress streams merged + shard-tagged through the unified API.
  * ``dist_reinforce`` -- the episode-parallel shard_map REINFORCE above,
    exposed through the same SearchRequest/SearchOutcome schema.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.api import registry as api_registry
from repro.api import types as api_types
from repro.core import chunk as chunk_lib
from repro.core import env as env_lib
from repro.core import ga as ga_lib
from repro.core import policy as policy_lib
from repro.core import reinforce
from repro.launch.mesh import auto_mesh
from repro.training import optim


# ---------------------------------------------------------------------------
# Compressed / masked reductions.
# ---------------------------------------------------------------------------
def psum_int8(tree, axis_name: str):
    """Quantized all-reduce: int8 per-leaf symmetric quantization.

    Wire cost is ~4x lower than f32 psum; the quantization error is bounded
    by scale/2 per element (tested).  Scales are reduced with a max so every
    participant dequantizes identically.
    """
    def reduce_leaf(x):
        scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
        scale = jax.lax.pmax(scale, axis_name)
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int32)
        total = jax.lax.psum(q, axis_name)
        return total.astype(jnp.float32) * scale

    return jax.tree.map(reduce_leaf, tree)


def masked_psum(tree, alive, axis_name: str):
    """Straggler-tolerant mean-reduction: dead shards contribute nothing."""
    n_alive = jnp.maximum(jax.lax.psum(alive.astype(jnp.float32),
                                       axis_name), 1.0)
    return jax.tree.map(
        lambda x: jax.lax.psum(x * alive.astype(x.dtype), axis_name)
        / n_alive, tree)


def masked_hierarchical_psum(tree, alive, axes, pod_axis: str = "pod",
                             compress: bool = False):
    """Masked global mean with an optionally compressed cross-pod hop.

    Semantics match :func:`masked_psum` over all ``axes``: the sum of the
    alive shards' leaves divided by the global alive-device count.  With
    ``compress`` the reduction is hierarchical -- exact f32 sums within each
    pod (fast links), then one int8-quantized psum across ``pod_axis`` (slow
    inter-pod links) for both the leaf sums and the alive counts' exact f32
    psum.  Normalizing by the true global alive count (instead of averaging
    per-pod means) keeps the result equal to the flat masked_psum, up to
    int8 quantization error, even when pods have different live counts.
    """
    if pod_axis not in axes or not compress:
        return masked_psum(tree, alive, axes)
    inpod = tuple(a for a in axes if a != pod_axis)
    af = alive.astype(jnp.float32)
    gsum = jax.tree.map(lambda x: x * af.astype(x.dtype), tree)
    n_local = af
    if inpod:
        gsum = jax.tree.map(lambda x: jax.lax.psum(x, inpod), gsum)
        n_local = jax.lax.psum(af, inpod)
    gsum = psum_int8(gsum, pod_axis)
    n_alive = jnp.maximum(jax.lax.psum(n_local, pod_axis), 1.0)
    return jax.tree.map(lambda g: g / n_alive, gsum)


# ---------------------------------------------------------------------------
# Episode-parallel REINFORCE.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DistConfig:
    episodes_per_device: int = 4
    compress_pod_axis: bool = False   # int8 reduction across 'pod'
    seed: int = 0


def make_distributed_epoch(ecfg: env_lib.EnvConfig,
                           pcfg: policy_lib.PolicyConfig,
                           rcfg: reinforce.ReinforceConfig,
                           env: env_lib.EnvArrays,
                           opt: optim.Adam, mesh,
                           dcfg: DistConfig = DistConfig()):
    """Build the shard_map'd epoch: all mesh axes run episodes in parallel."""
    rollout = reinforce.make_rollout(ecfg, pcfg, env, rcfg.discount)
    axes = tuple(mesh.axis_names)
    E = dcfg.episodes_per_device

    def local_loss(params, pmin, keys):
        rolls = jax.vmap(lambda k: rollout(params, pmin, k))(keys)
        G = jax.vmap(lambda r: reinforce._discounted_returns(
            r, rcfg.discount))(rolls.rewards * rolls.mask)
        n_valid = jnp.maximum(rolls.mask.sum(axis=1), 1.0)
        mean = (G * rolls.mask).sum(axis=1) / n_valid
        var = (jnp.square(G - mean[:, None]) * rolls.mask).sum(1) / n_valid
        G_std = (G - mean[:, None]) / (jnp.sqrt(var)[:, None] + 1e-8)
        pg = -(rolls.logps * jax.lax.stop_gradient(G_std)
               * rolls.mask).sum(axis=1)
        return jnp.mean(pg), rolls

    def epoch_shard(state: reinforce.SearchState, alive):
        alive = alive[0]  # (1,) local shard of the per-device flag vector
        # Per-device RNG: fold in every mesh axis index.
        key = state.key
        for ax in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, E)
        (_, rolls), grads = jax.value_and_grad(
            local_loss, has_aux=True)(state.params, state.pmin, keys)

        # Hierarchical reduction: f32 within the pod, optionally int8 across.
        grads = masked_hierarchical_psum(grads, alive, axes,
                                         compress=dcfg.compress_pod_axis)

        params, opt_state = opt.update(grads, state.opt_state, state.params)
        pmin = jax.lax.pmin(jnp.min(rolls.pmin), axes)

        values = jnp.where(rolls.feasible, rolls.model_value, jnp.inf)
        i = jnp.argmin(values)
        local_best = values[i]
        # Global argmin across devices.
        all_best = jax.lax.all_gather(local_best, axes, tiled=False)
        all_pe = jax.lax.all_gather(rolls.actions[i, :, 0], axes)
        all_kt = jax.lax.all_gather(rolls.actions[i, :, 1], axes)
        all_df = jax.lax.all_gather(rolls.actions[i, :, 2], axes)
        flat_best = all_best.reshape(-1)
        j = jnp.argmin(flat_best)
        better = flat_best[j] < state.best_value
        pick = lambda new, old: jnp.where(better, new, old)
        new_state = reinforce.SearchState(
            params=params, opt_state=opt_state, pmin=pmin,
            best_value=jnp.where(better, flat_best[j], state.best_value),
            best_pe_lvl=pick(all_pe.reshape(-1, all_pe.shape[-1])[j],
                             state.best_pe_lvl),
            best_kt_lvl=pick(all_kt.reshape(-1, all_kt.shape[-1])[j],
                             state.best_kt_lvl),
            best_df=pick(all_df.reshape(-1, all_df.shape[-1])[j],
                         state.best_df),
            key=state.key, epoch=state.epoch + 1)
        # Advance the replicated key identically on all shards.
        new_state = new_state._replace(
            key=jax.random.fold_in(state.key, state.epoch + 1))
        metrics = {
            "best_value": new_state.best_value,
            "feasible_frac": jax.lax.pmean(
                jnp.mean(rolls.feasible.astype(jnp.float32)), axes),
        }
        return new_state, metrics

    rep = P()
    fn = jax.shard_map(
        epoch_shard, mesh=mesh,
        in_specs=(rep, P(axes)),   # alive: one flag per device
        out_specs=(rep, rep),
        check_vma=False)
    return fn


def run_distributed_search(workload, ecfg: env_lib.EnvConfig, mesh,
                           rcfg: reinforce.ReinforceConfig,
                           dcfg: DistConfig = DistConfig(),
                           pcfg: Optional[policy_lib.PolicyConfig] = None,
                           straggler_mask=None):
    """Full distributed stage-1 search on a mesh.

    straggler_mask: optional bool array of shape (n_devices,) -- False marks
    a simulated dead/slow shard whose contribution is dropped.
    """
    env = env_lib.make_env(workload, ecfg)
    if pcfg is None:
        pcfg = policy_lib.PolicyConfig(obs_dim=ecfg.obs_dim, mix=ecfg.mix,
                                       levels=ecfg.levels)
    opt = optim.Adam(lr=rcfg.lr)
    state = reinforce.init_search(env, ecfg, pcfg, rcfg, opt)
    epoch_fn = make_distributed_epoch(ecfg, pcfg, rcfg, env, opt, mesh, dcfg)

    n_dev = int(np.prod(list(mesh.shape.values())))
    if straggler_mask is None:
        straggler_mask = np.ones((n_dev,), bool)
    alive = jax.device_put(
        jnp.asarray(straggler_mask),
        jax.sharding.NamedSharding(mesh, P(tuple(mesh.axis_names))))

    @jax.jit
    def one_epoch(state):
        return epoch_fn(state, alive)

    def run_epochs(state, n):
        vals = {"best_value": [], "feasible_frac": []}
        for _ in range(n):
            state, metrics = one_epoch(state)
            for k in vals:
                vals[k].append(float(metrics[k]))
        return state, vals

    # One chunk (chunk=0 -> full budget): nothing happens between epochs
    # here, drive() only adds the span/metrics accounting.
    state, chunks = chunk_lib.drive(
        state, rcfg.epochs, 0, run_epochs, lambda *a: None,
        engine="dist_reinforce",
        evals_per_step=dcfg.episodes_per_device * n_dev)
    history = {k: np.asarray([v for h in chunks for v in h[k]])
               for k in chunks[0]}
    return state, history


# ---------------------------------------------------------------------------
# Fanout execution backends.
# ---------------------------------------------------------------------------
# Inner methods whose whole search is one JAX program, so n seeds can run as
# one shard_map'd XLA computation over n local devices (bit-identical to the
# serial loop: each device executes exactly the single-shard program).
DEVICE_INNERS = ("reinforce", "ga")
FANOUT_BACKENDS = ("auto", "device", "threads", "serial")


class _MergedProgress:
    """Thread-safe merge of per-shard progress into one tagged stream.

    Each shard's Trials are re-emitted with ``shard=s`` and the *ensemble*
    best-so-far (min over everything any shard has reported).  ``step`` is
    the shard-local sample index, so every shard's sub-stream stays monotone;
    how the sub-streams interleave depends on the backend's scheduling.
    """

    def __init__(self, cb: Optional[api_types.ProgressFn], n_shards: int):
        self._cb = cb
        self._lock = threading.Lock()
        self._best = [float("inf")] * n_shards

    def shard_cb(self, s: int) -> Optional[api_types.ProgressFn]:
        if self._cb is None:
            return None

        def cb(trial: api_types.Trial) -> None:
            with self._lock:
                self._best[s] = min(self._best[s], trial.best_value)
                ensemble = min(self._best)
                self._cb(api_types.Trial(trial.step, trial.value,
                                         ensemble, shard=s))

        return cb


def _shard_mesh(n_shards: int):
    return auto_mesh((n_shards,), ("shard",))


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _fanout_reinforce_device(subs) -> list:
    """All shards' REINFORCE searches as one shard_map'd program.

    Every device runs the exact single-shard epoch scan (the per-shard block
    is squeezed to the serial shapes), so shard s's outcome is bit-identical
    to ``get_optimizer("reinforce").run(subs[s])`` -- only the wall-clock
    changes: one XLA compile for the whole fleet and all devices stepping
    concurrently.
    """
    from repro.api import optimizers as api_optimizers

    req0 = subs[0]
    n_shards = len(subs)
    wl = req0.resolve_workload()
    ecfg = req0.env
    env = env_lib.make_env(wl, ecfg)
    pcfg = api_optimizers._policy_config(ecfg, req0.options)
    rcfgs = [api_optimizers._reinforce_cfg(sub)[0] for sub in subs]
    E = rcfgs[0].episodes_per_epoch
    epochs = rcfgs[0].epochs
    opt = optim.Adam(lr=rcfgs[0].lr)
    epoch_fn = reinforce.make_epoch_fn(ecfg, pcfg, rcfgs[0], env, opt)
    stacked = _stack_trees(
        [reinforce.init_search(env, ecfg, pcfg, rcfg, opt)
         for rcfg in rcfgs])
    mesh = _shard_mesh(n_shards)
    P_s = P("shard")

    @functools.partial(jax.jit, static_argnames=("n",))
    def run_chunk(stacked, n):
        def body(state):
            state = jax.tree.map(lambda x: jnp.squeeze(x, 0), state)
            state2, metrics = jax.lax.scan(epoch_fn, state, None, length=n)
            return (jax.tree.map(lambda x: x[None], state2),
                    jax.tree.map(lambda x: x[None], metrics))

        return jax.shard_map(body, mesh=mesh, in_specs=(P_s,),
                             out_specs=(P_s, P_s), check_vma=False)(stacked)

    streaming = req0.on_progress is not None
    # Not streaming -> nothing happens between chunks, so run the whole
    # epoch budget as ONE static scan length (a tail chunk of a different
    # length would trigger a second fleet-wide compile).
    chunk = max(req0.progress_every // E, 1) if streaming else epochs
    t0 = time.time()

    def drive_chunk(stacked, n):
        stacked, metrics = run_chunk(stacked, n)
        # (n_shards, n) leaves
        return stacked, jax.tree.map(jax.device_get, metrics)

    def on_chunk(stacked, h, done):
        if not streaming:
            return
        best_now = np.asarray(stacked.best_value)
        for s, sub in enumerate(subs):
            sub.on_progress(api_types.Trial(
                min(done * E, sub.eps),
                float(np.min(h["best_value"][s])),
                float(best_now[s])))

    stacked, chunks = chunk_lib.drive(
        stacked, epochs, chunk, drive_chunk, on_chunk,
        engine="dist_reinforce", evals_per_step=E * n_shards)
    hist = {k: np.concatenate([h[k] for h in chunks], axis=1)
            for k in chunks[0]}

    outcomes = []
    for s, sub in enumerate(subs):
        state_s = jax.tree.map(lambda x: x[s], stacked)
        pe, kt, df = reinforce.solution_arrays(state_s, env)
        trace = api_types.expand_trace(hist["best_value"][s], E)
        outcomes.append(api_types.build_outcome(
            sub, "reinforce", float(state_s.best_value), np.asarray(pe),
            np.asarray(kt), np.asarray(df), trace, t0,
            extras={"epochs": epochs,
                    "history": {k: v[s] for k, v in hist.items()}},
            streamed=streaming))
    return outcomes


def _fanout_ga_device(subs) -> list:
    """All shards' GA runs as one shard_map'd generation scan.

    Per-shard carries differ only in their seed; the generation step is
    shared, so one compile drives every island.  The fitness hot-spot goes
    through :func:`repro.core.ga._fitness`, which dispatches the Pallas
    batched cost kernel on TPU (``kernels.ops.on_tpu``).
    """
    from repro.api import optimizers as api_optimizers

    req0 = subs[0]
    n_shards = len(subs)
    wl = req0.resolve_workload()
    ecfg = req0.env
    env = env_lib.make_env(wl, ecfg)
    cfg = api_optimizers._ga_cfg(req0)
    pop, gens = cfg.population, cfg.generations
    engine = ga_lib.make_ga_engine(env, ecfg, cfg)
    stacked = _stack_trees([engine.init_carry(sub.seed) for sub in subs])
    mesh = _shard_mesh(n_shards)
    P_s = P("shard")

    @jax.jit
    def run_all(stacked):
        def body(carry):
            carry = jax.tree.map(lambda x: jnp.squeeze(x, 0), carry)
            carry2, hist = jax.lax.scan(engine.gen_step, carry, None,
                                        length=gens)
            return jax.tree.map(lambda x: x[None], carry2), hist[None]

        return jax.shard_map(body, mesh=mesh, in_specs=(P_s,),
                             out_specs=(P_s, P_s), check_vma=False)(stacked)

    t0 = time.time()
    final, hist = run_all(stacked)
    best_vals = np.asarray(final.best_val)
    best_genomes = final.best_genome
    hist = np.asarray(hist)

    outcomes = []
    for s, sub in enumerate(subs):
        pe, kt, df = engine.decode(best_genomes[s])
        df = jnp.broadcast_to(df, (env.num_layers,))
        trace = api_types.expand_trace(hist[s], pop)
        outcomes.append(api_types.build_outcome(
            sub, "ga", float(best_vals[s]), np.asarray(pe), np.asarray(kt),
            np.asarray(df), trace, t0,
            extras={"generations": gens, "population": pop}))
    return outcomes


_DEVICE_ENGINES = {"reinforce": _fanout_reinforce_device,
                   "ga": _fanout_ga_device}


# ---------------------------------------------------------------------------
# Unified-API wrappers.
# ---------------------------------------------------------------------------
@api_registry.register("fanout")
class FanoutOptimizer:
    """Seed-parallel fan-out of any registered optimizer.

    options:
      ``inner``          registry name of the inner method (default
                         "reinforce")
      ``n_shards``       number of parallel searches (default 4)
      ``inner_options``  options dict passed to every shard
      ``backend``        "auto" | "device" | "threads" | "serial":

        * ``device``  -- one shard per local JAX device; every shard's whole
          search fuses into one shard_map'd XLA program (JAX-native inners
          only: reinforce, ga).  One compile for the fleet, all devices
          stepping concurrently, bit-identical results to ``serial``.
        * ``threads`` -- one host thread per shard running the inner
          optimizer unchanged (works for any inner; XLA releases the GIL
          during compilation and execution, so non-JAX engines like sa/bo/
          grid/random overlap too).
        * ``serial``  -- the in-process for-loop (debugging, 1-core hosts).
        * ``auto``    -- device when the inner supports it and enough local
          devices exist, else threads.

    Each shard keeps the full ``eps`` budget -- this models n workers
    searching in parallel, so the merged trace is the wall-clock best-so-far
    of the ensemble and total samples are ``n_shards * eps`` (reported in
    extras).  Shards are merged in shard-index order, so every backend
    returns identical outcomes for the same seeds.

    Progress streams through ``request.on_progress`` as shard-tagged Trials
    (``Trial.shard``) whose ``best_value`` is the ensemble best-so-far; each
    shard's sub-stream is monotone in ``step``, while the interleaving
    across shards follows the backend's scheduling.
    """

    name = "fanout"

    def run(self, request: api_types.SearchRequest) -> api_types.SearchOutcome:
        t0 = time.time()
        opts = request.options
        inner = opts.get("inner", "reinforce")
        n_shards = int(opts.get("n_shards", 4))
        inner_opts = dict(opts.get("inner_options", {}))
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        inner_impl = api_registry.get_optimizer(inner)
        if isinstance(inner_impl, FanoutOptimizer):
            raise ValueError("fanout cannot nest itself as the inner method")
        backend = _resolve_backend(opts.get("backend", "auto"),
                                   inner_impl.name, n_shards)
        merger = _MergedProgress(request.on_progress, n_shards)
        subs = [dataclasses.replace(
                    request, method=inner_impl.name, options=inner_opts,
                    seed=request.seed + s, on_progress=merger.shard_cb(s))
                for s in range(n_shards)]

        # Each shard gets a fresh optimizer instance so stateful custom
        # optimizers never share one object across concurrent threads.
        if backend == "device":
            shards = _DEVICE_ENGINES[inner_impl.name](subs)
        elif backend == "threads":
            with ThreadPoolExecutor(max_workers=n_shards) as pool:
                futures = [pool.submit(api_registry.get_optimizer(inner).run,
                                       sub) for sub in subs]
                shards = [f.result() for f in futures]
        else:
            shards = [api_registry.get_optimizer(inner).run(sub)
                      for sub in subs]

        best = min(shards, key=lambda o: o.best_value)
        trace = np.min(np.stack([o.history for o in shards]), axis=0)
        return api_types.build_outcome(
            request, self.name, best.best_value, best.pe, best.kt, best.df,
            trace, t0,
            extras={"inner": inner_impl.name, "n_shards": n_shards,
                    "backend": backend,
                    "total_samples": n_shards * request.eps,
                    "shard_best_values": [o.best_value for o in shards],
                    "best_seed": best.seed},
            streamed=request.on_progress is not None)


def _resolve_backend(backend: str, inner_name: str, n_shards: int) -> str:
    n_dev = len(jax.devices())
    if backend == "auto":
        return ("device" if inner_name in DEVICE_INNERS and n_shards <= n_dev
                else "threads")
    if backend == "device":
        if inner_name not in DEVICE_INNERS:
            raise ValueError(
                f"backend='device' supports the JAX-native inner methods "
                f"{DEVICE_INNERS}, not {inner_name!r}; use backend='threads'")
        if n_shards > n_dev:
            raise ValueError(
                f"backend='device' needs >= {n_shards} local devices, have "
                f"{n_dev} (lower n_shards or set the env var "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n_shards})")
        return backend
    if backend not in FANOUT_BACKENDS:
        raise ValueError(f"unknown fanout backend {backend!r}; expected one "
                         f"of {FANOUT_BACKENDS}")
    return backend


@api_registry.register("dist_reinforce")
class DistributedReinforceOptimizer:
    """Episode-parallel REINFORCE across every device of a mesh.

    options: ``mesh`` (a jax Mesh; default: one axis over all local devices),
    ``episodes_per_device``, ``compress_pod_axis``, ``straggler_mask``,
    ``lr``.  One epoch consumes ``episodes_per_device * n_devices`` samples.
    """

    name = "dist_reinforce"

    def run(self, request: api_types.SearchRequest) -> api_types.SearchOutcome:
        t0 = time.time()
        opts = request.options
        mesh = opts.get("mesh")
        if mesh is None:
            mesh = auto_mesh((len(jax.devices()),), ("data",))
        n_dev = int(np.prod(list(mesh.shape.values())))
        E = int(opts.get("episodes_per_device", 1))
        per_epoch = max(E * n_dev, 1)
        rcfg = reinforce.ReinforceConfig(
            epochs=max(request.eps // per_epoch, 1),
            lr=opts.get("lr", 3e-3), seed=request.seed)
        dcfg = DistConfig(
            episodes_per_device=E,
            compress_pod_axis=bool(opts.get("compress_pod_axis", False)),
            seed=request.seed)
        wl = request.resolve_workload()
        state, hist = run_distributed_search(
            wl, request.env, mesh, rcfg, dcfg,
            straggler_mask=opts.get("straggler_mask"))
        env = env_lib.make_env(wl, request.env)
        pe, kt, df = reinforce.solution_arrays(state, env)
        trace = api_types.expand_trace(hist["best_value"], per_epoch)
        return api_types.build_outcome(
            request, self.name, state.best_value, np.asarray(pe),
            np.asarray(kt), np.asarray(df), trace, t0,
            extras={"epochs": rcfg.epochs, "devices": n_dev,
                    "history": hist})
