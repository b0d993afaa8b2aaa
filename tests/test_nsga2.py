"""NSGA-II engine contract: chunk-invariant resume, eval_fn byte-identity,
frontier semantics, and the multi-DNN co-design scenario.

The cross-method schema checks live in test_optimizer_conformance.py; here:

  * chunk boundaries never change the result (one-shot == chunked == two
    sequential state-fed calls, byte for byte);
  * the injected host ``eval_fn`` path (the service's batcher programs) is
    deterministic and equals what the registry adapter reports;
  * the reported frontier is mutually non-dominating, budget-feasible, and
    its genomes actually realize their stated costs;
  * ``EnvConfig(mix=True)`` co-design over a multi-model workload searches
    per-layer dataflows and still honors the shared budget.
"""
import numpy as np
import pytest

from repro import api
from repro.core import env as env_lib
from repro.core import nsga2
from repro.costmodel import workloads

ECFG = env_lib.EnvConfig(platform="cloud")
CFG = nsga2.NSGA2Config(population=14, generations=9, seed=5)
NCF = workloads.get_workload("ncf")


def _bytes(state):
    return tuple(np.asarray(x).tobytes() for x in state)


def test_chunk_invariant_one_shot_vs_chunked():
    s1, h1 = nsga2.run_nsga2_search(NCF, ECFG, CFG)
    s2, h2 = nsga2.run_nsga2_search(NCF, ECFG, CFG, chunk=2)
    s3, h3 = nsga2.run_nsga2_search(NCF, ECFG, CFG, chunk=4)
    assert h1.tobytes() == h2.tobytes() == h3.tobytes()
    assert _bytes(s1) == _bytes(s2) == _bytes(s3)


def test_resume_from_state_matches_uninterrupted_run():
    import dataclasses

    s_full, h_full = nsga2.run_nsga2_search(NCF, ECFG, CFG)
    first = dataclasses.replace(CFG, generations=4)
    rest = dataclasses.replace(CFG, generations=5)
    s_a, h_a = nsga2.run_nsga2_search(NCF, ECFG, first)
    s_b, h_b = nsga2.run_nsga2_search(NCF, ECFG, rest, state=s_a)
    assert np.concatenate([h_a, h_b]).tobytes() == h_full.tobytes()
    assert _bytes(s_b) == _bytes(s_full)
    assert int(s_b.generation) == CFG.generations


def test_injected_eval_fn_is_deterministic_and_matches_adapter():
    from repro.serving import batcher as batcher_lib

    env = env_lib.make_env(workloads.get_workload("ncf"), ECFG)
    eval_fn = batcher_lib.make_local_costs_eval(env, ECFG)
    s1, h1 = nsga2.run_nsga2_search(NCF, ECFG, CFG, eval_fn=eval_fn,
                                    env=env)
    s2, h2 = nsga2.run_nsga2_search(NCF, ECFG, CFG, chunk=3,
                                    eval_fn=eval_fn, env=env)
    assert h1.tobytes() == h2.tobytes()
    assert _bytes(s1) == _bytes(s2)
    # The registry adapter (which defaults to this very eval path) agrees.
    out = api.run_search(api.SearchRequest(
        workload="ncf", env=ECFG, eps=CFG.population * CFG.generations,
        seed=CFG.seed, method="nsga2", options={"population": CFG.population,
                                                "generations":
                                                CFG.generations}))
    assert out.best_value == pytest.approx(float(s1.best_val))
    assert np.float32(out.history[-1]) == np.float32(s1.best_val)


def _check_frontier(out, wl, ecfg):
    f = out.frontier
    F = len(f["lat"])
    assert F >= 1
    obj = np.stack([f["lat"], f["en"]], axis=-1)
    assert nsga2.non_dominated_mask(obj).all()
    assert np.all(np.diff(f["lat"]) >= 0)          # sorted by latency
    # Every frontier genome realizes its stated costs and fits the budget.
    import jax.numpy as jnp

    env = env_lib.make_env(wl, ecfg)
    for i in range(F):
        tl, te, ta, tp, feas = env_lib.genome_costs_multi(
            env, ecfg, jnp.asarray(f["pe"][i], jnp.float32),
            jnp.asarray(f["kt"][i], jnp.float32), np.asarray(f["df"][i]))
        assert bool(feas)
        np.testing.assert_allclose(
            [float(tl), float(te), float(ta), float(tp)],
            [f["lat"][i], f["en"][i], f["area"][i], f["pw"][i]], rtol=1e-6)
    return F


def test_frontier_is_nondominated_and_feasible():
    out = api.run_search(api.SearchRequest(
        workload="ncf", env=ECFG, eps=150, seed=1, method="nsga2",
        options={"population": 15}))
    _check_frontier(out, workloads.get_workload("ncf"), ECFG)
    # The scalar best is the frontier's best primary objective.
    assert out.best_value == pytest.approx(float(np.min(out.frontier["lat"])))


def test_mix_codesign_searches_dataflows_under_one_budget():
    wl = workloads.multi_dnn(["qwen1p5_0p5b", "whisper_small",
                              "mamba2_130m"], tokens=32)
    names = [l.name for l in wl]
    assert len({n.split(".")[0] for n in names}) == 3   # ragged 3-model mix
    ecfg = env_lib.EnvConfig(platform="cloud", mix=True)
    out = api.run_search(api.SearchRequest(
        workload=wl, env=ecfg, eps=120, seed=0, method="nsga2",
        options={"population": 12}))
    assert out.feasible
    assert out.df.shape == (len(wl),)
    assert set(np.unique(out.df)) <= {0, 1, 2}          # per-layer dataflow
    _check_frontier(out, wl, ecfg)


def test_aliases_resolve_to_nsga2():
    assert type(api.get_optimizer("pareto")).__name__ == "NSGA2Optimizer"
    assert type(api.get_optimizer("moo")).__name__ == "NSGA2Optimizer"
