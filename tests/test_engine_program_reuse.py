"""The engines' process-wide program cache (``repro.core.programs``).

A search whose program key (env config, engine settings, env contents)
matches an earlier search's reuses that search's ``jax.jit`` object, so it
traces nothing; its answer is byte-identical to the same request run with
the cache cleared.  A changed key builds a new program, concurrent searches
of one key give the serial answers, and each engine's cache is a bounded
LRU.
"""
import dataclasses
import threading

import numpy as np
import pytest

from repro import api, obs
from repro.core import env as env_lib
from repro.core import ga
from repro.core import programs
from repro.costmodel import workloads
from repro.obs import instrument
from repro.serving import SearchService, ServiceConfig

ECFG = env_lib.EnvConfig(platform="cloud")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _req(method="reinforce", seed=0, eps=24, wl="ncf", env=ECFG, **opts):
    return api.SearchRequest(workload=wl, env=env, eps=eps, seed=seed,
                             method=method, options=opts)


def _answer(out):
    """Every byte of an outcome a user reads."""
    parts = [np.float64(out.best_value).tobytes(), out.pe.tobytes(),
             out.kt.tobytes(), out.df.tobytes(), out.history.tobytes()]
    hist = out.extras.get("history")
    if isinstance(hist, dict):
        parts += [np.asarray(hist[k]).tobytes() for k in sorted(hist)]
    return parts


def _programs(engine, result):
    return instrument.ENGINE_PROGRAMS.value(engine=engine, result=result)


def _traces():
    return instrument.JIT_SECONDS.stats(phase="trace")["count"]


def test_second_reinforce_search_traces_nothing():
    obs.enable(trace=False)
    api.run_search(_req(seed=1))
    traced = _traces()
    assert traced > 0 and _programs("reinforce", "built") == 1
    second = api.run_search(_req(seed=2))
    assert _traces() == traced
    assert _programs("reinforce", "reused") == 1
    assert _programs("reinforce", "built") == 1

    programs.clear()
    fresh = api.run_search(_req(seed=2))
    assert _programs("reinforce", "built") == 2
    assert _answer(second) == _answer(fresh)


@pytest.mark.parametrize("change", [
    {"env": env_lib.EnvConfig(platform="cloud", objective="energy")},
    {"wl": workloads.ncf(batch=512)},     # same shapes, other contents
    {"episodes_per_epoch": 2},
    {"lr": 1e-2},
], ids=["objective", "workload", "episodes_per_epoch", "lr"])
def test_changed_key_builds_a_new_program(change):
    obs.enable(trace=False)
    api.run_search(_req(eps=8))
    api.run_search(_req(eps=8, **change))
    assert _programs("reinforce", "built") == 2
    assert _programs("reinforce", "reused") == 0
    assert len(programs.cache("reinforce")) == 2


@pytest.mark.parametrize("method,opts", [
    ("ga", {"population": 16}),
    ("nsga2", {"population": 16}),
])
def test_population_engines_reuse_evolve_through_the_service(method, opts):
    obs.enable(trace=False)
    svc = SearchService(ServiceConfig(max_workers=2))
    try:
        svc.submit(_req(method, seed=1, eps=64, **opts)).result(timeout=300)
        second = svc.submit(_req(method, seed=2, eps=64, **opts)).result(
            timeout=300)
        assert _programs(method, "built") == 1
        assert _programs(method, "reused") == 1
        programs.clear()
        fresh = svc.submit(_req(method, seed=2, eps=64, **opts)).result(
            timeout=300)
        assert _programs(method, "built") == 2
    finally:
        svc.close()
    assert _answer(second) == _answer(fresh)


@pytest.mark.parametrize("method,opts", [
    ("reinforce", {}),
    ("ga", {"population": 16}),
])
def test_concurrent_same_key_searches_give_the_serial_answers(method, opts):
    seeds = (3, 4)
    got = {}

    def run(seed):
        got[seed] = _answer(api.run_search(_req(method, seed=seed, **opts)))

    threads = [threading.Thread(target=run, args=(s,)) for s in seeds]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert sorted(got) == list(seeds)
    assert len(programs.cache(method)) == 1
    for seed in seeds:
        programs.clear()
        assert got[seed] == _answer(api.run_search(
            _req(method, seed=seed, **opts)))


@pytest.mark.parametrize("init,result", [
    ((8, 4, 0), "reused"),
    ((8, 4, 1), "built"),                 # another stage-1 dataflow
    ((16, 4, 0), "built"),                # another stage-1 PE split
], ids=["same_init", "init_df", "init_pe"])
def test_local_ga_keys_on_its_stage1_seed(init, result):
    obs.enable(trace=False)
    wl = workloads.ncf()
    n = len(wl)
    cfg = ga.LocalGAConfig(population=8, generations=4)

    def run(pe, kt, df, seed):
        state, hist = ga.run_local_ga(
            wl, ECFG, np.full(n, pe, np.float32), np.full(n, kt, np.float32),
            np.full(n, df, np.int32), dataclasses.replace(cfg, seed=seed))
        return [np.asarray(state.best_genome).tobytes(), hist.tobytes()]

    run(8, 4, 0, seed=1)
    second = run(*init, seed=2)
    assert _programs("local_ga", result) == (2 if result == "built" else 1)
    programs.clear()
    assert second == run(*init, seed=2)


def test_lru_evicts_at_its_bound(monkeypatch):
    obs.enable(trace=False)
    monkeypatch.setattr(programs, "MAX_PROGRAMS", 2)
    cache = programs.ProgramCache("lru_test")
    built = []

    def build(name):
        def f():
            built.append(name)
            return lambda: name
        return f

    assert cache.get("a", build("a"))() == "a"
    cache.get("b", build("b"))
    cache.get("a", build("a2"))          # reused: "a" is now most recent
    cache.get("c", build("c"))           # evicts "b", the least recent
    assert len(cache) == 2 and built == ["a", "b", "c"]
    assert cache.get("a", build("a3"))() == "a"
    cache.get("b", build("b2"))          # "b" was evicted: built again
    assert built == ["a", "b", "c", "b2"]
    assert _programs("lru_test", "built") == 4
    assert _programs("lru_test", "reused") == 2
