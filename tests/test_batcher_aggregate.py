"""The batcher's host aggregation: NumPy gives the jitted program's bytes.

A fused dispatch reduces its requests' per-layer costs on the host through
``env.aggregate_costs_multi`` (the one definition, run by NumPy on NumPy
arrays), in one pass per group of requests with the same layer count.
Every answer must be the bytes the per-request jitted aggregation gave --
scalar fitness with ``+inf`` where infeasible, and the (b, 4) whole-model
costs -- whatever else rides in the same dispatch.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import env as env_lib
from repro.costmodel import dataflows as dfl
from repro.costmodel import layers_to_array, workloads
from repro.obs import instrument
from repro.serving import CostEvalBatcher
from repro.serving import batcher as batcher_lib
from repro.serving.batcher import eval_point_rows, pack_point_rows


@functools.partial(jax.jit, static_argnums=2)
def _jit_multi(vals, budget, ecfg):
    tl, te, ta, tp, feas = env_lib.aggregate_costs_multi(
        vals[..., 0], vals[..., 1], vals[..., 2], vals[..., 3], ecfg, budget)
    return jnp.stack([tl, te, ta, tp], axis=-1), feas


def _jit_answer(vals, budget, ecfg, multi):
    """The per-request device aggregation the batcher ran for every
    request before it aggregated on the host."""
    if multi:
        return np.asarray(_jit_multi(jnp.asarray(vals), budget, ecfg)[0])
    return np.asarray(batcher_lib._agg_fn(ecfg)(jnp.asarray(vals), budget))


def _same_bytes(got, want):
    assert got.dtype == want.dtype == np.float32
    assert got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("N", [1, 2, 3, 16, 53, 54, 61])
@pytest.mark.parametrize("objective", ["latency", "energy"])
@pytest.mark.parametrize("constraint", ["area", "power"])
@pytest.mark.parametrize("scenario", ["LP", "LS"])
def test_host_aggregation_bytes_equal_jitted(scenario, constraint,
                                             objective, N):
    ecfg = env_lib.EnvConfig(objective=objective, constraint=constraint,
                             scenario=scenario)
    rng = np.random.default_rng(N)
    # Per-layer costs over many decades, as the cost model's are.
    vals = rng.lognormal(4.0, 3.0, size=(41, N, 4)).astype(np.float32)
    cons = np.asarray(_jit_multi(jnp.asarray(vals), np.float32(np.inf),
                                 ecfg)[0])[:, 2 if constraint == "area"
                                           else 3]
    budget = np.float32(np.median(cons))    # about half the rows infeasible

    totals = batcher_lib._host_totals(vals, ecfg, budget)
    want, want_feas = _jit_multi(jnp.asarray(vals), budget, ecfg)
    _same_bytes(np.stack(totals[:4], axis=-1), np.asarray(want))
    np.testing.assert_array_equal(totals[4], np.asarray(want_feas))
    assert 0 < totals[4].sum() < len(vals)

    for multi in (False, True):
        got = batcher_lib._host_result(totals, slice(None), ecfg, multi)
        _same_bytes(got, _jit_answer(vals, budget, ecfg, multi))
    fit = batcher_lib._host_result(totals, slice(None), ecfg, False)
    assert np.isposinf(fit).any() and np.isfinite(fit).any()


def _requests():
    """(workload, EnvConfig, multi) of one fused dispatch: three layer
    counts, scalar and multi requests, both constraints and scenarios,
    and one blend request."""
    cloud = env_lib.EnvConfig(platform="cloud")
    iot = env_lib.EnvConfig(platform="iot", objective="energy")
    return [
        ("mobilenet_v2", iot, True),
        ("resnet50", cloud, True),
        ("mobilenet_v2", env_lib.EnvConfig(platform="iot",
                                           constraint="power"), False),
        ("deepseek_v3_decode", cloud, False),
        ("resnet50", env_lib.EnvConfig(platform="cloud", objective="blend",
                                       blend_weight=0.3), False),
        ("mobilenet_v2", env_lib.EnvConfig(platform="iot", scenario="LS",
                                           dataflow=dfl.EYE), False),
        ("deepseek_v3_decode", iot, False),   # its group's other budget
        ("resnet50", cloud, False),
    ]


def _item(name, ecfg, multi, rng, b=23):
    """A request of ``b`` random genomes whose budget is the median of
    their constraint consumption, so some are feasible and some not."""
    layers = layers_to_array(workloads.get_workload(name))
    N = layers.shape[0]
    pe_t = dfl.pe_levels(ecfg.levels).astype(np.float32)
    kt_t = dfl.kt_levels(ecfg.levels).astype(np.float32)
    pe = pe_t[rng.integers(0, ecfg.levels, (b, N))]
    kt = kt_t[rng.integers(0, ecfg.levels, (b, N))]
    points = pack_point_rows(layers, pe, kt, np.float32(ecfg.dataflow))
    vals = eval_point_rows(points).reshape(b, N, 4)
    totals = np.asarray(_jit_multi(jnp.asarray(vals), np.float32(np.inf),
                                   ecfg)[0])
    cons = totals[:, 2 if ecfg.constraint == "area" else 3]
    return batcher_lib._Item(points, pe.shape, ecfg,
                             np.float32(np.median(cons)), multi=multi)


def test_fused_dispatch_answers_each_request_with_its_jitted_bytes():
    rng = np.random.default_rng(7)
    items = [_item(n, e, m, rng) for n, e, m in _requests()]
    bat = CostEvalBatcher(window_ms=0.0)
    obs.enable(trace=False)
    try:
        c = instrument.BATCHER_AGGREGATED_ITEMS
        host0, jit0 = c.value(path="host"), c.value(path="jit")
        bat._dispatch(items)
        assert c.value(path="host") - host0 == len(items) - 1
        assert c.value(path="jit") - jit0 == 1
    finally:
        obs.disable()
        bat.close()
    assert bat.stats()["dispatches"] == 1
    for it in items:
        assert it.event.is_set() and it.error is None
        vals = eval_point_rows(it.points).reshape(it.shape + (4,))
        want = _jit_answer(vals, it.budget, it.agg_key, it.multi)
        _same_bytes(it.fit, want)
        if not it.multi:
            assert np.isfinite(it.fit).any() and np.isposinf(it.fit).any()


def test_aggregation_error_reaches_the_waiter(monkeypatch):
    """A failure while aggregating is raised in the waiting request's
    thread, which is not left blocked."""
    def boom(*_):
        raise FloatingPointError("aggregation failed")

    monkeypatch.setattr(batcher_lib, "_host_totals", boom)
    rng = np.random.default_rng(3)
    it = _item("resnet50", env_lib.EnvConfig(platform="cloud"), False, rng)
    layers = layers_to_array(workloads.get_workload("resnet50"))
    N = layers.shape[0]
    bat = CostEvalBatcher(window_ms=0.0)
    try:
        with pytest.raises(FloatingPointError, match="aggregation failed"):
            bat.evaluate(layers, it.points[:, -3].reshape(-1, N),
                         it.points[:, -2].reshape(-1, N),
                         it.points[:, -1].reshape(-1, N), it.agg_key,
                         it.budget)
    finally:
        bat.close()
