"""CPU tests of the readers of the per-layer metrics that come from
``repro.obs`` spans and counters, on snapshots made by hand.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import run  # noqa: E402

NEW = ("engine.jit_s.evals", "batcher.host_us_per_point.sweep",
       "engine.host_ms_per_gen.sweep")


def _hist(**rows):
    """A histogram's snapshot: label value -> (sum, count)."""
    return {"kind": "histogram", "values": {
        k: {"sum": s, "count": n, "max": s, "buckets": {}}
        for k, (s, n) in rows.items()}}


def _reading(obs0, obs1):
    ctx = types.SimpleNamespace(seconds=51.0)
    return run.Reading(ctx, None, None, obs0, obs1, "TPU v5 lite", 1)


def _snap(k):
    """The program's registry after ``k`` units of work."""
    return {
        "repro_jit_seconds": _hist(trace=(0.5 * k, 10 * k),
                                   lower=(0.25 * k, 2 * k),
                                   compile=(1.0 * k, 2 * k)),
        "repro_batcher_phase_seconds": _hist(
            dedup=(0.02 * k, k), lookup=(0.01 * k, k), eval=(5.0 * k, k),
            fill=(0.03 * k, k), aggregate=(0.04 * k, k)),
        "repro_batcher_points": {"kind": "counter",
                                 "values": {"submitted": 10_000.0 * k,
                                            "unique": 900.0 * k}},
        "repro_search_step_seconds": _hist(ga=(0.3 * k, 100 * k),
                                           nsga2=(0.2 * k, 50 * k)),
        "repro_search_eval_wait_seconds": _hist(ga=(0.15 * k, 100 * k),
                                                nsga2=(0.05 * k, 50 * k)),
    }


def test_readers_on_the_window():
    r = _reading(_snap(1), _snap(3))
    read = {m: run.load_metric(m) for m in NEW}
    # Two units of work inside the window.
    assert read["engine.jit_s.evals"](r) == pytest.approx(2 * 1.75)
    # (dedup + lookup + fill + aggregate) s, eval left out, per point.
    assert read["batcher.host_us_per_point.sweep"](r) == pytest.approx(
        1e6 * 2 * 0.10 / 20_000)
    # (step - eval wait) over both engines, per generation.
    assert read["engine.host_ms_per_gen.sweep"](r) == pytest.approx(
        1e3 * 2 * (0.15 + 0.15) / 300)


def test_readers_on_a_program_without_the_counters():
    """A program older than these counters reads nothing; none raises."""
    old = {"repro_batcher_dispatch_seconds": _hist(**{"": (1.0, 10)})}
    for m in NEW:
        assert run.load_metric(m)(_reading(old, old)) is None, m
        assert run.load_metric(m)(_reading({}, {})) is None, m


def test_readers_without_work_in_the_window():
    r = _reading(_snap(2), _snap(2))
    assert run.load_metric("engine.jit_s.evals")(r) == 0.0
    assert run.load_metric("batcher.host_us_per_point.sweep")(r) is None
    assert run.load_metric("engine.host_ms_per_gen.sweep")(r) is None


def test_readers_read_the_registrys_own_snapshot():
    """The hand-made snapshots have the registry's form: the same readings
    from ``repro.obs`` itself."""
    from repro import obs
    from repro.obs import instrument as ins

    obs.disable()
    obs.reset()
    obs.enable(trace=False)
    try:
        s0 = obs.REGISTRY.snapshot()
        for phase, sec in (("trace", 0.5), ("lower", 0.25),
                           ("compile", 1.0)):
            ins.JIT_SECONDS.observe(sec, phase=phase)
        for phase, sec in (("dedup", 0.02), ("lookup", 0.01),
                           ("eval", 5.0), ("fill", 0.03),
                           ("aggregate", 0.04)):
            ins.BATCHER_PHASE_SECONDS.observe(sec, phase=phase)
        ins.BATCHER_POINTS.inc(10_000, kind="submitted")
        for _ in range(4):
            ins.SEARCH_STEP_SECONDS.observe(0.01, engine="ga")
            ins.SEARCH_EVAL_WAIT_SECONDS.observe(0.004, engine="ga")
        r = _reading(s0, obs.REGISTRY.snapshot())
    finally:
        obs.disable()
        obs.reset()
    assert run.load_metric("engine.jit_s.evals")(r) == pytest.approx(1.75)
    assert run.load_metric("batcher.host_us_per_point.sweep")(r) == \
        pytest.approx(10.0)
    assert run.load_metric("engine.host_ms_per_gen.sweep")(r) == \
        pytest.approx(6.0)
