"""CPU tests of the benchmark's own pieces.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The slow ones drive whole (shrunk) runs of each cell through
``chipbench.run.execute`` with the chip check skipped: the sound program
must come out correct, the control (the reference one precision lower) and
each fault planted in the timed path must come out not correct.
"""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import drivers, reference, run, traffic, work  # noqa: E402

CELLS = ["mobilenet_v2-iot.rl_global", "resnet50-cloud.ga_sweep_cold"]


def test_every_cell_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]] == CELLS
    for name in CELLS:
        _, _, _, mix, limits = run.load_cell(name)
        assert drivers.load(mix["driver"])
        assert "best_gap" in limits
    for m in bench["per_layer"]:
        assert callable(run.load_metric(m["name"]))


# -- traffic ----------------------------------------------------------------
@pytest.mark.parametrize("name", CELLS)
def test_traffic_is_deterministic_from_seed(name):
    _, _, config, mix, _ = run.load_cell(name)
    seed = 3_000_000_019
    reqs = lambda s: [traffic.closed_request(mix, config, s, c, k)
                      for c in range(mix["clients"]) for k in range(6)]
    a, b, c = reqs(seed), reqs(seed), reqs(seed + 1)
    assert a == b and a != c
    strip = lambda rs: [{k: v for k, v in r.items() if k != "seed"}
                        for r in rs]
    assert strip(a) == strip(c)
    assert all(0 <= r["seed"] < 2 ** 31 - 1 for r in a)


def test_work_in_window_counts_the_share_inside():
    # A search submitted at 0 streams 200 evals at 2, 4 and 6 s; a window
    # over [3, 5] holds half of each of the chunks ending at 4 and 6.
    marks = [[(0.0, 0), (2.0, 200), (4.0, 400), (6.0, 600)]]
    assert drivers.work_in_window(marks, 3.0, 5.0) == pytest.approx(200.0)
    assert drivers.work_in_window(marks, 0.0, 6.0) == pytest.approx(600.0)
    assert drivers.work_in_window(marks, 6.5, 9.0) == 0.0
    # Two marks at one instant count whole, where the instant is inside.
    same = [[(0.0, 0), (1.0, 100), (1.0, 150)]]
    assert drivers.work_in_window(same, 0.5, 2.0) == pytest.approx(100.0)


# -- ops and bytes ----------------------------------------------------------
def test_work_at_cell_shapes():
    # LSTM cell at one episode row, observation width 10, 128 hidden units.
    assert work.lstm_step_flops(1, 10, 128) == 2 * 138 * 512 == 141_312
    assert work.lstm_step_bytes(1, 10, 128) == ((10 + 128 + 1) * 512
                                                + 10 + 512) * 4 == 286_760
    # One REINFORCE episode of mobilenet_v2 (53 layers, 12 levels).
    assert work.policy_step_flops(10, 128, 12) == 141_312 + 2 * 2 * 128 * 12
    assert work.policy_episode_flops(53, 10, 128, 12) == 3 * 53 * 147_456
    # One cost-model point: 8 fields + 3 inputs in, 4 outputs out, f32.
    assert work.cost_eval_bytes(1) == 60
    assert work.cost_eval_bytes(9_072) == 544_320


# -- the frozen reference ---------------------------------------------------
@pytest.mark.parametrize("config", ["mobilenet_v2-iot", "resnet50-cloud"])
def test_reference_equals_program_model(config):
    from repro.costmodel import maestro
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      config + ".json")))
    layers = np.asarray(cfg["layers"], np.float32)
    rng = np.random.default_rng(0)
    n = 4096
    rows = layers[rng.integers(0, len(layers), n)]
    pe = rng.choice(reference.PE_LEVELS[12], n).astype(np.float32)
    kt = rng.integers(1, 13, n).astype(np.float32)
    df = rng.integers(0, 3, n).astype(np.float32)
    got = reference.point_costs(rows, pe, kt, df)
    out = maestro.evaluate(rows, pe, kt, df)
    want = np.stack([np.asarray(out.latency), np.asarray(out.energy),
                     np.asarray(out.area), np.asarray(out.power)], -1)
    # Two float32 programs fused differently may differ in the last ulp.
    np.testing.assert_allclose(got, want, rtol=4e-7, atol=0)
    assert np.mean(got == want) > 0.95


def test_reference_layers_are_the_programs():
    from repro.costmodel import workloads
    from repro.costmodel.layers import layers_to_array
    for config in ("mobilenet_v2-iot", "resnet50-cloud"):
        cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                          config + ".json")))
        want = layers_to_array(workloads.get_workload(cfg["workload"]))
        np.testing.assert_array_equal(np.asarray(cfg["layers"]), want)


def test_reference_budget_matches_program_env():
    from repro.core import env as env_lib
    from repro.costmodel import workloads
    for wl, platform, df in (("mobilenet_v2", "iot", 0),
                             ("resnet50", "cloud", 2)):
        layers = workloads.get_workload(wl)
        env = env_lib.make_env(layers, env_lib.EnvConfig(
            platform=platform, dataflow=df))
        from repro.costmodel.layers import layers_to_array
        b = reference.budget(layers_to_array(layers), df,
                             env_lib.PLATFORM_FRACTIONS[platform])
        assert abs(float(env.budget) - b) <= 1e-6 * b


def test_control_precision_reads_far_off():
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      "resnet50-cloud.json")))
    layers = np.asarray(cfg["layers"], np.float32)
    rng = np.random.default_rng(1)
    n = 2048
    rows = layers[rng.integers(0, len(layers), n)]
    args = (rows, rng.choice(reference.PE_LEVELS[12], n),
            rng.integers(1, 13, n), rng.integers(0, 3, n))
    import jax.numpy as jnp
    f32 = reference.point_costs(*args)
    bf16 = reference.point_costs(*args, dtype=jnp.bfloat16)
    gap = np.max(np.abs(bf16 - f32) / np.abs(f32))
    assert gap > 1e-3


# -- the trace reduction ----------------------------------------------------
def test_trace_reduction_reads_recorded_trace():
    from chipbench import trace as trace_lib
    path = os.path.join(ROOT, "chipbench", "data", "recorded.xplane.pb")
    tr = trace_lib.Trace(path)
    expect = json.load(open(os.path.join(ROOT, "chipbench", "data",
                                         "recorded.json")))
    assert list(tr.ops) == expect["devices"]
    assert tr.window_s == pytest.approx(expect["window_s"], rel=1e-9)
    busy = tr.busy_s()
    for d, v in expect["busy_s"].items():
        assert busy[d] == pytest.approx(v, rel=1e-9)
    assert 0.0 < tr.idle_pct() < 100.0
    assert tr.op_count(expect["kernel_pattern"]) == expect["kernel_calls"]
    assert tr.op_seconds(expect["kernel_pattern"]) == pytest.approx(
        expect["kernel_s"], rel=1e-9)
    ann = tr.annotations("chipbench.eval_points")
    assert sum(int(n.split(":")[1]) for *_, n in ann) == expect["points"]
    assert 0.0 < tr.busy_under("chipbench.eval_points") <= tr.window_s
    assert len(tr.top_ops(10)) == min(10, expect["distinct_ops"])
    gaps = tr.idle_gaps(10)
    assert gaps and all(g[1] > 0 for g in gaps)


def test_interval_arithmetic():
    from chipbench import trace as trace_lib
    u = trace_lib.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)]
    assert trace_lib.length(trace_lib.clip(u, 1, 5.5)) == 2.5
    assert trace_lib.intersect(u, [(2, 5.5)]) == [(2, 3), (5, 5.5)]


# -- the command ------------------------------------------------------------
def test_run_without_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", "2147483999", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


# -- whole shrunk runs: sound, control, faults ------------------------------
def _shrunk(monkeypatch):
    def small(name):
        bench, cell, config, mix, limits = load(name)
        config["eps"] = 400
        if "kernel_rows_max" in mix.get("warmup", {}):
            mix["warmup"]["kernel_rows_max"] = 256
        return bench, cell, config, mix, limits

    load = run.load_cell
    monkeypatch.setattr(run, "load_cell", small)


def _run(cell, seed=2_147_483_777, seconds=6.0, control=False):
    out = io.StringIO()
    assert run.execute(cell, seed, seconds, False, require_chip=False,
                       control=control, out=out) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, monkeypatch):
    _shrunk(monkeypatch)
    line = _run(cell)
    assert line["correct"] is True, line["checks"]


def _perturb_costs(monkeypatch, frac_rows=None, scale=1.0 + 1e-3):
    """Fault in the batcher's kernel call: costs altered where produced, or
    (``frac_rows``) the trailing rows of each call left out."""
    from repro.serving import batcher
    orig = batcher.CostEvalBatcher._eval_points

    def bad(self, rows):
        out = np.array(orig(self, rows), copy=True)
        if frac_rows is None:
            out[:, 0] *= scale
        else:
            out[int(len(out) * frac_rows):] = 0.0
        return out

    monkeypatch.setattr(batcher.CostEvalBatcher, "_eval_points", bad)


def test_fault_answer_altered_is_refused(monkeypatch):
    _shrunk(monkeypatch)
    _perturb_costs(monkeypatch)
    assert _run(CELLS[1])["correct"] is False


def test_fault_half_the_batch_left_out_is_refused(monkeypatch):
    _shrunk(monkeypatch)
    _perturb_costs(monkeypatch, frac_rows=0.5)
    assert _run(CELLS[1])["correct"] is False


def test_fault_rl_answer_altered_is_refused(monkeypatch):
    from repro.core import reinforce
    _shrunk(monkeypatch)
    orig = reinforce.solution_arrays

    def bad(state, env):
        pe, kt, df = orig(state, env)
        return pe.at[0].set(pe[0] * 2.0 if pe[0] < 64 else 1.0), kt, df

    monkeypatch.setattr(reinforce, "solution_arrays", bad)
    assert _run(CELLS[0])["correct"] is False


def test_fault_rl_state_unchanged_is_refused(monkeypatch):
    from repro.training import optim
    _shrunk(monkeypatch)
    orig = optim.Adam.update

    def stuck(self, grads, state, params):
        _, new_state = orig(self, grads, state, params)
        return params, new_state

    monkeypatch.setattr(optim.Adam, "update", stuck)
    assert _run(CELLS[0])["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_refused(cell, monkeypatch):
    _shrunk(monkeypatch)
    line = _run(cell, control=True)
    assert line["correct"] is False, line["checks"]
    assert any(not run._holds(c["value"] if c["value"] is not None
                              else float("inf"), c["limit"], c["holds"])
               for c in line["checks"].values())
