"""CPU tests of the two cells beside the first benchmark's: the DeepSeek-V3
decode sweep (instance rows: BMM, EXPERTS) and the four-chip fanout.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The whole-run tests drive shrunk runs through ``chipbench.run.execute``
with the chip check skipped: the sound program must read ``correct`` true
and the control (the reference one precision lower) false.  The fanout
cell needs four devices, so its runs go to a child process with four
virtual CPU devices.
"""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import drivers, reference, reference_inst, run  # noqa: E402

LLM = "deepseek_v3-decode.llm_sweep_cold"
FANOUT = "mobilenet_v2-iot.fanout_x4"
METRIC = "kernels.instance_rows_pct.llm"


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_new_cells_have_their_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [w["name"] for w in bench["workloads"]]
    assert names[-2:] == [LLM, FANOUT]
    for name in (LLM, FANOUT):
        _, cell, config, mix, limits = run.load_cell(name)
        assert drivers.load(mix["driver"])
        assert limits["best_gap"] == 1e-5
    assert run.load_cell(LLM)[4]["point_gap"] == 1e-5
    assert run.load_cell(FANOUT)[4]["rl_value_gap"] == 1e-5
    assert run.load_cell(FANOUT)[1]["chips"] == 4
    assert callable(run.load_metric(METRIC))


def test_config_keeps_every_published_number():
    """The catalog's DeepSeek-V3 config.json numbers, as published."""
    cfg = _config("deepseek_v3-decode")
    published = {
        "hidden_size": 7168, "num_hidden_layers": 61,
        "first_k_dense_replace": 3, "num_attention_heads": 128,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "n_routed_experts": 256, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "n_group": 8, "topk_group": 4,
        "vocab_size": 129280, "num_nextn_predict_layers": 1}
    for k, v in published.items():
        assert cfg[k] == v, k
    layers = np.asarray(cfg["layers"])
    assert layers.shape == (16, 8)
    assert sorted(set(layers[:, 6])) == [2, 3, 4]


def test_reference_inst_equals_program_model_on_all_five_types():
    from repro.costmodel import maestro
    rows = np.concatenate([np.asarray(_config(c)["layers"], np.float32)
                           for c in ("mobilenet_v2-iot", "resnet50-cloud",
                                     "deepseek_v3-decode")])
    assert sorted(set(rows[:, 6])) == [0, 1, 2, 3, 4]
    rng = np.random.default_rng(0)
    n = 4096
    r = rows[rng.integers(0, len(rows), n)]
    pe = rng.choice(reference.PE_LEVELS[12], n).astype(np.float32)
    kt = rng.integers(1, 13, n).astype(np.float32)
    df = rng.integers(0, 3, n).astype(np.float32)
    got = reference_inst.point_costs(r, pe, kt, df)
    out = maestro.evaluate(r, pe, kt, df)
    want = np.stack([np.asarray(out.latency), np.asarray(out.energy),
                     np.asarray(out.area), np.asarray(out.power)], -1)
    # Two float32 programs fused differently may differ in the last ulp;
    # the instance factor is one more float32 multiply on each side.
    np.testing.assert_allclose(got, want, rtol=4e-7, atol=0)
    assert np.mean(got == want) > 0.95


def test_reference_inst_budget_matches_program_env():
    from repro.core import env as env_lib
    from repro.costmodel import workloads
    from repro.costmodel.layers import layers_to_array
    layers = workloads.get_workload("deepseek_v3_decode")
    for df in (0, 1, 2):
        env = env_lib.make_env(layers, env_lib.EnvConfig(platform="cloud",
                                                         dataflow=df))
        b = reference_inst.budget(layers_to_array(layers), df,
                                  env_lib.PLATFORM_FRACTIONS["cloud"])
        assert abs(float(env.budget) - b) <= 1e-6 * b


def test_fanout_marks_sum_the_shards():
    """Each shard's Trial counts its own evaluations; the mark a request
    records is the sum of every shard's latest count."""
    from repro.api.types import Trial
    from chipbench.drivers.fanout_closed import FanoutClosed
    ctx = types.SimpleNamespace(config=_config("mobilenet_v2-iot"),
                                mix={"progress_every": 200})
    drv = FanoutClosed(ctx)
    seen = []
    spec = {"objective": "latency", "platform": "iot", "dataflow": "dla",
            "eps": 400, "seed": 1, "method": "fanout", "options": {}}
    req = drv.request(spec, on_progress=lambda t: seen.append(t.step))
    for shard, step in ((0, 200), (1, 200), (0, 400), (2, 200), (1, 400)):
        req.on_progress(Trial(step, 1.0, 1.0, shard=shard))
    assert seen == [200, 400, 600, 800, 1000]


def test_fanout_answer_without_shard_histories_is_not_correct():
    """An answer whose shards' epochs were not kept cannot be replayed, so
    it reads as an infinite gap rather than passing unchecked."""
    from chipbench.drivers.fanout_closed import FanoutClosed
    ctx = types.SimpleNamespace(config=_config("mobilenet_v2-iot"),
                                mix={"progress_every": 200})
    drv = FanoutClosed(ctx)
    assert drv.rl_value_gap(False) == float("inf")
    spec = {"objective": "latency", "platform": "iot", "dataflow": "dla",
            "seed": 3}
    drv.outcomes.append(drivers.Outcome(spec, 1.0, [1], [1], [0]))
    drv.shard_histories[4] = [{"mean_value": [1.0]}]
    assert drv.rl_value_gap(False) == float("inf")


def _snap(fresh):
    return {"repro_batcher_fresh_points": {"kind": "counter",
                                           "values": dict(fresh)}}


def test_instance_rows_reader():
    read = run.load_metric(METRIC)
    ctx = types.SimpleNamespace(seconds=51.0)
    r = run.Reading(ctx, None, None,
                    _snap({"gemm": 100.0, "bmm": 10.0}),
                    _snap({"gemm": 400.0, "bmm": 160.0, "experts": 40.0}), "",
                    1)
    assert read(r) == pytest.approx(100.0 * 190.0 / 490.0)
    # A program without the counter, or a window without fresh points.
    assert read(run.Reading(ctx, None, None, {}, {}, "", 1)) is None
    same = _snap({"gemm": 5.0})
    assert read(run.Reading(ctx, None, None, same, same, "", 1)) is None


# -- whole shrunk runs ------------------------------------------------------
SHRUNK = """
import io, json, sys
sys.path[:0] = [{root!r} + "/src", {root!r}]
from chipbench import run
load = run.load_cell
def small(name):
    bench, cell, config, mix, limits = load(name)
    config["eps"] = 400
    if "kernel_rows_max" in mix.get("warmup", {{}}):
        mix["warmup"]["kernel_rows_max"] = 256
    return bench, cell, config, mix, limits
run.load_cell = small
out = io.StringIO()
assert run.execute({cell!r}, 2_147_483_777, {seconds}, False,
                   require_chip=False, control={control}, out=out) == 0
print(out.getvalue().strip().splitlines()[-1])
"""


def _run(cell, control, seconds, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{devices}").strip()
    code = SHRUNK.format(root=ROOT, cell=cell, seconds=seconds,
                         control=control)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,devices", [(LLM, 1), (FANOUT, 4)])
@pytest.mark.parametrize("control", [False, True], ids=["sound", "control"])
def test_shrunk_run(cell, devices, control):
    line = _run(cell, control, 6.0, devices)
    assert line["correct"] is (not control), line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
