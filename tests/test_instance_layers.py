"""BMM / EXPERTS instance layers and the DeepSeek-V3 decode lowering.

* A BMM row costs X times its one-instance GEMM in latency and energy and
  the same area and power; an EXPERTS row is the converse (area, power and
  energy times X, the same latency) -- on the hard path, the soft path and
  both Pallas kernels.
* Types 0-2 keep the values stored before the instance types existed.
* ``deepseek_v3_ref`` (the plain forward) agrees with itself across the
  absorbed and naive MLA forms, and its matmuls are the lowering's rows.
* The workload runs through ``SearchService`` bit-identically to serial.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, configs, obs
from repro.configs import deepseek_v3
from repro.core import env as env_lib
from repro.costmodel import (BMM, EXPERTS, GEMM, arch_workloads,
                             deepseek_v3_ref, maestro, workloads)
from repro.costmodel.layers import LayerSpec, layers_to_array
from repro.kernels import ops
from repro.obs import instrument
from repro.serving import SearchService, ServiceConfig
from repro.serving.batcher import CostEvalBatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PE_LEVELS = (1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128)


def _rows(rng, n, ltype):
    """n random (M, N, Kg, X, repeat) rows of ``ltype`` and their
    one-instance GEMMs."""
    M = rng.integers(1, 300, n)
    N = rng.integers(1, 40000, n)
    Kg = rng.integers(1, 40000, n)
    X = rng.integers(1, 300, n)
    rep = rng.integers(1, 70, n)
    one = np.ones(n, np.int64)
    rows = np.stack([N, Kg, M, X, one, one, np.full(n, ltype), rep], 1)
    gemm = np.stack([N, Kg, M, one, one, one, np.full(n, GEMM), rep], 1)
    return (rows.astype(np.float32), gemm.astype(np.float32),
            X.astype(np.float32))


def _points(rng, n):
    return (rng.choice(PE_LEVELS, n).astype(np.float32),
            rng.integers(1, 13, n).astype(np.float32),
            rng.integers(0, 3, n).astype(np.float32))


def _scales(ltype, X):
    """(latency, energy, area, power) factors of an instance row."""
    one = np.ones_like(X)
    return ((X, X, one, one) if ltype == BMM else (one, X, X, X))


@pytest.mark.parametrize("ltype", [BMM, EXPERTS], ids=["bmm", "experts"])
@pytest.mark.parametrize("path", ["hard", "soft"])
def test_instance_row_scales_its_one_instance_gemm(ltype, path):
    rng = np.random.default_rng(11 + ltype)
    rows, gemm, X = _rows(rng, 512, ltype)
    pe, kt, df = _points(rng, 512)
    if path == "hard":
        got = maestro.evaluate(rows, pe, kt, df)
        base = maestro.evaluate(gemm, pe, kt, df)
    else:
        w = np.eye(3, dtype=np.float32)[df.astype(int)]
        got = maestro.soft_evaluate(rows, pe * 1.07, kt * 0.93, w, 0.05)
        base = maestro.soft_evaluate(gemm, pe * 1.07, kt * 0.93, w, 0.05)
    for g, b, s in zip(got[:4], base[:4], _scales(ltype, X)):
        # The instance factor is the last multiply of each output, in
        # float32 on both sides: equal to the bit.
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(b) * s)
    np.testing.assert_array_equal(np.asarray(got.macs),
                                  np.asarray(base.macs) * X)
    # Buffers and utilization are per instance.
    for f in ("l1_bytes", "l2_bytes", "util"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(base, f)))


def test_layerspec_macs_count_instances():
    b = LayerSpec.bmm(128, 32768, 576, 64, repeat=61)
    e = LayerSpec.experts(2, 4096, 7168, 256, repeat=58)
    assert b.macs() == 128 * 32768 * 576 * 64 * 61
    assert e.macs() == 2 * 4096 * 7168 * 256 * 58
    assert (b.type, b.X, e.type, e.X) == (BMM, 64, EXPERTS, 256)


@pytest.mark.parametrize("kernel", ["broadcast", "multi"])
def test_kernels_match_oracle_on_instance_rows(kernel):
    rng = np.random.default_rng(5)
    a, _, _ = _rows(rng, 70, BMM)
    b, g, _ = _rows(rng, 70, EXPERTS)
    layers = np.concatenate([a, b, g])                    # (210, 8)
    B, N = 9, layers.shape[0]
    pe, kt, df = (x.reshape(B, N) for x in _points(rng, B * N))
    if kernel == "broadcast":
        got = ops._batched_cost_pallas(layers, pe, kt, df)
        want = ops.batched_cost(layers, pe, kt, df)
    else:
        per_row = np.stack([layers[rng.permutation(N)] for _ in range(B)])
        got = ops._batched_cost_multi_pallas(per_row, pe, kt, df)
        want = ops.batched_cost_multi(per_row, pe, kt, df)
    for gv, wv in zip(got, want):
        np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                                   rtol=1e-6, atol=0)


# Values the model gave before the instance types existed: (row, (pe, kt,
# df)) -> hard (lat, en, area, pw), soft at tau = 1 (pe x 1.1, kt x 0.9,
# one-hot dataflow).
BEFORE = {
    ((64, 64, 58, 58, 3, 3, 0, 1), (16.0, 4.0, 0)): (
        (7225368.0, 567609.875, 115200.0, 24.6560001373291),
        (5850485.0, 566693.4375, 119680.0, 26.487998962402344)),
    ((64, 64, 58, 58, 3, 3, 0, 1), (128.0, 12.0, 2)): (
        (1161247.375, 730530.375, 716800.0, 178.8159942626953),
        (832929.1875, 681989.6875, 754688.0, 193.6563262939453)),
    ((144, 144, 58, 58, 3, 3, 1, 1), (48.0, 7.0, 1)): (
        (148202.921875, 211003.859375, 259200.0, 66.19200134277344),
        (70524.21875, 211106.8125, 270336.0, 71.48063659667969)),
    ((1000, 1280, 1, 1, 1, 1, 2, 1), (16.0, 4.0, 0)): (
        (81944.0, 273739.71875, 51200.0, 18.895999908447266),
        (81428.1640625, 273408.3125, 54912.0, 20.65888023376465)),
    ((512, 256, 128, 1, 1, 1, 2, 12), (48.0, 7.0, 1)): (
        (4774211.0, 7723824.5, 2188800.0, 711.3599853515625),
        (3784429.0, 7076068.0, 2318976.0, 774.5125732421875)),
    ((512, 256, 128, 1, 1, 1, 2, 12), (128.0, 12.0, 2)): (
        (1585527.75, 1390297.5, 7372800.0, 2035.2000732421875),
        (1597266.25, 1395531.25, 7704576.0, 2202.224609375)),
}


@pytest.mark.parametrize("row,point", sorted(BEFORE))
def test_types_0_to_2_keep_their_values(row, point):
    pe, kt, df = point
    hard, soft = BEFORE[(row, point)]
    r = np.asarray(row, np.float32)
    o = maestro.evaluate(r, pe, kt, df)
    so = maestro.soft_evaluate(r, pe * 1.1, kt * 0.9,
                               np.eye(3, dtype=np.float32)[df], 1.0)
    f32 = lambda out: tuple(np.float32(x) for x in out[:4])
    assert f32(o) == tuple(np.float32(v) for v in hard)
    assert f32(so) == tuple(np.float32(v) for v in soft)


# ---------------------------------------------------------------------------
# DeepSeek-V3: config, lowering, reference.
# ---------------------------------------------------------------------------
def test_deepseek_v3_config_is_the_published_one():
    cfg = configs.get("deepseek_v3")
    assert cfg.family == "mla_moe"
    # 671B without the MTP layer and the norms (the report's 671B total).
    assert abs(cfg.param_count() / 671e9 - 1) < 0.002
    assert "deepseek_v3" not in configs.ARCH_IDS


def test_decode_workload_is_registered_and_prefill_refused():
    assert "deepseek_v3_decode" in workloads.workload_names()
    wl = workloads.get_workload("deepseek_v3_decode")
    assert len(wl) == 16
    assert [l.type for l in wl].count(BMM) == 4
    assert [l.type for l in wl].count(EXPERTS) == 2
    # mla_moe lowers no prefill: asked for tokens, it lowers a decode step
    # of that many requests, each scoring its own cache (X = tokens).
    score = {l.name: l for l in arch_workloads.lower_arch(
        "deepseek_v3", tokens=8, ctx=100)}["score"]
    assert (score.type, score.X, score.K) == (BMM, 8, 100)
    req = api.SearchRequest(workload="deepseek_v3_decode")
    assert req.num_layers == 16


def _lowered(cfg, batch, ctx):
    kind = {GEMM: "gemm", BMM: "bmm", EXPERTS: "experts"}
    return [(l.name, l.Y, l.K, l.C, l.X, kind[l.type]) for l in
            arch_workloads.lower_config(cfg, tokens=batch, ctx=ctx)]


def test_absorbed_mla_equals_naive_and_matmuls_are_the_rows():
    cfg = deepseek_v3.smoke()
    B, T = 8, 24
    p = deepseek_v3_ref.init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, cfg.d_model))
    caches = deepseek_v3_ref.random_caches(cfg, jax.random.PRNGKey(2), B, T)
    absorbed, rows = deepseek_v3_ref.decode_step(cfg, p, x, caches, True)
    naive, _ = deepseek_v3_ref.decode_step(cfg, p, x, caches, False)
    absorbed, naive = np.asarray(absorbed), np.asarray(naive)
    # Both forms are float32 at "highest" precision and differ only in how
    # the sums over the head and latent widths associate: a few ulps of the
    # logits' scale (4e-7 measured), so 1e-5 of the largest logit.
    # Per-element relative error is no measure here: some logits sit near 0.
    scale = np.max(np.abs(naive))
    np.testing.assert_allclose(absorbed, naive, rtol=0, atol=1e-5 * scale)
    assert [tuple(r) for r in rows] == _lowered(cfg, B, T)


def test_published_matmuls_are_the_rows_by_shape():
    cfg = deepseek_v3.CONFIG
    B, T = 64, 32768
    f32 = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)
    params = {k: f32(s) for k, s in
              deepseek_v3_ref.param_shapes(cfg).items()}
    caches = [(f32((B, T - 1, cfg.kv_lora_rank)),
               f32((B, T - 1, cfg.qk_rope_head_dim)))] * 2
    seen = []

    def step(p, x, c):
        logits, rows = deepseek_v3_ref.decode_step(cfg, p, x, c)
        seen.append(rows)
        return logits

    out = jax.eval_shape(step, params, f32((B, cfg.d_model)), caches)
    assert out.shape == (B, cfg.vocab_size)
    assert [tuple(r) for r in seen[0]] == _lowered(cfg, B, T)
    assert layers_to_array(workloads.get_workload("deepseek_v3_decode")) \
        .tolist() == layers_to_array(arch_workloads.lower_config(
            cfg, tokens=B, ctx=T)).tolist()


def test_chipbench_config_holds_the_workload():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "deepseek_v3-decode.json")) as f:
        cfg = json.load(f)
    want = layers_to_array(workloads.get_workload(cfg["workload"]))
    np.testing.assert_array_equal(np.asarray(cfg["layers"]), want)


# ---------------------------------------------------------------------------
# The normal path: batcher counter, service parity.
# ---------------------------------------------------------------------------
def test_batcher_counts_fresh_points_by_type():
    obs.disable()
    obs.reset()
    obs.enable(trace=False)
    b = CostEvalBatcher(window_ms=0.0)
    try:
        layers = layers_to_array(workloads.get_workload("deepseek_v3_decode"))
        rng = np.random.default_rng(0)
        pe = rng.choice(PE_LEVELS, (6, 16)).astype(np.float32)
        kt = rng.integers(1, 13, (6, 16)).astype(np.float32)
        b.evaluate(layers, pe, kt, 0, env_lib.EnvConfig(), 1e30)
        b.evaluate(layers, pe, kt, 0, env_lib.EnvConfig(), 1e30)  # cached
        c = instrument.BATCHER_FRESH_POINTS
        fresh = instrument.BATCHER_POINTS.value(kind="fresh")
        by = {t: c.value(ltype=t) for t in ("gemm", "bmm", "experts")}
        assert sum(by.values()) == fresh == b.stats()["fresh_points"]
        # Rows 3-6 of the table are the BMMs; a point is a (row, pe, kt).
        assert by["bmm"] == len({(j, pe[i, j], kt[i, j])
                                 for i in range(6) for j in range(3, 7)})
        assert by["experts"] > 0 and c.value(ltype="conv") == 0
    finally:
        b.close()
        obs.disable()
        obs.reset()


@pytest.mark.parametrize("method,options", [
    ("ga", {"population": 20}), ("nsga2", {"population": 16}),
    ("reinforce", {"episodes_per_epoch": 1})])
def test_decode_workload_through_service_equals_serial(method, options):
    ecfg = env_lib.EnvConfig(platform="cloud", dataflow=1)
    req = lambda: api.SearchRequest(
        workload="deepseek_v3_decode", env=ecfg, eps=80, seed=5,
        method=method, options=dict(options))
    want = api.run_search(req())
    svc = SearchService(ServiceConfig(max_workers=2))
    try:
        got = svc.submit(req()).result(timeout=300)
    finally:
        svc.close()
    assert np.isfinite(want.best_value)
    assert got.best_value == want.best_value
    assert got.history.tobytes() == want.history.tobytes()
    np.testing.assert_array_equal(got.pe, want.pe)
    np.testing.assert_array_equal(got.kt, want.kt)
