"""The batcher's point dedup: rows are the same point iff their bytes are.

``dedup_point_rows`` must find exactly the unique rows a float
``np.unique(axis=0)`` finds, rebuild every submitted row from them, and
hand the memo cache the same ``row.tobytes()`` keys as before -- so cache
entries, in memory or in a ``PersistentCostCache`` shard, keep hitting.
"""
import numpy as np
import pytest

from repro.core import env as env_lib
from repro.costmodel import dataflows as dfl
from repro.costmodel import layers_to_array, workloads
from repro.serving import CostEvalBatcher, PersistentCostCache
from repro.serving.batcher import (ROW_WIDTH, dedup_point_rows,
                                   eval_point_rows, pack_point_rows)

ECFG = env_lib.EnvConfig(platform="cloud")
WORKLOADS = ("mobilenet_v2", "resnet50", "deepseek_v3_decode")


def _mixed_rows(name, seed=0):
    """Several items' packed rows, as one fused dispatch concatenates
    them: each item a batch of genomes drawn with repeats from a small
    pool, under each of the three dataflows (dla is 0.0)."""
    layers = layers_to_array(workloads.get_workload(name))
    N = layers.shape[0]
    pe_t = dfl.pe_levels(12).astype(np.float32)
    kt_t = dfl.kt_levels(12).astype(np.float32)
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, 12, size=(24, N, 2))
    items = []
    for df in (0.0, 1.0, 2.0, 0.0):
        g = pool[rng.integers(0, len(pool), size=40)]
        items.append(pack_point_rows(layers, pe_t[g[..., 0]],
                                     kt_t[g[..., 1]], np.float32(df)))
    return np.concatenate(items, axis=0)


def _check_dedup(rows):
    uniq, inv, keys = dedup_point_rows(rows)
    assert uniq.dtype == np.float32 and uniq.shape[1] == ROW_WIDTH
    assert inv.shape == (rows.shape[0],)
    assert uniq[inv].tobytes() == np.ascontiguousarray(rows).tobytes()
    assert keys == [r.tobytes() for r in uniq]
    want = np.unique(rows, axis=0)
    assert len(keys) == len(want)
    assert set(keys) == {r.tobytes() for r in want}
    return uniq, inv, keys


@pytest.mark.parametrize("name", WORKLOADS)
def test_dedup_matches_float_unique(name):
    rows = _mixed_rows(name)
    uniq, _, _ = _check_dedup(rows)
    assert len(uniq) < len(rows)            # the repeats collapsed


@pytest.mark.parametrize("case", ["one_row", "all_same", "strided"])
def test_dedup_edge_shapes(case):
    rows = _mixed_rows("resnet50")
    if case == "one_row":
        rows = rows[:1]
    elif case == "all_same":
        rows = np.repeat(rows[:1], 7, axis=0)
    else:                                   # a non-contiguous view
        rows = rows[::3]
    uniq, _, _ = _check_dedup(rows)
    if case != "strided":
        assert len(uniq) == 1


def test_batcher_unique_count_matches_float_unique():
    """A fused dispatch's unique-point count is the float np.unique's."""
    layers = layers_to_array(workloads.get_workload("deepseek_v3_decode"))
    rows = _mixed_rows("deepseek_v3_decode", seed=1)
    N = layers.shape[0]
    pe = rows[:, ROW_WIDTH - 3].reshape(-1, N)
    kt = rows[:, ROW_WIDTH - 2].reshape(-1, N)
    df = rows[:, ROW_WIDTH - 1].reshape(-1, N)
    bat = CostEvalBatcher(window_ms=0.0)
    try:
        bat.evaluate(layers, pe, kt, df, ECFG, np.float32(1e30))
        s = bat.stats()
        assert s["points"] == len(rows)
        assert s["unique_points"] == len(np.unique(rows, axis=0))
        assert s["fresh_points"] == s["unique_points"]
    finally:
        bat.close()


def test_persistent_shard_with_float_unique_keys_still_hits(tmp_path):
    """A shard written under the keys the float np.unique path made
    (``row.tobytes()`` of its unique rows) serves a later batcher fully:
    the key format is unchanged."""
    layers = layers_to_array(workloads.get_workload("mobilenet_v2"))
    rows = _mixed_rows("mobilenet_v2", seed=2)
    old_uniq = np.unique(rows, axis=0)
    d = str(tmp_path / "cache")
    c = PersistentCostCache(d, flush_every=10 ** 6)
    vals = eval_point_rows(old_uniq)
    c.put_many([u.tobytes() for u in old_uniq], [v.copy() for v in vals])
    c.close()

    c2 = PersistentCostCache(d)
    assert len(c2) == len(old_uniq)
    N = layers.shape[0]
    bat = CostEvalBatcher(cache=c2, window_ms=0.0)
    try:
        got = bat.evaluate_costs(
            layers, rows[:, ROW_WIDTH - 3].reshape(-1, N),
            rows[:, ROW_WIDTH - 2].reshape(-1, N),
            rows[:, ROW_WIDTH - 1].reshape(-1, N), ECFG, np.float32(1e30))
        assert c2.misses == 0 and c2.hits == len(old_uniq)
        assert bat.stats()["fresh_points"] == 0
        assert np.isfinite(got).all()
    finally:
        bat.close()
        c2.close()
