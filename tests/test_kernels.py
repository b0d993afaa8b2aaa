"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # degrade property tests to skips, not collection errors
    from hypothesis_stub import given, settings, st

from repro.costmodel import layers_to_array, workloads
from repro.costmodel.layers import LayerSpec
from repro.kernels import ops


def _rand_layers(rng, n):
    out = []
    for _ in range(n):
        t = rng.integers(0, 3)
        if t == 2:
            out.append(LayerSpec.gemm(int(rng.integers(1, 512)),
                                      int(rng.integers(1, 512)),
                                      int(rng.integers(1, 512))))
        elif t == 1:
            c = int(rng.integers(1, 256))
            out.append(LayerSpec.dwconv(c, int(rng.integers(7, 64)),
                                        int(rng.integers(7, 64)), 3, 3))
        else:
            out.append(LayerSpec.conv(int(rng.integers(1, 256)),
                                      int(rng.integers(1, 256)),
                                      int(rng.integers(7, 64)),
                                      int(rng.integers(7, 64)), 3, 3))
    return layers_to_array(out)


@pytest.mark.parametrize("B,N", [(1, 1), (3, 7), (8, 53), (13, 130),
                                 (16, 128)])
def test_costmodel_kernel_shapes(B, N):
    rng = np.random.default_rng(B * 100 + N)
    layers = _rand_layers(rng, N)
    key = jax.random.PRNGKey(B)
    pe = jax.random.randint(key, (B, N), 1, 161).astype(jnp.float32)
    kt = jax.random.randint(jax.random.fold_in(key, 1), (B, N), 1,
                            17).astype(jnp.float32)
    df = jax.random.randint(jax.random.fold_in(key, 2), (B, N), 0,
                            3).astype(jnp.float32)
    got = ops._batched_cost_pallas(layers, pe, kt, df)
    want = ops.batched_cost(layers, pe, kt, df)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-2)


@pytest.mark.parametrize("B,N", [(1, 1), (3, 7), (9, 130), (16, 128)])
def test_costmodel_multi_kernel_shapes(B, N):
    """Per-row-layers kernel (multi-tenant batches) vs its oracle."""
    rng = np.random.default_rng(B * 71 + N)
    layers = np.stack([_rand_layers(rng, N) for _ in range(B)])
    key = jax.random.PRNGKey(B + N)
    pe = jax.random.randint(key, (B, N), 1, 161).astype(jnp.float32)
    kt = jax.random.randint(jax.random.fold_in(key, 1), (B, N), 1,
                            17).astype(jnp.float32)
    df = jax.random.randint(jax.random.fold_in(key, 2), (B, N), 0,
                            3).astype(jnp.float32)
    got = ops._batched_cost_multi_pallas(layers, pe, kt, df)
    want = ops.batched_cost_multi(layers, pe, kt, df)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-2)


def test_costmodel_multi_kernel_matches_broadcast_kernel():
    """With every row carrying the SAME workload, multi == broadcast."""
    rng = np.random.default_rng(0)
    layers = _rand_layers(rng, 9)
    B = 5
    pe = rng.integers(1, 161, size=(B, 9)).astype(np.float32)
    kt = rng.integers(1, 17, size=(B, 9)).astype(np.float32)
    df = rng.integers(0, 3, size=(B, 9)).astype(np.float32)
    multi = ops.batched_cost_multi(np.broadcast_to(layers, (B,) + layers.shape),
                                   pe, kt, df)
    broad = ops.batched_cost(layers, pe, kt, df)
    for m, b in zip(multi, broad):
        np.testing.assert_array_equal(np.asarray(m), np.asarray(b))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), B=st.integers(1, 12),
       N=st.integers(1, 64))
def test_costmodel_kernel_property(seed, B, N):
    rng = np.random.default_rng(seed)
    layers = _rand_layers(rng, N)
    pe = rng.integers(1, 161, (B, N)).astype(np.float32)
    kt = rng.integers(1, 17, (B, N)).astype(np.float32)
    df = rng.integers(0, 3, (B, N)).astype(np.float32)
    got = ops._batched_cost_pallas(layers, pe, kt, df)
    want = ops.batched_cost(layers, pe, kt, df)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-2)


@pytest.mark.parametrize("B,I,H", [(1, 10, 128), (5, 10, 128), (8, 11, 128),
                                   (16, 130, 128), (3, 10, 256)])
def test_lstm_kernel_shapes(B, I, H):
    key = jax.random.PRNGKey(B + I)
    x = jax.random.normal(key, (B, I))
    h = jax.random.normal(jax.random.fold_in(key, 1), (B, H)) * 0.1
    c = jax.random.normal(jax.random.fold_in(key, 2), (B, H)) * 0.1
    wx = jax.random.normal(jax.random.fold_in(key, 3), (I, 4 * H)) * 0.1
    wh = jax.random.normal(jax.random.fold_in(key, 4), (H, 4 * H)) * 0.1
    b = jax.random.normal(jax.random.fold_in(key, 5), (4 * H,)) * 0.1
    h1, c1 = ops._lstm_step_pallas(x, h, c, wx, wh, b)
    h2, c2 = ops.lstm_step(x, h, c, wx, wh, b)
    np.testing.assert_allclose(h1, h2, atol=1e-5)
    np.testing.assert_allclose(c1, c2, atol=1e-5)


@pytest.mark.parametrize("B,Hq,Hkv,D,T", [
    (1, 4, 4, 128, 512), (2, 8, 2, 128, 1024), (2, 16, 2, 128, 2048),
    (1, 8, 1, 256, 512),
])
def test_flash_decode_kernel(B, Hq, Hkv, D, T):
    key = jax.random.PRNGKey(T)
    q = jax.random.normal(key, (B, Hq, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, D))
    o1 = ops._decode_attention_pallas(q, k, v)
    o2 = ops.decode_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)


def test_flash_decode_fallback_unaligned():
    """T not divisible by the tile -> silently uses the oracle path."""
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 4, 128))
    k = jax.random.normal(key, (1, 700, 2, 128))
    v = jax.random.normal(key, (1, 700, 2, 128))
    o = ops._decode_attention_pallas(q, k, v)
    assert o.shape == (1, 4, 128) and bool(jnp.isfinite(o).all())


# ---------------------------------------------------------------------------
# Multi-DNN mix rows: ragged per-model layer counts through the padded path.
# ---------------------------------------------------------------------------
def _ragged_mix_rows(names, tokens=32):
    """Stack >=3 model configs' layer rows, padding ragged tails with
    repeat=0 layers (benign: all four cost outputs are zero)."""
    import dataclasses

    packs = [layers_to_array(workloads.get_workload(n, tokens=tokens))
             for n in names]
    counts = [len(p) for p in packs]
    N = max(counts)
    pad = dataclasses.replace(LayerSpec.gemm(1, 1, 1), repeat=0).as_row()
    rows = np.stack([np.concatenate([p, np.tile(pad, (N - len(p), 1))])
                     for p in packs]).astype(np.float32)
    return rows, counts


MIX_NAMES = ["qwen1p5_0p5b", "whisper_small", "mamba2_130m"]


def test_mix_rows_oracle_wrapper_is_exact():
    """batched_cost_multi off a TPU == cost_eval_multi_ref verbatim:
    the wrapper's transpose/broadcast plumbing is lossless on ragged mix
    rows from three different model configs."""
    from repro.kernels import ref

    rows, counts = _ragged_mix_rows(MIX_NAMES)
    assert len(set(counts)) == 3            # genuinely ragged
    B, N = rows.shape[:2]
    rng = np.random.default_rng(7)
    pe = rng.integers(1, 161, (B, N)).astype(np.float32)
    kt = rng.integers(1, 17, (B, N)).astype(np.float32)
    df = rng.integers(0, 3, (B, N)).astype(np.float32)
    got = ops.batched_cost_multi(rows, pe, kt, df)
    want = ref.cost_eval_multi_ref(rows.transpose(0, 2, 1), pe, kt, df)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("seed", [0, 3])
def test_mix_rows_kernel_matches_oracle(seed):
    """Pallas per-row-layers kernel on the padded ragged mix: within an ulp
    of the oracle on every output (the kernel's fused accumulations round
    once differently); repeat=0 padding rows are exactly zero."""
    rows, counts = _ragged_mix_rows(MIX_NAMES)
    B, N = rows.shape[:2]
    rng = np.random.default_rng(seed)
    pe = rng.integers(1, 161, (B, N)).astype(np.float32)
    kt = rng.integers(1, 17, (B, N)).astype(np.float32)
    df = rng.integers(0, 3, (B, N)).astype(np.float32)
    got = ops._batched_cost_multi_pallas(rows, pe, kt, df)
    want = ops.batched_cost_multi(rows, pe, kt, df)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_allclose(g, w, rtol=5e-7)
        for b, n in enumerate(counts):      # padding stays exactly zero
            assert np.all(g[b, n:] == 0.0)
