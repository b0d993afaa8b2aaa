"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the ground truth a kernel is validated against (allclose
over shape/dtype sweeps in tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.costmodel import maestro
from repro.costmodel.layers import NUM_FIELDS


def cost_eval_ref(layers_t, pe, kt, df):
    """Oracle for kernels.costmodel_eval: (NUM_FIELDS, N) x (B, N) -> 4x(B, N).

    Identical math to the kernel: both call maestro.core_cost, which runs on
    the shared *hard* plateau-op primitives (costmodel/primitives.py) -- the
    single source of truth for the dataflow-term math.  This version simply
    broadcasts without any tiling.
    """
    fields = [layers_t[i][None, :] for i in range(NUM_FIELDS)]
    out = maestro.core_cost(*fields, pe, kt, df)
    return out.latency, out.energy, out.area, out.power


def cost_eval_multi_ref(layers_bt, pe, kt, df):
    """Oracle for the per-row-layers kernel: (B, NUM_FIELDS, N) x (B, N).

    Every batch row carries its own layer descriptor (the cross-request
    batcher's multi-tenant shape); plain broadcasting, no tiling.
    """
    fields = [layers_bt[:, i, :] for i in range(NUM_FIELDS)]
    out = maestro.core_cost(*fields, pe, kt, df)
    return out.latency, out.energy, out.area, out.power


def lstm_cell_ref(x, h, c, wx, wh, b):
    """Oracle for kernels.lstm_cell: one fused LSTM step.

    x: (B, I), h/c: (B, H), wx: (I, 4H), wh: (H, 4H), b: (4H,).
    Gate order: i, f, g, o.  Returns (h', c').
    """
    hi = jax.lax.Precision.HIGHEST     # f32 math on every backend
    gates = jnp.dot(x, wx, precision=hi) + jnp.dot(h, wh, precision=hi) + b
    H = h.shape[-1]
    i = _sig(gates[..., 0 * H:1 * H])
    f = _sig(gates[..., 1 * H:2 * H])
    g = jnp.tanh(gates[..., 2 * H:3 * H])
    o = _sig(gates[..., 3 * H:4 * H])
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    return h_new, c_new


def _sig(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def flash_decode_ref(q, k, v):
    """Oracle for kernels.flash_decode: single-token GQA attention.

    q: (B, Hq, D), k/v: (B, T, Hkv, D) with Hq % Hkv == 0.
    Returns (B, Hq, D).
    """
    B, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, D)
    logits = jnp.einsum("bhgd,bthd->bhgt", qg, k) / jnp.sqrt(D).astype(q.dtype)
    w = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    w = w / w.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgt,bthd->bhgd", w, v)
    return out.reshape(B, Hq, D)
